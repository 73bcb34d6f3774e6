"""The harness finds every cell, configuration, traffic mix, limit and
metric reader by name, and refuses to run where it must."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = run.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_from_its_files(workload):
    cell = run.resolve(BENCH, workload)
    assert cell.chips in (1, 4)
    names = {m["name"] for m in cell.metrics}
    assert "setup_s" in names
    e2e = {m["name"] for m in BENCH["end_to_end"]} & names
    assert len(e2e) >= 2 and names - e2e
    for m in cell.metrics:
        reader = run.load_module(HERE / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    for part, key in run.MODULES:
        assert (HERE / part / f"{cell.config[key]}.py").is_file()


def test_benchmark_json_keeps_its_contract():
    assert BENCH["command"][:2] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    configs = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert configs == used
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/") and (ROOT / c["file"]).is_file()
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2), "at most half the cells, or one, take 4 chips"
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and (HERE / "traffic" / f"{w['traffic']}.json").is_file()
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "benchmarks/chip/run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def _no_result(out: str) -> bool:
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return not lines or not lines[-1].lstrip().startswith("{")


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout), p.stdout + p.stderr
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout), p.stdout + p.stderr


def test_configs_state_their_cuts():
    for c in BENCH["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"] and data["source"]
        for key in c["reduced"]:
            assert key in data["model"]
