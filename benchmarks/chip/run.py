"""The chip benchmark: one run of one cell.

    python benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``
at the root of the checkout:

* the cell's configuration file (``configs/<config>.json``), which names
  the runner (``runners/<runner>.py``) that drives the program, the
  plain reference (``references/<reference>.py``) that checks it, and
  the counter of its model FLOPs (``flops/<flops>.py``);
* its traffic mix (``traffic/<traffic>.json``), read by ``traffic.py``;
* its correctness limits (``limits/<workload>.json``);
* one reader per metric (``metrics/<metric>.py``, a ``read(record)``
  that returns the number, or None where it finds nothing to read).

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the result
carries its per-layer metrics, read through ``trace.py``.  The last
line of standard output is one JSON object; the numbers the
correctness check compared, each beside its limit, are the last lines
of standard error and the last key of that object.  With no TPU, or
fewer chips than the cell asks for, the run fails with no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    metrics: list  # BENCHMARK.json metric entries, end-to-end then per-layer


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


# Where a configuration's modules live, by the key that names each.
MODULES = (("runners", "runner"), ("references", "reference"), ("flops", "flops"))


def resolve(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with every file it names loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    mix = _read_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(HERE / "limits" / f"{workload}.json")
    for part, key in MODULES:
        path = HERE / part / f"{config[key]}.py"
        if not path.is_file():
            raise FileNotFoundError(f"{path} is missing")
    metrics = [m for m in bench["end_to_end"] + bench["per_layer"]
               if applies(m, workload)]
    for m in metrics:
        if not (HERE / "metrics" / f"{m['name']}.py").is_file():
            raise FileNotFoundError(f"no reader for metric {m['name']!r}")
    return Cell(workload, int(w["chips"]), config, mix, limits, metrics)


def read_metrics(cell: Cell, rec: dict, per_layer: bool, bench: dict) -> dict:
    names = {m["name"] for m in bench["per_layer" if per_layer else "end_to_end"]}
    out = {}
    for m in cell.metrics:
        if m["name"] not in names:
            continue
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def use_compile_cache(jax) -> str:
    """JAX's persistent compilation cache, in ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at a fixed path inside the checkout; every
    program is kept, however quick its compile."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def verdict(checks: list) -> bool:
    return all(c["value"] <= c["limit"] for c in checks if "limit" in c)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: {ROOT} holds no program (src/repro)", file=sys.stderr)
        return 2
    bench = load_benchmark()
    cell = resolve(bench, args.workload)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"run.py: no TPU (JAX platform {dev.platform!r}); the benchmark "
              f"runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    import peaks

    trace_mod = load_module(HERE / "trace.py")

    peak = peaks.peak_for(dev.device_kind)
    use_compile_cache(jax)

    runner, reference, counter = (load_module(HERE / part / f"{cell.config[key]}.py")
                                  for part, key in MODULES)
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-")) if args.trace else None
    try:
        rec = runner.run(cell, args.seed, args.seconds, trace_dir, T0, reference, counter)
        rec.update(peak=peak, chips=cell.chips, model=cell.config["model"],
                   counter=counter)
        if trace_dir is not None:
            rec["trace"] = trace_mod.reduce(trace_dir, runner.HOST_SPANS,
                                            chips=cell.chips)
            rec["earlier"]["Pallas kernels (name: device s per chip)"] = \
                rec["trace"]["kernels"]
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = read_metrics(cell, rec, bool(args.trace), bench)
    checks = rec["checks"]
    line = {
        "correct": verdict(checks) and rec["correct_outputs"],
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": cell.chips,
                   "memory_peak_bytes": int(rec["memory_peak_bytes"])},
    }
    if args.trace:
        line["device"].update(busy_s=rec["trace"]["busy_s"],
                              window_s=rec["trace"]["window_s"])
        line["breakdown"] = rec["trace"]["breakdown"]
    for key, value in rec.get("earlier", {}).items():
        print(f"{key}: {value}", flush=True)
    line["checks"] = checks
    for c in checks:
        print(f"check {c['name']}: {c.get('value', c.get('note'))}"
              + (f" (limit {c['limit']})" if "limit" in c else ""), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
