"""Pallas kernel call sites and the training loop on a 4-device DP mesh
(subprocess, 4 host devices): each kernel runs per DP shard and matches
its single-device result; ``train --mesh host`` compiles once and
agrees with the single-device loop step for step."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def run():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    res = subprocess.run(
        [sys.executable, str(REPO / "tests/helpers/mesh_train_check.py")],
        env=env, capture_output=True, text=True, timeout=900)
    return res


@pytest.mark.parametrize("check", [
    "flash_attention", "moe_grouped", "mamba1_pallas", "mamba2_pallas",
    "non_dividing_batch_raises", "train_mesh_host"])
def test_dp_mesh(run, check):
    assert f"ok {check}\n" in run.stdout, (
        f"rc={run.returncode}\nstdout:\n{run.stdout[-3000:]}\n"
        f"stderr:\n{run.stderr[-3000:]}")
