"""The four-chip path of a training cell, on four virtual CPU devices
(``dp_check.py``, in a process of its own so that it can set the device
count before JAX starts): the sound program is correct, and each fault
of ``faults.py`` planted under the timed path -- the exchange between
chips left out among them -- is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import faults  # noqa: E402
import test_correctness as tc  # noqa: E402


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, str(HERE / "tests" / "dp_check.py")],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    return {r["run"]: r for r in out}


def test_four_devices_sound_program_is_correct(runs):
    sound = runs["sound"]
    assert sound["correct"], sound
    assert sound["checks"]["compiles_in_window"] == 0
    for k, lim in tc.SMALL_TRAIN_LIMITS.items():
        assert sound["checks"][k] < lim / 3, (k, sound["checks"][k], lim)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_four_devices_fault_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]
