"""The four-chip path of a training cell on four virtual CPU devices.

    python benchmarks/chip/tests/dp_check.py

Drives the runner, its device check skipped, on the small cell of
test_correctness.py with one DP instance per device (the mesh path,
with the all-to-all exchange): once sound and once with each fault of
``faults.py`` planted under the timed path.  Prints one JSON line per
run: ``{"run": name, "correct": bool, "checks": {...}}``.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import functools  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE / "tests"), str(HERE), str(HERE.parents[1] / "src")]

import faults  # noqa: E402
import run  # noqa: E402
import test_correctness as tc  # noqa: E402


def main() -> int:
    runner = run.load_module(HERE / "runners/train.py")
    ref = run.load_module(HERE / "references/mllm.py")
    counter = run.load_module(HERE / "flops/mllm.py")
    ref.batch_grads = functools.partial(ref.batch_grads, **tc.TRAIN_BUCKETS)
    cell = tc.small_train_cell()
    cell.chips = 4
    build = runner.build
    for name, fault in [("sound", None)] + sorted(faults.FAULTS.items()):
        runner.build = functools.partial(build, make_train_step=fault)
        rec = runner.run(cell, tc.SEED, 0.5, None, time.perf_counter(), ref, counter)
        checks = {c["name"]: c.get("value", c.get("note")) for c in rec["checks"]}
        print(json.dumps({"run": name, "correct": run.verdict(rec["checks"]),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
