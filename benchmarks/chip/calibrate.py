"""Read the numbers a cell's correctness limits are set from, on the chip.

    python benchmarks/chip/calibrate.py --workload W --seeds 1 2 ... \\
        --control-seeds 1 2 3 [--fault half_batch]

For each seed, the sound program's readings against the plain reference
and, for the control seeds, the lower-precision control's (the
reference computed in fp8), one JSON line each, all in one process.
The lower reading of a limit is the largest over the program's seeds,
the upper the smallest over the control's (PERF.md gives both).  With
``--fault`` (training cells) the program read carries that fault of
``faults.py``.
"""
from __future__ import annotations

import argparse
import sys

import faults
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_benchmark(), args.workload)
    sys.path.insert(0, str(run.HERE))
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 1
    run.use_compile_cache(jax)
    runner = run.load_module(run.HERE / "runners" / f"{cell.config['runner']}.py")
    reference = run.load_module(run.HERE / "references" / f"{cell.config['reference']}.py")
    fault = {"make_train_step": faults.FAULTS[args.fault]} if args.fault else {}
    runner.calibrate(cell, args.seeds, set(args.control_seeds), reference, **fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
