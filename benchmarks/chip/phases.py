"""The traced run of a training cell, read per step phase, per chip and
per program span.

    python benchmarks/chip/phases.py --workload <name> --seed <n> \\
        --seconds <s> [--out FILE]

This is ``run.py --trace 1`` itself: its run, its reduction, its
correctness check and its result line.  The same profile is then also
read for what ``trace.py`` does not reduce:

* ``phase_s``: device self time of each step phase on each chip, within
  the window.  An operation's phase is the named scope its ``op_name``
  keeps in the compiled step (``repro.obs.spans.op_phases`` of
  ``step.lower(...).compile().as_text()``, a compile-cache hit once the
  step has run).  Collective operations count under ``<phase>/collective``,
  apart from the compute, so a rank's wait at an all-reduce does not
  read as its work; operations under no phase count as ``unscoped``.
* ``program_spans``: seconds of each program span (``repro.obs.spans``:
  ``loader.*``, ``dispatch.*``, ``engine.*``, ``ckpt.*``) inside the
  window, over all host threads.
* ``idle_gaps_program``: the ten longest idle gaps of chip 0 that
  ``trace.py`` names by the runner's spans, each named instead by the
  program spans that cover its middle, joined by ``+``.
* the packer's counters: padding share of each stream over the window's
  steps (``OrchestratorReport.stream_tokens``) and the resamples by the
  stream that overflowed (``OrchestratorReport.resamples``).

The last line of standard output is one JSON object of these readings,
after ``run.py``'s own lines; ``--out`` also writes it to a file.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import re
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

trace = run.load_module(HERE / "trace.py")

PROGRAM_SPAN = re.compile(r"^(loader|dispatch|engine|ckpt)\.")
UNSCOPED = "unscoped"


def load_program_spans(path: Path) -> list[tuple[str, float, float]]:
    """(name, start_s, end_s) of every program span on the host planes."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if PROGRAM_SPAN.match(ev.name)]


def reduce_program(ev: dict, chips: int, op_phase: dict, program) -> dict:
    """``phase_s``, ``program_spans`` and ``idle_gaps_program`` of the
    events ``trace.load_events`` gives, with ``op_phase`` ({operation:
    phase}) and the program spans of :func:`load_program_spans`, inside
    the window ``trace.reduce_events`` reads: from the first to the last
    runner span."""
    lo = min(s for _, s, _ in ev["host"])
    hi = max(e for _, _, e in ev["host"])
    planes = sorted(ev["device"])[:chips]
    phase_s: dict[str, list[float]] = {}
    for i, plane in enumerate(planes):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e, _ in ev["device"][plane]
               if e > lo and s < hi]
        for name, t in trace.self_times(ops).items():
            key = op_phase.get(name, UNSCOPED)
            if trace.COLLECTIVE.match(name):
                key += "/collective"
            phase_s.setdefault(key, [0.0] * len(planes))[i] += t
    spans: dict[str, float] = {}
    for name, s, e in program:
        if e > lo and s < hi:
            spans[name] = spans.get(name, 0.0) + min(e, hi) - max(s, lo)
    ops0 = [(max(o[1], lo), min(o[2], hi)) for o in ev["device"][planes[0]]
            if o[2] > lo and o[1] < hi]
    gaps = sorted(trace._gaps(ops0, lo, hi), key=lambda g: g[0] - g[1])[:10]

    def cover(t):
        inside = sorted((s, name) for name, s, e in program if s <= t < e)
        return "+".join(dict.fromkeys(n for _, n in inside)) or "no program span"

    return {"phase_s": phase_s, "program_spans": spans,
            "idle_gaps_program": [[cover((s + e) / 2), e - s] for s, e in gaps]}


def per_step(reduced: dict, n_steps: int, stream_tokens: list[dict],
             resamples: list[dict]) -> dict:
    """The per-step and per-chip readings of :func:`reduce_program` and
    of the window steps' counters."""
    phase_s = reduced["phase_s"]
    busy = sum(sum(v) for v in phase_s.values())
    unscoped = sum(sum(v) for k, v in phase_s.items() if k.startswith(UNSCOPED))

    def mean(v):
        return sum(v) / len(v)

    out = {
        "phase_ms": {k: 1e3 * mean(v) / n_steps for k, v in sorted(phase_s.items())},
        "exchange_ms": 1e3 * sum(mean(v) for k, v in phase_s.items()
                                 if k.startswith("exchange.")) / n_steps,
        "unscoped_share": 100.0 * unscoped / busy if busy else None,
    }
    llm = phase_s.get("llm")
    if llm and len(llm) > 1:
        out["rank_spread.llm"] = 100.0 * (max(llm) / mean(llm) - 1.0)
    real: dict[str, int] = {}
    slots: dict[str, int] = {}
    for st in stream_tokens:
        for name, (r, s) in st.items():
            real[name] = real.get(name, 0) + r
            slots[name] = slots.get(name, 0) + s
    out["pad_frac"] = {k: 100.0 * (1.0 - real[k] / slots[k]) for k in sorted(slots)
                       if slots[k]}
    by_stream: dict[str, int] = {}
    for r in resamples:
        for name, n in r.items():
            by_stream[name] = by_stream.get(name, 0) + n
    out["resamples"] = by_stream
    return out


class _Capture:
    """What the runner hands out: its record, each batch's report, and
    the program with the first batch, which the compiled step's text is
    taken from."""

    def __init__(self):
        self.rec = None
        self.reports: list = []
        self.args = None

    def wrap(self, runner) -> None:
        import jax

        build, run_cell, capture = runner.build, runner.run, self

        def wrapped_build(*args, **kwargs):
            prog = build(*args, **kwargs)
            loader, state = prog.loader, _avals((prog.params, prog.opt_state))
            sharding = prog.batch_sharding  # a device on one chip
            if not isinstance(sharding, jax.sharding.Sharding):
                sharding = jax.sharding.SingleDeviceSharding(sharding)

            class Loader:
                def __next__(self):
                    item = next(loader)
                    if capture.args is None:
                        batch = jax.tree_util.tree_map(
                            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=sharding), item[0])
                        capture.args = (prog, state, batch)
                    capture.reports.append(item[1])
                    return item

                def close(self):
                    loader.close()

            prog.loader = Loader()
            return prog

        def wrapped_run(*args, **kwargs):
            capture.rec = run_cell(*args, **kwargs)
            return capture.rec

        runner.build, runner.run = wrapped_build, wrapped_run

    def hlo_text(self) -> str:
        import jax

        prog, state, batch = self.args
        with (jax.set_mesh(prog.mesh) if prog.mesh is not None
              else contextlib.nullcontext()):
            return prog.step.lower(*state, batch).compile().as_text()


def _avals(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    capture, readings = _Capture(), {}
    load_module = run.load_module

    def load(path: Path):
        """run.py's module loader, with the runner and the trace reduction
        wrapped to keep what this script reads."""
        mod = load_module(path)
        if path.parent.name == "runners":
            capture.wrap(mod)
        elif path.name == "trace.py":
            reduce = mod.reduce

            def reduce_and_read(root, spans, chips=1):
                from repro.obs.spans import op_phases

                out = reduce(root, spans, chips)
                xplane = mod.find_xplane(Path(root))
                t = time.perf_counter()
                op_phase = op_phases(capture.hlo_text())
                readings.update(hlo_text_s=time.perf_counter() - t,
                                ops_with_a_phase=len(op_phase))
                readings.update(reduce_program(mod.load_events(xplane, set(spans)), chips,
                                               op_phase, load_program_spans(xplane)))
                return out

            mod.reduce = reduce_and_read
        return mod

    run.load_module = load
    try:
        rc = run.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "1"])
    finally:
        run.load_module = load_module
    if rc != 0:
        return rc
    n = len(capture.rec["steps"])
    window = capture.reports[len(capture.reports) - n:]
    line = {
        "workload": args.workload, "seed": int(args.seed), "window_steps": n,
        **per_step(readings, n, [r.stream_tokens for r in window],
                   [r.resamples for r in window]),
        **readings,
    }
    text = json.dumps(line, default=str)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
