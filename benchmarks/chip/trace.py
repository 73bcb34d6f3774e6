"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops``
  line holds one event per operation run on the device.  An event's
  name is the operation's HLO text (``%name = shape op(operands),
  attributes``); the operation is named by what precedes `` = ``.  The
  operations of a ``while`` body (a scan over layers) are events of
  their own inside the ``while`` event's interval, so the breakdown
  counts self time.
* ``busy_s``: the union of those intervals on each chip, averaged over
  the chips used.  ``window_s``: the traced window, from the first to
  the last of the host spans the runner names (its ``jax.profiler.
  TraceAnnotation`` names).  The host and device planes keep separate
  clocks that agree to about a millisecond, so the window is exact to
  that and an idle gap's host span is named only to that.
* ``kernels``: the summed device time of each Pallas kernel operation
  (``custom_call_target="tpu_custom_call"``) by operation name,
  averaged over chips.  The kernels carry no name of their own; the
  operation takes the name of the jitted function that wraps it, and
  each kernel's metric reader picks its own operations from this table
  by a pattern of its own.
* Collective time: the summed device time of the all-reduce,
  all-gather, all-to-all, reduce-scatter and collective-permute
  operations, and the part of it during which no other operation ran
  on that chip.
* ``compute_s_by_chip``: for each chip used, in plane order, the union
  of its non-collective operation intervals inside the window: what
  each data-parallel rank computed, apart from waiting on the others.
* ``breakdown``: the ten operations with the most device self time
  (an event's time less that of the events nested in it), and the ten
  longest idle gaps on chip 0, each named by the host span that covers
  the gap's middle.
"""
from __future__ import annotations

import re
from pathlib import Path

PALLAS = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|all-to-all|reduce-scatter|"
                        r"collective-permute)", re.I)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def op_name(text: str) -> str:
    """The operation's name from its HLO text (``%fusion.3 = ...`` ->
    ``fusion.3``); a name that is not HLO text is kept whole."""
    head, sep, _ = text.partition(" = ")
    return head.lstrip("%") if sep else text


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    """Idle [start, end) gaps between merged busy intervals inside [lo, hi)."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(ops) -> dict[str, float]:
    """Device self time per operation name: each event's length less
    that of the events nested directly inside it."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self time]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2]

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    for entry in stack:
        close(entry)
    return out


def load_events(path: Path, spans) -> dict:
    """{'device': {plane: [(name, start_s, end_s, pallas)]}, 'host':
    [(name, s, e)]}: every operation on each device plane, whether it is
    a Pallas kernel, and the host spans named in ``spans``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, PALLAS in ev.name)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"device": device, "host": host}


def reduce_events(ev: dict, chips: int) -> dict:
    device, host = ev["device"], ev["host"]
    if not device:
        raise ValueError("the trace holds no device operations")
    if host:
        lo = min(s for _, s, _ in host)
        hi = max(e for _, _, e in host)
    else:
        lo = min(o[1] for ops in device.values() for o in ops)
        hi = max(o[2] for ops in device.values() for o in ops)
    planes = sorted(device)[:chips]
    busy, coll, coll_alone, by_name, kern = 0.0, 0.0, 0.0, {}, {}
    compute = []
    for plane in planes:
        ops = [(n, max(s, lo), min(e, hi), k) for n, s, e, k in device[plane]
               if e > lo and s < hi]
        busy += union_length((s, e) for _, s, e, _ in ops)
        for name, s, e, pallas in ops:
            if pallas:
                kern[name] = kern.get(name, 0.0) + e - s
        for name, t in self_times([o[:3] for o in ops]).items():
            by_name[name] = by_name.get(name, 0.0) + t
        c_iv = [(s, e) for n, s, e, _ in ops if COLLECTIVE.match(n)]
        other = [(s, e) for n, s, e, _ in ops if not COLLECTIVE.match(n)]
        coll += union_length(c_iv)
        compute.append(union_length(other))
        coll_alone += union_length(c_iv + other) - compute[-1]
    n = len(planes)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    ops0 = [(max(o[1], lo), min(o[2], hi)) for o in device[planes[0]]
            if o[2] > lo and o[1] < hi]
    gaps = sorted(_gaps(ops0, lo, hi), key=lambda g: g[0] - g[1])[:10]

    def cover(t):
        inside = [(e - s, name) for name, s, e in host if s <= t < e]
        return min(inside)[1] if inside else "no host span"

    return {
        "busy_s": busy / n,
        "window_s": hi - lo,
        "kernels": {k: v / n for k, v in sorted(kern.items())},
        "collective_s": coll / n,
        "collective_exposed_s": coll_alone / n,
        "compute_s_by_chip": compute,
        "breakdown": {
            "device_ops": [[name, t / n] for name, t in top],
            "idle_gaps": [[cover((s + e) / 2), e - s] for s, e in gaps],
        },
    }


def reduce(root, spans, chips: int = 1) -> dict:
    return reduce_events(load_events(find_xplane(Path(root)), set(spans)), chips)
