"""Roofline-driven block-shape autotuner for the Pallas kernels.

The flash, selective-scan, and grouped-GEMM kernels all take block
shapes that trade VMEM residency against grid overhead, and the best
choice depends on the call shape, dtype, and backend.  Hardcoding
128x128 (the pre-autotuner default) leaves real throughput behind on
small or skewed shapes.  This module applies the PR-4
calibrate-against-measurement philosophy one level down:

  1. enumerate candidate block shapes for a call signature,
  2. score each with a roofline prediction (``launch/roofline.py`` HW
     presets: compute time vs HBM time, plus a per-grid-step launch
     overhead term) and PRUNE candidates predicted far off the best --
     the model is there to keep the sweep cheap, not to decide,
  3. measure wall time for the survivors and pick the winner,
  4. cache the winner per (kernel, shape signature, dtype, device
     kind) in a JSON file consulted at trace time by the call sites
     (``resolve``), with an explicit-override escape hatch
     (``REPRO_KERNEL_BLOCKS`` env var) that always wins.

The cache stores plain data (block tuple + the prediction and
measurement that chose it), so a committed cache file is reviewable
and the escape hatch can pin any site without re-tuning.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Mapping, Sequence

import numpy as np

from repro.launch.roofline import HW
from repro.utils import CHECKOUT_CACHE

__all__ = [
    "Candidate", "autotune", "resolve", "cache_key", "default_cache_path",
    "flash_candidates", "scan_candidates", "grouped_candidates",
    "predict_flash", "predict_scan", "predict_grouped",
]

# Per-grid-step launch/bookkeeping overhead (s).  On real TPUs this is
# the Mosaic grid-step cost (~microseconds); the exact value matters
# only relatively -- it penalizes tiny blocks that explode the grid.
STEP_OVERHEAD_S = 1e-6

_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_OVERRIDE = "REPRO_KERNEL_BLOCKS"


@dataclasses.dataclass(frozen=True)
class Candidate:
    blocks: tuple[int, ...]
    predicted_s: float
    measured_ms: float | None = None


def default_cache_path() -> str:
    env = os.environ.get(_ENV_CACHE)
    if env:
        return env
    return str(CHECKOUT_CACHE / "autotune.json")


def cache_key(kernel: str, key: Mapping[str, object]) -> str:
    parts = [kernel] + [f"{k}={key[k]}" for k in sorted(key)]
    return "|".join(parts)


def _device_key(kernel: str, key: Mapping[str, object]) -> str:
    """Cache key of ``key`` on the attached device: a winner timed on
    one device kind (or in the interpreter) never applies to another."""
    import jax

    return cache_key(kernel, {**key, "device": jax.devices()[0].device_kind})


def _load_cache(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save_cache(path: str, data: dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


_GET_REGISTRY = None


def _count_resolve(kernel: str, outcome: str) -> None:
    """Count resolve outcomes (override/disabled/hit/miss) in the obs
    registry.  Lazy import: kernels must not depend on obs at import
    time, and the counter costs one dict hit per *trace*, not per step.
    Re-fetched from the process default registry each call so a test
    that swaps registries sees its own counts."""
    global _GET_REGISTRY
    if _GET_REGISTRY is None:
        try:
            from repro.obs.registry import get_registry
            _GET_REGISTRY = get_registry
        except Exception:
            _GET_REGISTRY = False  # obs unavailable: stay silent
    if _GET_REGISTRY:
        _GET_REGISTRY().counter(
            "kernel_autotune_resolves", "autotune block lookups by outcome",
            labels=("kernel", "outcome")).inc(kernel=kernel, outcome=outcome)


def _env_override(kernel: str) -> tuple[int, ...] | None:
    """REPRO_KERNEL_BLOCKS="flash=256x128,scan=128x64,grouped=128x128":
    an explicit pin that beats both the cache and the defaults."""
    raw = os.environ.get(_ENV_OVERRIDE)
    if not raw:
        return None
    for part in raw.split(","):
        if "=" not in part:
            continue
        name, _, val = part.partition("=")
        if name.strip() == kernel:
            return tuple(int(v) for v in val.strip().split("x"))
    return None


def resolve(
    kernel: str,
    key: Mapping[str, object],
    default: tuple[int, ...],
    *,
    enabled: bool = True,
    cache_path: str | None = None,
) -> tuple[int, ...]:
    """Trace-time block lookup for kernel call sites: env override >
    cached tuning winner > ``default``.  Never measures."""
    override = _env_override(kernel)
    if override is not None:
        _count_resolve(kernel, "override")
        return override
    if not enabled:
        _count_resolve(kernel, "disabled")
        return default
    entry = _load_cache(cache_path or default_cache_path()).get(
        _device_key(kernel, key))
    if entry is None:
        _count_resolve(kernel, "miss")
        return default
    _count_resolve(kernel, "hit")
    return tuple(int(b) for b in entry["blocks"])


def autotune(
    kernel: str,
    key: Mapping[str, object],
    candidates: Sequence[tuple[int, ...]],
    run_fn: Callable[[tuple[int, ...]], None],
    *,
    predict_fn: Callable[[tuple[int, ...]], float] | None = None,
    prune: float = 4.0,
    repeat: int = 3,
    cache_path: str | None = None,
    use_cache: bool = True,
) -> dict:
    """Sweep ``candidates``, cache and return the winner.

    ``run_fn(blocks)`` must execute the kernel to completion (jit +
    block_until_ready); it is called once for warmup/compile and
    ``repeat`` more times, keeping the best wall time.  ``predict_fn``
    maps blocks -> predicted seconds; candidates predicted worse than
    ``prune`` x the best prediction are skipped (the roofline model
    trims the sweep, measurement decides among survivors).  Returns
    ``{"blocks", "predicted_s", "measured_ms", "candidates", "cached"}``.
    """
    path = cache_path or default_cache_path()
    ck = _device_key(kernel, key)
    if use_cache:
        hit = _load_cache(path).get(ck)
        if hit is not None:
            return {**hit, "blocks": tuple(int(b) for b in hit["blocks"]),
                    "cached": True}

    preds = [float(predict_fn(c)) if predict_fn else 0.0 for c in candidates]
    best_pred = min(preds) if preds else 0.0
    rows: list[Candidate] = []
    for blocks, pred in zip(candidates, preds):
        if predict_fn and best_pred > 0 and pred > prune * best_pred:
            rows.append(Candidate(tuple(blocks), pred, None))  # pruned
            continue
        run_fn(tuple(blocks))  # warmup / compile
        best_ms = np.inf
        for _ in range(max(1, repeat)):
            t0 = time.perf_counter()
            run_fn(tuple(blocks))
            best_ms = min(best_ms, (time.perf_counter() - t0) * 1e3)
        rows.append(Candidate(tuple(blocks), pred, float(best_ms)))

    measured = [c for c in rows if c.measured_ms is not None]
    if not measured:
        raise ValueError(f"no measurable candidates for {ck}")
    winner = min(measured, key=lambda c: c.measured_ms)
    entry = {
        "blocks": list(winner.blocks),
        "predicted_s": winner.predicted_s,
        "measured_ms": winner.measured_ms,
        "candidates": [dataclasses.asdict(c) for c in rows],
    }
    if use_cache:
        data = _load_cache(path)
        data[ck] = entry
        _save_cache(path, data)
    return {**entry, "blocks": winner.blocks, "cached": False}


# ----------------------------------------------------------------------
# Candidate enumeration + roofline predictors.
# ----------------------------------------------------------------------
def _pow2_blocks(limit: int, lo: int = 16) -> list[int]:
    out = []
    b = lo
    while b <= limit:
        out.append(b)
        b *= 2
    return out or [limit]


def flash_candidates(Tq: int, Tkv: int) -> list[tuple[int, int]]:
    return [(bq, bk)
            for bq in _pow2_blocks(min(Tq, 512), 32) if Tq % bq == 0
            for bk in _pow2_blocks(min(Tkv, 512), 32) if Tkv % bk == 0]


def scan_candidates(T: int, di: int) -> list[tuple[int, int]]:
    return [(bd, ct)
            for bd in _pow2_blocks(min(di, 256), 16) if di % bd == 0
            for ct in _pow2_blocks(min(T, 512), 16) if T % ct == 0]


def grouped_candidates(M: int, N: int) -> list[tuple[int, int]]:
    return [(bm, bn)
            for bm in _pow2_blocks(min(M, 512), 32) if M % bm == 0
            for bn in _pow2_blocks(min(N, 512), 32) if N % bn == 0]


def _roofline_s(flops: float, mem_bytes: float, grid_steps: float,
                hw: HW) -> float:
    return max(flops / hw.peak_flops, mem_bytes / hw.hbm_bw) + (
        grid_steps * STEP_OVERHEAD_S)


def predict_flash(blocks, *, heads: int, Tq: int, Tkv: int, D: int,
                  hw: HW, live_frac: float = 1.0,
                  dtype_bytes: int = 2) -> float:
    """Forward-pass roofline: 4*Tq*Tkv*D MACs over the live tiles, K/V
    tiles re-streamed once per live (q-tile, kv-tile) pair."""
    bq, bk = blocks
    tiles = (Tq // bq) * (Tkv // bk) * live_frac
    flops = 4.0 * heads * tiles * bq * bk * D
    mem = heads * dtype_bytes * (
        2 * Tq * D + tiles * 2 * bk * D)  # q in + out, live k/v tiles
    return _roofline_s(flops, mem, heads * tiles, hw)


def predict_scan(blocks, *, T: int, di: int, N: int, hw: HW,
                 dtype_bytes: int = 4) -> float:
    """Recurrence is bandwidth/latency bound: stream u/dt/y (+B/C per
    channel block) once, plus a chunk-boundary state checkpoint; the
    per-grid-step overhead is what penalizes tiny chunks."""
    bd, ct = blocks
    n_d, n_t = di // bd, T // ct
    flops = 8.0 * T * di * N
    mem = dtype_bytes * (
        3 * T * di            # u, dt, y
        + n_d * 2 * T * N     # B, C re-streamed per channel block
        + n_t * di * N        # chunk-boundary checkpoints
    )
    return _roofline_s(flops, mem, n_d * n_t, hw)


def predict_grouped(blocks, *, M: int, K: int, N: int, E: int,
                    hw: HW, live_tiles: int | None = None,
                    dtype_bytes: int = 2) -> float:
    """Live (m-tile, expert) pairs do a [bm,K]x[K,bn] MAC; dead pairs
    still pay a grid step (the tile-skip saves MXU+HBM, not issue)."""
    bm, bn = blocks
    n_m, n_n = M // bm, N // bn
    if live_tiles is None:
        live_tiles = n_m + E - 1  # contiguous groups: one overlap per seam
    live = live_tiles * n_n
    flops = 2.0 * live * bm * K * bn
    mem = dtype_bytes * (live * (bm * K + K * bn + bm * bn))
    return _roofline_s(flops, mem, n_m * n_n * E, hw)
