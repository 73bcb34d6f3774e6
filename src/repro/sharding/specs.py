"""PartitionSpecs for params, optimizer state, batches and decode caches.

Strategy (mirrors the paper's FSDP setup, S7/S8, mapped to TPU):
  * params: ZeRO-3-style sharding over the ``data`` axis + tensor
    parallelism over ``model``; the ``pod`` axis REPLICATES params --
    that's the paper's hybrid-shard group (they used group size 256; our
    single-pod data*model = 256 matches), with gradient all-reduce over
    pods.
  * batch streams: leading (DP-shard) dim over (pod, data).
  * decode caches: batch dim over DP when divisible; otherwise the
    long-context case (B=1) shards the sequence / feature dims instead.

Assignment is pattern-free: for every param leaf we pick the last dim
divisible by the ``model`` axis for TP and the largest remaining dim
divisible by ``data`` for FSDP, skipping the stacked-layer leading dim.
This is deliberately generic -- per-arch hand overrides live in the
perf-iteration layer (EXPERIMENTS.md S-Perf), not here.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

__all__ = [
    "param_specs",
    "opt_state_specs",
    "batch_specs",
    "cache_sharding_specs",
    "dp_axes_of",
    "dp_shards_of",
    "stage_partition",
    "to_shardings",
]


def stage_partition(n_layers: int, pp: int,
                    layer_costs=None) -> tuple[int, ...]:
    """Contiguous partition of ``n_layers`` into ``pp`` pipeline stages.

    Minimizes the max per-stage cost over contiguous splits (activations
    only flow between adjacent stages, so stages must be contiguous).
    ``layer_costs`` is an optional per-layer cost vector -- e.g. the
    calibrated per-layer LLM cost from the telemetry fits -- defaulting
    to uniform layers, where the split is the balanced floor/ceil one.
    Returns layers-per-stage (len ``pp``, sums to ``n_layers``); every
    stage gets at least one layer.
    """
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got {pp}")
    if pp > n_layers:
        raise ValueError(f"pp={pp} exceeds n_layers={n_layers}")
    if pp == 1:
        return (n_layers,)
    if layer_costs is None:
        base, extra = divmod(n_layers, pp)
        # Heavier stages FIRST: warmup bubbles shrink toward the tail,
        # so front-loading keeps the steady-state critical path tight.
        return tuple(base + (1 if s < extra else 0) for s in range(pp))
    costs = np.asarray(layer_costs, dtype=np.float64)
    if costs.shape != (n_layers,):
        raise ValueError(f"layer_costs must have shape ({n_layers},)")
    prefix = np.concatenate([[0.0], np.cumsum(costs)])

    def feasible(cap: float) -> tuple[int, ...] | None:
        """Greedy: longest prefix per stage under ``cap``; leave enough
        layers so every remaining stage can take at least one."""
        out, lo = [], 0
        for s in range(pp):
            hi_max = n_layers - (pp - 1 - s)
            hi = int(np.searchsorted(prefix, prefix[lo] + cap, side="right")) - 1
            hi = min(max(hi, lo + 1), hi_max)
            out.append(hi - lo)
            lo = hi
        return tuple(out) if lo == n_layers else None

    # Binary search the min-max stage cost over the distinct candidates.
    lo_cap, hi_cap = float(costs.max()), float(costs.sum())
    best = feasible(hi_cap)
    for _ in range(64):
        mid = 0.5 * (lo_cap + hi_cap)
        got = feasible(mid)
        if got is not None:
            best, hi_cap = got, mid
        else:
            lo_cap = mid
    assert best is not None
    return best


def _leaf_spec(shape: tuple[int, ...], data: int, model: int,
               *, skip_dims: int = 0) -> P:
    """Generic FSDP+TP assignment with divisibility checks."""
    spec: list[Any] = [None] * len(shape)
    dims = list(range(skip_dims, len(shape)))
    # TP: last eligible dim divisible by `model` and reasonably large.
    tp_dim = None
    for d in reversed(dims):
        if model > 1 and shape[d] % model == 0 and shape[d] >= 2 * model:
            tp_dim = d
            spec[d] = "model"
            break
    # FSDP: largest remaining dim divisible by `data`.
    best, best_size = None, 0
    for d in dims:
        if d == tp_dim:
            continue
        if data > 1 and shape[d] % data == 0 and shape[d] >= data and shape[d] > best_size:
            best, best_size = d, shape[d]
    if best is not None:
        spec[best] = "data"
    return P(*spec)


def param_specs(cfg: ModelConfig, params, mesh: Mesh):
    """Specs matching the params pytree.  Stacked-layer leaves (inside
    'layers'/'enc_layers') skip their leading [L] dim for FSDP/TP; when
    the mesh carries a ``pp`` axis that dim is instead SHARDED over it --
    stage s owns its contiguous layer slice (``stage_partition``), which
    is exactly the pipeline placement expressed as a sharding."""
    data = mesh.shape.get("data", 1)
    model = mesh.shape.get("model", 1)
    pp = mesh.shape.get("pp", 1)

    def walk(tree, stacked: bool):
        if isinstance(tree, dict):
            return {
                k: walk(v, stacked or k in ("layers", "enc_layers"))
                for k, v in tree.items()
            }
        spec = _leaf_spec(tree.shape, data, model, skip_dims=1 if stacked else 0)
        if stacked and pp > 1 and tree.shape[0] % pp == 0:
            spec = P("pp", *tuple(spec)[1:]) if len(spec) > 1 else P("pp")
        return spec

    return walk(params, False)


def opt_state_specs(p_specs):
    return {
        "mu": p_specs,
        "nu": p_specs,
        "step": P(),
    }


def dp_axes_of(mesh) -> tuple[str, ...]:
    """The mesh's DP-shard axes, ("pod", "data") where present; works on
    a ``Mesh`` and on an ``AbstractMesh``."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_shards_of(mesh) -> int:
    n = 1
    for a in dp_axes_of(mesh):
        n *= mesh.shape[a]
    return n


def batch_specs(batch: dict[str, Any], dp_axes: tuple[str, ...]) -> dict[str, P]:
    """All batch arrays carry the DP-shard layout on their leading dim."""
    return {k: P(dp_axes) for k in batch}


def cache_sharding_specs(cfg: ModelConfig, cache, dp_axes: tuple[str, ...],
                         mesh: Mesh):
    """Decode-cache specs; see module docstring for the B=1 fallback."""
    dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
    model = mesh.shape.get("model", 1)
    all_axes = tuple(mesh.axis_names)

    def leaf(path: str, x) -> P:
        shape = x.shape
        if path in ("kv_pos", "kv_seg", "sa_kv_pos", "sa_kv_seg",
                    "cross_seg", "cross_pos"):
            B = shape[0]
            return P(dp_axes) if B % dp == 0 and B >= dp else P()
        if path in ("k", "v", "sa_k", "sa_v", "cross_k", "cross_v"):
            L, B, S = shape[0], shape[1], shape[2]
            if B % dp == 0 and B >= dp:
                seq_ax = "model" if S % model == 0 and S >= model else None
                return P(None, dp_axes, seq_ax, None, None)
            # Long-context: shard the sequence across everything it divides.
            if S % int(np.prod([mesh.shape[a] for a in all_axes])) == 0:
                return P(None, None, all_axes, None, None)
            return P(None, None, dp_axes if S % dp == 0 else None, None, None)
        if path == "conv":
            L, B = shape[0], shape[1]
            di = shape[-1]
            if B % dp == 0 and B >= dp:
                return P(None, dp_axes, None, "model" if di % model == 0 else None)
            return P(None, None, None, "model" if di % model == 0 else None)
        if path == "h":
            B = shape[1]
            if B % dp == 0 and B >= dp:
                if len(shape) == 4:  # mamba1 [L,B,di,N]
                    return P(None, dp_axes, "model" if shape[2] % model == 0 else None, None)
                return P(None, dp_axes, None, None, None)  # mamba2 [L,B,H,P,N]
            if len(shape) == 4:
                return P(None, None, "model" if shape[2] % model == 0 else None, None)
            return P()
        return P()

    return {k: leaf(k, v) for k, v in cache.items()}


def to_shardings(specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
