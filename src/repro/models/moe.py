"""Mixture-of-Experts FFN (grok-1: 8e top-2; granite: 40e top-8).

Two dispatch backends behind ``moe_ffn(backend=...)``, sharing one
routing prologue (top-k over router softmax, invalid/padding
assignments remapped to a sentinel expert so they never steal capacity
or rows):

  "dense"    legacy sort + scatter into a [E, capacity, d] buffer:
             static shapes, but pays E*capacity rows of matmul and
             silently drops assignments past capacity (the dropped
             fraction is now reported as an aux metric).

  "grouped"  drop-free sorted dispatch: tokens sorted by expert form
             contiguous variable-length groups, and the three expert
             matmuls run through the Pallas grouped-GEMM kernel
             (``kernels/grouped_gemm.py``) with scalar-prefetch group
             offsets and tile-skip over empty experts.  Work scales
             with the routed rows (aligned up to the tile), not with
             E * max-capacity, no matter how imbalanced the routing.

Both return aux metrics: the Switch-style load-balance loss over ALL
top-k slots, the realized per-expert load fractions, and the dropped
fraction (identically 0.0 for "grouped").

Token-to-expert routing is the paper's imbalanced-assignment problem
one level down: ``expert_shard_plan`` reuses the chunked-exact LPT
engine from ``core/balancing_vec.py`` to bin experts onto expert-
parallel shards from the *measured* loads the aux metrics report, and
to derive the capacity a drop-free dense dispatch would need.

Expert parallelism (dense path): the [E,C,*] buffers and expert
weights carry sharding constraints over the ``model`` mesh axis
(weights: d_ff dim; buffers: capacity dim), so the big matmuls are
tensor-parallel within each expert -- this avoids requiring
n_experts % mesh_model == 0 (grok has 8 experts on a 16-wide model
axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["moe_ffn", "router_load_balance_loss", "expert_shard_plan"]


def moe_ffn(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    valid: jnp.ndarray | None = None,
    shard_buffers: bool = False,
    backend: str = "dense",
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """x: [B, T, d]; router_w: [d, E]; w_*: [E, d, f] / [E, f, d].

    ``valid``: [B, T] bool -- padding tokens get zero routing weight and
    are remapped to a sentinel expert, so they never steal capacity
    (post-balancing keeps padding minimal, but the packed stream tail
    may be padded to the static shape).

    Returns ``(output [B,T,d], aux)`` where ``aux`` is a dict of
    metrics:

      "lb_loss"       Switch-style load-balance loss (scalar; counts
                      all top-k slots)
      "expert_load"   [E] realized fraction of routed assignments
      "dropped_frac"  fraction of valid assignments dropped by the
                      capacity buffer (0.0 on the drop-free "grouped"
                      backend)
    """
    if backend not in ("dense", "grouped"):
        raise ValueError(f"unknown moe backend {backend!r}")
    B, T, d = x.shape
    E = router_w.shape[-1]
    n = B * T
    xf = x.reshape(n, d)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), router_w.astype(jnp.float32))
    if valid is not None:
        logits = jnp.where(valid.reshape(n, 1), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_ids = jax.lax.top_k(probs, top_k)  # [n, k]
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    if valid is not None:
        gate_vals = gate_vals * valid.reshape(n, 1)

    # Flatten assignments; invalid tokens route to sentinel expert E
    # (sorts past every real expert -> zero rows, zero capacity use).
    flat_e = gate_ids.reshape(-1)  # [n*k]
    if valid is not None:
        flat_e = jnp.where(jnp.repeat(valid.reshape(n), top_k), flat_e, E)

    counts = jnp.zeros(E + 1, jnp.int32).at[flat_e].add(1)
    n_routed = jnp.maximum(counts[:E].sum(), 1)
    expert_load = counts[:E].astype(jnp.float32) / n_routed.astype(jnp.float32)

    if backend == "grouped":
        # Sort, expert matmuls and combine run per DP shard: XLA cannot
        # partition the Mosaic kernel, and drop-free routing leaves each
        # token's output independent of every other shard's tokens.
        from repro.kernels.ops import per_dp_shard

        combined = per_dp_shard(
            functools.partial(_grouped_ffn, top_k=top_k, block_m=block_m,
                              block_n=block_n, interpret=interpret),
            x, flat_e.reshape(B, T * top_k), gate_vals.reshape(B, T, top_k),
            replicated=(w_gate, w_up, w_down))
        dropped = jnp.int32(0)
    else:
        order, sorted_e, sorted_tok = _sort_assignments(flat_e, top_k)
        expert_out, dropped = _dense_dispatch(
            xf, w_gate, w_up, w_down, sorted_e, sorted_tok, order, counts,
            n, top_k, E, capacity_factor, shard_buffers)
        combined = _combine(expert_out, order, gate_vals)

    aux = {
        "lb_loss": router_load_balance_loss(
            probs, gate_ids, E, valid.reshape(n) if valid is not None else None,
            top_k=top_k),
        "expert_load": expert_load,
        "dropped_frac": dropped.astype(jnp.float32) / n_routed.astype(jnp.float32),
    }
    return combined.reshape(B, T, d).astype(x.dtype), aux


def _sort_assignments(flat_e, top_k):
    """Stable sort of the [n*k] assignments by expert: ``(order,
    sorted experts, sorted token ids)``."""
    flat_tok = jnp.repeat(jnp.arange(flat_e.shape[0] // top_k), top_k)
    order = jnp.argsort(flat_e, stable=True)
    return order, flat_e[order], flat_tok[order]


def _combine(expert_out, order, gate_vals):
    """Un-sort the [n*k, d] expert outputs and weight each token's k
    outputs by its gates: [n, d] in f32."""
    n, k = gate_vals.shape
    inv = jnp.argsort(order, stable=True)
    out = expert_out[inv].reshape(n, k, -1)
    return jnp.einsum("nkd,nk->nd", out.astype(jnp.float32),
                      gate_vals.astype(jnp.float32))


def _dense_dispatch(xf, w_gate, w_up, w_down, sorted_e, sorted_tok, order,
                    counts, n, top_k, E, capacity_factor, shard_buffers):
    """Legacy capacity-buffer path.  Returns outputs in SORTED
    assignment order [n*k, d] plus the dropped-assignment count."""
    d = xf.shape[1]
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * top_k) - starts[sorted_e]

    capacity = int(max(1, round(n * top_k / E * capacity_factor)))
    keep = (rank < capacity) & (sorted_e < E)
    dropped = counts[:E].sum() - keep.sum()
    slot = jnp.where(keep, sorted_e * capacity + rank, E * capacity)

    buf = jnp.zeros((E * capacity + 1, d), xf.dtype)
    buf = buf.at[slot].set(xf[sorted_tok], mode="drop")
    buf = buf[:-1].reshape(E, capacity, d)
    if shard_buffers:
        # S-Perf knob: pin the dispatch buffer's capacity dim to the
        # model axis so expert matmuls parallelize over C instead of
        # round-tripping through resharding collectives.
        from jax.sharding import PartitionSpec as _P

        buf = jax.lax.with_sharding_constraint(buf, _P(None, "model", None))

    # Expert matmuls (tensor-parallel over f via weight sharding).
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E * capacity, d)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), out_buf.dtype)], axis=0)
    return out_buf[slot], dropped  # dropped slot -> zeros row


def _grouped_ffn(x, flat_e, gate_vals, w_gate, w_up, w_down, *, top_k,
                 block_m, block_n, interpret):
    """Drop-free grouped-GEMM expert FFN over the tokens of ``x`` [b, T,
    d] with their assignments ``flat_e`` [b, T*k] and gates [b, T, k]:
    sort by expert, three grouped matmuls, combine.  Returns [b, T, d]
    in f32."""
    from repro.kernels.ops import grouped_matmul_op

    b, T, d = x.shape
    flat_e = flat_e.reshape(-1)
    order, _, sorted_tok = _sort_assignments(flat_e, top_k)
    counts = jnp.zeros(w_gate.shape[0] + 1, jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts[:-1]).astype(jnp.int32)])
    xs = x.reshape(b * T, d)[sorted_tok]  # sorted by expert; sentinel last
    M = b * T * top_k
    bm = min(block_m, M)
    pad = (-M) % bm
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad, d), xs.dtype)])

    g = grouped_matmul_op(xs, w_gate, offsets, block_m=bm,
                          block_n=_divisor_block(w_gate.shape[-1], block_n),
                          interpret=interpret)
    u = grouped_matmul_op(xs, w_up, offsets, block_m=bm,
                          block_n=_divisor_block(w_up.shape[-1], block_n),
                          interpret=interpret)
    h = jax.nn.silu(g) * u
    out = grouped_matmul_op(h, w_down, offsets, block_m=bm,
                            block_n=_divisor_block(d, block_n),
                            interpret=interpret)
    combined = _combine(out[:M], order, gate_vals.reshape(b * T, top_k))
    return combined.reshape(b, T, d)


def _divisor_block(size: int, target: int) -> int:
    """Largest block <= target that divides size (trace-time helper)."""
    for b in range(min(target, size), 0, -1):
        if size % b == 0:
            return b
    return 1


def router_load_balance_loss(
    probs: jnp.ndarray, gate_ids: jnp.ndarray, n_experts: int,
    valid: jnp.ndarray | None = None, *, top_k: int | None = None,
) -> jnp.ndarray:
    """Switch-style aux loss: E * sum_e fraction_slots_e * mean_prob_e.

    Counts ALL top-k assignment slots (normalized by k) -- a top-8
    router whose 2nd..8th choices pile onto one expert is imbalanced
    even when the top-1 choices are uniform.  Balanced-uniform routing
    (uniform probs, uniform slot usage) gives exactly 1.0 for any k.
    """
    n, k = gate_ids.shape
    if top_k is not None and top_k != k:
        raise ValueError(f"top_k={top_k} != gate_ids k={k}")
    onehot = jax.nn.one_hot(gate_ids, n_experts, dtype=jnp.float32).sum(1) / k
    if valid is not None:
        vf = valid.astype(jnp.float32)
        onehot = onehot * vf[:, None]
        denom = jnp.clip(vf.sum(), 1.0)
        mean_p = (probs * vf[:, None]).sum(0) / denom
    else:
        denom = float(n)
        mean_p = probs.mean(0)
    frac = onehot.sum(0) / denom
    return n_experts * jnp.sum(frac * mean_p)


def expert_shard_plan(
    expert_load: np.ndarray, n_shards: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side planner: bin experts onto ``n_shards`` expert-parallel
    shards balancing *measured* load, via the chunked-exact LPT engine
    from ``core/balancing_vec.py`` (token-to-expert routing is the
    paper's imbalanced-assignment problem one level down).

    ``expert_load``: [E] nonnegative loads (e.g. the ``expert_load``
    aux metric from ``moe_ffn``, or raw token counts).  Returns
    ``(assignment [E] int, shard_loads [n_shards] float)``.
    """
    from repro.core.balancing_vec import lpt_assign

    loads = np.asarray(expert_load, np.float64)
    if loads.ndim != 1 or n_shards < 1:
        raise ValueError(f"bad plan inputs: {loads.shape}, {n_shards}")
    order = np.argsort(-loads, kind="stable")
    assign_sorted, _, shard_loads = lpt_assign(loads[order], n_shards)
    assignment = np.empty(loads.size, np.int64)
    assignment[order] = assign_sorted
    return assignment, shard_loads
