"""Segment-aware flash attention, Pallas TPU kernel (fwd + bwd).

This is the TPU-native form of the packed-batch attention that
post-balancing relies on (no-padding batching, paper Alg 1/3): the
kernel masks by SEGMENT ID inside each tile, so one shard's stream can
hold many examples with zero cross-contamination and zero padding
FLOPs beyond tile granularity.

Design notes
============

Tiling
------
Forward and dq grids are ``(B*H, nQ, nK)`` with the KV dimension
innermost (sequential on TPU); the dk/dv grid is ``(B*Hkv, nK, nQ*g)``
with the (GQA group member, Q tile) axis innermost so each KV tile owns
one scratch accumulator that sums its whole group.  GQA (Hkv < H) is
resolved purely by BlockSpec index maps (q head h reads kv head
``h // g``) -- K/V tiles are shared across the group, never
materialized per Q head.  VMEM scratch carries the online-softmax state
(m, l, acc) or the gradient accumulators across the innermost loop --
the standard FlashAttention-2 schedule mapped onto the MXU:
``block_q x block_kv`` score tiles, 128-aligned.

Residuals
---------
The forward pass emits, next to the output, the per-row logsumexp
``lse = m + log(l)`` (0 for fully-masked rows).  The backward pass
recomputes each score tile from (q, k) and reconstructs the softmax as
``p = exp(s - lse)`` -- O(Tq) residual memory instead of the O(Tq*Tkv)
probability matrix.  ``delta = rowsum(do * o)`` is precomputed outside
the kernels (a cheap O(T*D) contraction) and streamed in per Q tile:

    dv_j = sum_i p_ij do_i
    ds_ij = p_ij * (dp_ij - delta_i),  dp = do v^T
    dq_i = scale * sum_j ds_ij k_j,    dk_j = scale * sum_i ds_ij q_i

Block-skip index math
---------------------
``pack_stream`` lays examples out contiguously, so most (Q tile, KV
tile) pairs are FULLY masked: their segment-id ranges do not intersect,
or the KV tile lies entirely above the causal / sliding-window
frontier.  :func:`tile_stats` reduces each tile of the packed
``seg``/``pos`` arrays to interval summaries over the valid (seg > 0)
entries -- ``(smin, smax, pmin, pmax, any_valid)`` -- and
:func:`live_tile_mask` combines them into a ``[B, nQ, nK]`` visit mask.
A KV tile k is skipped for Q tile q when any of these hold:

    dead      :  no valid entry in q or in k
    segments  :  q.smax < k.smin  or  k.smax < q.smin
                 (interval disjointness => no equal segment ids)
    causal    :  k.pmin > q.pmax          (every key is in the future)
    window    :  q.pmin - k.pmax >= W     (every key fell out of the window)

Each rule is conservative (a skipped tile is provably all-masked for
ANY layout, contiguous or not); contiguous packed layouts are where the
intervals become tight and most of the grid drops out.  The mask is
computed once outside the ``pallas_call`` (O(nQ*nK), not O(T^2)) and
scalar-prefetched into SMEM; all three kernels wrap their tile body in
``pl.when(live)`` so skipped tiles issue no MXU work.  The flat mask
holds B*nQ*nK int32 words of SMEM, which bounds the sequence length a
call can take at a given block size.

Differentiation
---------------
``flash_attention`` carries a ``jax.custom_vjp``: gradients of packed
train steps flow through the Pallas dq/dk/dv kernels, never through a
dense ``[Tq, Tkv]`` mask.  seg/pos inputs get symbolic-zero (float0)
cotangents.

``interpret=True`` runs the kernel bodies in Python/XLA on the CPU (the
validation mode of the tests); on a TPU they compile through Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
_BIG = np.int32(2**30)

__all__ = [
    "flash_attention",
    "tile_stats",
    "live_tile_mask",
    "count_live_tiles",
    "tile_skip_fraction",
]


# ----------------------------------------------------------------------
# Block-skip precomputation (host side).
# ----------------------------------------------------------------------
def tile_stats(seg: jnp.ndarray, pos: jnp.ndarray, block: int):
    """Interval summaries per tile of a packed stream.

    seg, pos: [B, T] int32 (seg 0 = padding).  Returns a dict of
    [B, T // block] arrays: smin/smax/pmin/pmax over valid entries and
    ``any`` (tile has at least one valid token).
    """
    B, T = seg.shape
    n = T // block
    s = seg.reshape(B, n, block)
    p = pos.reshape(B, n, block)
    valid = s > 0
    return {
        "smin": jnp.where(valid, s, _BIG).min(axis=-1),
        "smax": jnp.where(valid, s, -1).max(axis=-1),
        "pmin": jnp.where(valid, p, _BIG).min(axis=-1),
        "pmax": jnp.where(valid, p, -1).max(axis=-1),
        "any": valid.any(axis=-1),
    }


def live_tile_mask(
    q_seg: jnp.ndarray,
    kv_seg: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    *,
    block_q: int,
    block_kv: int,
    causal: bool,
    window: int | None,
) -> jnp.ndarray:
    """[B, nQ, nK] bool: True where the (Q tile, KV tile) pair may hold
    at least one unmasked score (see module docstring for the rules)."""
    qs = tile_stats(q_seg, q_pos, block_q)
    ks = tile_stats(kv_seg, kv_pos, block_kv)
    live = qs["any"][:, :, None] & ks["any"][:, None, :]
    live &= qs["smin"][:, :, None] <= ks["smax"][:, None, :]
    live &= ks["smin"][:, None, :] <= qs["smax"][:, :, None]
    if causal:
        live &= ks["pmin"][:, None, :] <= qs["pmax"][:, :, None]
    if window is not None:
        live &= qs["pmin"][:, :, None] - ks["pmax"][:, None, :] < window
    return live


def count_live_tiles(
    q_seg, kv_seg, q_pos, kv_pos, *, block_q, block_kv, causal, window
) -> tuple[int, int]:
    """(visited, total) KV-tile visits for ONE head's grid pass, summed
    over all streams in the batch (the mask is head-independent; every
    head of a stream visits the same tiles)."""
    live = live_tile_mask(q_seg, kv_seg, q_pos, kv_pos, block_q=block_q,
                          block_kv=block_kv, causal=causal, window=window)
    return int(jnp.sum(live)), int(np.prod(live.shape))


def tile_skip_fraction(
    q_seg, kv_seg, q_pos, kv_pos, *, block_q, block_kv, causal, window
) -> float:
    """Fraction of (Q tile, KV tile) grid cells the kernel skips on this
    batch -- the observability counterpart of :func:`count_live_tiles`.
    Host-side and data-dependent, so sample it at flush intervals (the
    ledger does), never inside the traced step."""
    visited, total = count_live_tiles(
        q_seg, kv_seg, q_pos, kv_pos, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window)
    return 1.0 - visited / total if total else 0.0


# ----------------------------------------------------------------------
# Kernel bodies.
#
# Layouts (Mosaic wants the last two block dims (8, 128)-aligned or
# whole): per-row values of the Q side (q seg/pos, lse, delta) travel as
# [.., T, 1] columns and per-key values of the KV side (kv seg/pos) as
# [.., 1, T] rows, so a tile mask is a plain [bq, 1] x [1, bk]
# broadcast.  The live-tile mask is flattened to [B * nQ * nK] and
# arrives by scalar prefetch (SMEM), read before the tile body runs.
# ----------------------------------------------------------------------
def _tile_mask(qs, ks, qp, kp, *, causal, window):
    """[bq, bk] bool mask for one score tile (qs/qp [bq, 1], ks/kp
    [1, bk])."""
    mask = (qs == ks) & (qs > 0)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= qp - kp < window
    return mask


def _live(live_ref, b, iq, ik, n_q, n_kv):
    return live_ref[(b * n_q + iq) * n_kv + ik] > 0


def _fwd_kernel(live_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, qpos_ref,
                kpos_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                causal, window, scale, H, n_q, n_kv):
    b, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_live(live_ref, b // H, iq, ik, n_q, n_kv))
    def _body():
        q = q_ref[0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        mask = _tile_mask(qseg_ref[0], kseg_ref[0], qpos_ref[0], kpos_ref[0],
                          causal=causal, window=window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # Masked entries contribute exactly zero (fully-masked rows would
        # otherwise see exp(NEG_INF - NEG_INF) = 1).
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, ...] = (acc_scr[...] / l_safe).astype(out_ref.dtype)
        lse_ref[0, ...] = jnp.where(l > 0.0, m_scr[...] + jnp.log(l_safe), 0.0)


def _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
                  kseg_ref, qpos_ref, kpos_ref, *, causal, window, scale):
    """Recomputed softmax tile p and score gradient ds ([bq, bk] each),
    plus the f32 q/k/do tiles they came from."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    mask = _tile_mask(qseg_ref[0], kseg_ref[0], qpos_ref[0], kpos_ref[0],
                      causal=causal, window=window)
    p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bk]
    ds = p * (dp - delta_ref[0]) * scale
    return q, k, do, p, ds


def _dq_kernel(live_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               qseg_ref, kseg_ref, qpos_ref, kpos_ref, dq_ref, dq_scr, *,
               causal, window, scale, H, n_q, n_kv):
    b, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_live(live_ref, b // H, iq, ik, n_q, n_kv))
    def _body():
        _, k, _, _, ds = _probs_and_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
            kseg_ref, qpos_ref, kpos_ref, causal=causal, window=window,
            scale=scale)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == n_kv - 1)
    def _finalize():
        dq_ref[0, ...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(live_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                qseg_ref, kseg_ref, qpos_ref, kpos_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, causal, window, scale, Hkv, n_q, n_kv, n_t):
    """Grid (B*Hkv, nK, nQ * group): the innermost axis walks every
    (GQA group member, Q tile) pair, so dk/dv accumulate the full group
    sum in scratch and are emitted once per KV head -- no repeated K/V
    and no post-hoc reduction."""
    b, ik, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_live(live_ref, b // Hkv, t % n_q, ik, n_q, n_kv))
    def _body():
        q, _, do, p, ds = _probs_and_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
            kseg_ref, qpos_ref, kpos_ref, causal=causal, window=window,
            scale=scale)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0, ...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call wrappers (flat [B*H, T, D] layouts; q-side rows [B, T, 1],
# kv-side rows [B, 1, T]; index maps take the prefetched mask last).
# ----------------------------------------------------------------------
def _kv_head(b, H, Hkv):
    """Flat q index [0, B*H) -> flat kv index [0, B*Hkv) (GQA grouping:
    q head h reads kv head h // (H // Hkv), matching _gqa_* in
    repro.models.attention)."""
    return (b // H) * Hkv + (b % H) // (H // Hkv)


def _forward(qf, kf, vf, q_seg, kv_seg, q_pos, kv_pos, live, *, causal,
             window, scale, bq, bk, interpret):
    BH, Tq, D = qf.shape
    Tkv = kf.shape[1]
    B = q_seg.shape[0]
    H, Hkv = BH // B, kf.shape[0] // B
    n_q, n_kv = Tq // bq, Tkv // bk
    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, scale=scale, H=H,
        n_q=n_q, n_kv=n_kv)
    kvh = functools.partial(_kv_head, H=H, Hkv=Hkv)
    q_row = pl.BlockSpec((1, bq, 1), lambda b, iq, ik, _: (b // H, iq, 0))
    k_row = pl.BlockSpec((1, 1, bk), lambda b, iq, ik, _: (b // H, 0, ik))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, iq, ik, _: (b, iq, 0)),
                pl.BlockSpec((1, bk, D), lambda b, iq, ik, _: (kvh(b), ik, 0)),
                pl.BlockSpec((1, bk, D), lambda b, iq, ik, _: (kvh(b), ik, 0)),
                q_row, k_row, q_row, k_row,
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, iq, ik, _: (b, iq, 0)),
                pl.BlockSpec((1, bq, 1), lambda b, iq, ik, _: (b, iq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),    # running max m
                pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
                pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(live, qf, kf, vf, q_seg, kv_seg, q_pos, kv_pos)


def _backward(qf, kf, vf, dof, lse, delta, q_seg, kv_seg, q_pos, kv_pos,
              live, *, causal, window, scale, bq, bk, interpret):
    BH, Tq, D = qf.shape
    BHkv, Tkv, _ = kf.shape
    B = q_seg.shape[0]
    H, Hkv = BH // B, BHkv // B
    g = H // Hkv
    n_q, n_kv = Tq // bq, Tkv // bk
    kvh = functools.partial(_kv_head, H=H, Hkv=Hkv)
    args = (live, qf, kf, vf, dof, lse, delta, q_seg, kv_seg, q_pos, kv_pos)

    q_tile = pl.BlockSpec((1, bq, D), lambda b, iq, ik, _: (b, iq, 0))
    q_col = pl.BlockSpec((1, bq, 1), lambda b, iq, ik, _: (b, iq, 0))
    q_row = pl.BlockSpec((1, bq, 1), lambda b, iq, ik, _: (b // H, iq, 0))
    k_row = pl.BlockSpec((1, 1, bk), lambda b, iq, ik, _: (b // H, 0, ik))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          scale=scale, H=H, n_q=n_q, n_kv=n_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_q, n_kv),
            in_specs=[
                q_tile,
                pl.BlockSpec((1, bk, D), lambda b, iq, ik, _: (kvh(b), ik, 0)),
                pl.BlockSpec((1, bk, D), lambda b, iq, ik, _: (kvh(b), ik, 0)),
                q_tile, q_col, q_col, q_row, k_row, q_row, k_row,
            ],
            out_specs=q_tile,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), qf.dtype),
        interpret=interpret,
    )(*args)

    # dk/dv grid walks (group member, Q tile) pairs innermost so each KV
    # head's scratch accumulates the whole GQA group before one emit.
    def qb(b, t):
        return (b // Hkv) * H + (b % Hkv) * g + t // n_q

    kv_tile = pl.BlockSpec((1, bk, D), lambda b, ik, t, _: (b, ik, 0))
    q_tile = pl.BlockSpec((1, bq, D), lambda b, ik, t, _: (qb(b, t), t % n_q, 0))
    q_col = pl.BlockSpec((1, bq, 1), lambda b, ik, t, _: (qb(b, t), t % n_q, 0))
    q_row = pl.BlockSpec((1, bq, 1), lambda b, ik, t, _: (b // Hkv, t % n_q, 0))
    k_row = pl.BlockSpec((1, 1, bk), lambda b, ik, t, _: (b // Hkv, 0, ik))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          scale=scale, Hkv=Hkv, n_q=n_q, n_kv=n_kv,
                          n_t=n_q * g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BHkv, n_kv, n_q * g),
            in_specs=[q_tile, kv_tile, kv_tile, q_tile, q_col, q_col,
                      q_row, k_row, q_row, k_row],
            out_specs=[kv_tile, kv_tile],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, Tkv, D), kf.dtype),
            jax.ShapeDtypeStruct((BHkv, Tkv, D), vf.dtype),
        ],
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ----------------------------------------------------------------------
# custom_vjp assembly.
# ----------------------------------------------------------------------
def _rows(q_seg, kv_seg, q_pos, kv_pos):
    """[B, T] seg/pos -> the kernels' q-side [B, Tq, 1] columns and
    kv-side [B, 1, Tkv] rows (free reshapes)."""
    return (q_seg[:, :, None], kv_seg[:, None, :], q_pos[:, :, None],
            kv_pos[:, None, :])


@functools.lru_cache(maxsize=None)
def _make_diff_flash(causal, window, bq, bk, interpret, block_skip):
    def _prep(q, q_seg, kv_seg, q_pos, kv_pos):
        B, H, Tq, D = q.shape
        scale = 1.0 / np.sqrt(D)
        if block_skip:
            live = live_tile_mask(q_seg, kv_seg, q_pos, kv_pos, block_q=bq,
                                  block_kv=bk, causal=causal, window=window)
            live = live.astype(jnp.int32)
        else:
            live = jnp.ones(
                (B, Tq // bq, kv_seg.shape[1] // bk), jnp.int32)
        return scale, live.reshape(-1)

    def _run_fwd(q, k, v, q_seg, kv_seg, q_pos, kv_pos):
        B, H, Tq, D = q.shape
        Hkv, Tkv = k.shape[1], k.shape[2]
        scale, live = _prep(q, q_seg, kv_seg, q_pos, kv_pos)
        out, lse = _forward(
            q.reshape(B * H, Tq, D), k.reshape(B * Hkv, Tkv, D),
            v.reshape(B * Hkv, Tkv, D), *_rows(q_seg, kv_seg, q_pos, kv_pos),
            live, causal=causal, window=window, scale=scale, bq=bq, bk=bk,
            interpret=interpret)
        return out.reshape(B, H, Tq, D), lse, live

    @jax.custom_vjp
    def flash(q, k, v, q_seg, kv_seg, q_pos, kv_pos):
        out, _, _ = _run_fwd(q, k, v, q_seg, kv_seg, q_pos, kv_pos)
        return out

    def fwd(q, k, v, q_seg, kv_seg, q_pos, kv_pos):
        out, lse, live = _run_fwd(q, k, v, q_seg, kv_seg, q_pos, kv_pos)
        return out, (q, k, v, q_seg, kv_seg, q_pos, kv_pos, out, lse, live)

    def bwd(res, do):
        q, k, v, q_seg, kv_seg, q_pos, kv_pos, out, lse, live = res
        B, H, Tq, D = q.shape
        Hkv, Tkv = k.shape[1], k.shape[2]
        scale = 1.0 / np.sqrt(D)
        dof = do.reshape(B * H, Tq, D)
        outf = out.reshape(B * H, Tq, D)
        delta = (dof.astype(jnp.float32) * outf.astype(jnp.float32)).sum(
            -1, keepdims=True)  # [B*H, Tq, 1]
        dq, dk, dv = _backward(
            q.reshape(B * H, Tq, D), k.reshape(B * Hkv, Tkv, D),
            v.reshape(B * Hkv, Tkv, D), dof, lse, delta,
            *_rows(q_seg, kv_seg, q_pos, kv_pos), live, causal=causal,
            window=window, scale=scale, bq=bq, bk=bk, interpret=interpret)
        zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
        return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
                zero(q_seg), zero(kv_seg), zero(q_pos), zero(kv_pos))

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_seg: jnp.ndarray,
    kv_seg: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = True,
    block_skip: bool = True,
) -> jnp.ndarray:
    """q [B,H,Tq,D]; k,v [B,Hkv,Tkv,D] with H a multiple of Hkv (GQA
    groups resolved by BlockSpec index maps -- K/V are never
    materialized per Q head); seg/pos [B,T*] int32.

    Differentiable (custom VJP through Pallas dq/dk/dv kernels) and
    block-sparse over fully-masked (Q tile, KV tile) pairs when
    ``block_skip`` is on.  T must divide by the block sizes -- the
    model-level wrapper (``repro.models.attention``) pads arbitrary
    lengths before calling in here.
    """
    B, H, Tq, D = q.shape
    Hkv, Tkv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of kv heads {Hkv}")
    bq = min(block_q, Tq)
    bk = min(block_kv, Tkv)
    if Tq % bq or Tkv % bk:
        raise ValueError(f"T ({Tq},{Tkv}) must be divisible by blocks ({bq},{bk})")
    window = None if window is None else int(window)
    fn = _make_diff_flash(bool(causal), window, bq, bk, bool(interpret),
                          bool(block_skip))
    return fn(q, k, v, q_seg.astype(jnp.int32), kv_seg.astype(jnp.int32),
              q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32))
