"""Mamba-1 selective scan, Pallas TPU kernel (training grade).

TPU adaptation of the CUDA selective-scan: instead of warp-level
parallel prefix, we tile the CHANNEL dimension across the grid (each
channel block is an independent recurrence -> trivially parallel across
TPU cores) and walk TIME in VMEM-resident chunks, carrying the [bd, N]
state in scratch across sequential grid steps.  Segment-aware: the
state resets where the segment id changes (packed post-balanced
streams).

Grid: (n_channel_blocks, n_time_chunks) -- time innermost (sequential
on TPU), channels outer (parallelizable).

Differentiable: the forward kernel additionally emits the state at
every chunk boundary (``ckpt [n_t, di, N]`` -- the same residual style
as the flash backward's lse, one checkpoint per tile of sequential
work) plus the final state, and a reverse-time backward kernel
recomputes the per-step states inside each chunk from its checkpoint
while propagating the state cotangent across chunks in scratch.  The
recurrence

    h_t = keep_t * exp(dt_t A) * h_{t-1} + (dt_t u_t) B_t
    y_t = <h_t, C_t> + D u_t

gives, with ``g_t = dL/dh_t`` accumulated as
``g_t = dy_t C_t + keep_{t+1} exp(dt_{t+1} A) g_{t+1}``:

    du_t  = D dy_t + dt_t <g_t, B_t>
    ddt_t = <g_t, keep_t h_{t-1} A e^{dt_t A}> + u_t <g_t, B_t>
    dA   += keep_t dt_t g_t h_{t-1} e^{dt_t A}      (summed over t)
    dB_t  = sum_d g_t dt_t u_t       dC_t = sum_d dy_t h_t
    dD   += dy_t u_t                                (summed over t)

``selective_scan`` wraps the pair in a ``jax.custom_vjp`` (seg gets a
symbolic-zero cotangent like the flash kernel's seg/pos inputs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan"]


def _row(ref, t):
    """Row ``t`` of a [ct, w] VMEM block as a [1, w] f32 value (a
    dynamic sublane load; value indexing would lower to dynamic_slice,
    which Mosaic rejects)."""
    return ref[pl.ds(t, 1), :].astype(jnp.float32)


def _keep(ref, t, bd):
    """keep_t as a [1, bd] bool row, for ``jnp.where`` against [N, bd]
    values (Mosaic will not broadcast a [1, 1] operand to [N, bd] in one
    arithmetic op)."""
    return jnp.broadcast_to(ref[pl.ds(t, 1), :], (1, bd)) > 0


def _fwd_kernel(u_ref, dt_ref, At_ref, B_ref, C_ref, D_ref, keep_ref,
                y_ref, ckpt_ref, hfin_ref, h_scr, *, chunk, n_t):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    ckpt_ref[0] = h_scr[...]  # state entering this chunk (bwd residual)
    At = At_ref[...].astype(jnp.float32)    # [N, bd]
    Dv = D_ref[...].astype(jnp.float32)     # [1, bd]

    def step(t, h):
        u, dt = _row(u_ref, t), _row(dt_ref, t)        # [1, bd]
        keep = _keep(keep_ref, t, u.shape[1])          # [1, bd] bool
        b = _row(B_ref, t).T                            # [N, 1]
        c = _row(C_ref, t).T
        h = jnp.where(keep, h, 0.0) * jnp.exp(dt * At) + b * (dt * u)
        y_ref[pl.ds(t, 1), :] = (h * c).sum(axis=0, keepdims=True) + Dv * u
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(it == n_t - 1)
    def _emit_final():
        hfin_ref[...] = h


def _bwd_kernel(u_ref, dt_ref, At_ref, B_ref, C_ref, D_ref, keep_ref,
                ckpt_ref, dy_ref, dhf_ref,
                du_ref, ddt_ref, dB_ref, dC_ref, dA_ref, dD_ref,
                g_scr, dA_scr, dD_scr, hs_scr, *, chunk, n_t):
    it = pl.program_id(1)  # 0 = LAST time chunk (index maps reverse)

    @pl.when(it == 0)
    def _init():
        g_scr[...] = dhf_ref[...]  # dL/dh_final enters the recurrence
        dA_scr[...] = jnp.zeros_like(dA_scr)
        dD_scr[...] = jnp.zeros_like(dD_scr)

    At = At_ref[...].astype(jnp.float32)    # [N, bd]
    Dv = D_ref[...].astype(jnp.float32)     # [1, bd]

    # Recompute the states of this chunk from its checkpoint:
    # hs_scr[t] is the state entering step t, hs_scr[t + 1] the state
    # after it.
    hs_scr[0] = ckpt_ref[0]

    def fstep(t, h):
        u, dt = _row(u_ref, t), _row(dt_ref, t)
        keep = _keep(keep_ref, t, u.shape[1])
        h = (jnp.where(keep, h, 0.0) * jnp.exp(dt * At)
             + _row(B_ref, t).T * (dt * u))
        hs_scr[t + 1] = h
        return h

    jax.lax.fori_loop(0, chunk, fstep, ckpt_ref[0])

    def bstep(r, carry):
        g_nxt, dAa, dDa = carry
        t = chunk - 1 - r
        u, dt, dy = _row(u_ref, t), _row(dt_ref, t), _row(dy_ref, t)
        keep = _keep(keep_ref, t, u.shape[1])
        b, c = _row(B_ref, t).T, _row(C_ref, t).T   # [N, 1]
        h_t = hs_scr[t + 1]
        hm = jnp.where(keep, hs_scr[t], 0.0)
        dA_t = jnp.exp(dt * At)
        g = c * dy + g_nxt                                  # [N, bd]
        gB = (g * b).sum(axis=0, keepdims=True)             # [1, bd]
        du_ref[pl.ds(t, 1), :] = dy * Dv + dt * gB
        ddt_ref[pl.ds(t, 1), :] = (
            (g * hm * At * dA_t).sum(axis=0, keepdims=True) + u * gB)
        dB_ref[0, pl.ds(t, 1), :] = (g * (dt * u)).sum(
            axis=1, keepdims=True).T
        dC_ref[0, pl.ds(t, 1), :] = (h_t * dy).sum(axis=1, keepdims=True).T
        dAa = dAa + g * hm * dt * dA_t
        return jnp.where(keep, dA_t * g, 0.0), dAa, dDa + dy * u

    g, dAa, dDa = jax.lax.fori_loop(
        0, chunk, bstep, (g_scr[...], dA_scr[...], dD_scr[...]))
    g_scr[...] = g
    dA_scr[...] = dAa
    dD_scr[...] = dDa

    @pl.when(it == n_t - 1)
    def _emit():
        dA_ref[...] = dA_scr[...]
        dD_ref[...] = dD_scr[...]


def _fwd_call(u, delta, At, B, C, D2, keep, *, bd, ct, interpret):
    T, di = u.shape
    N = At.shape[0]
    n_d, n_t = di // bd, T // ct
    kernel = functools.partial(_fwd_kernel, chunk=ct, n_t=n_t)
    return pl.pallas_call(
        kernel,
        grid=(n_d, n_t),
        in_specs=[
            pl.BlockSpec((ct, bd), lambda id_, it: (it, id_)),   # u
            pl.BlockSpec((ct, bd), lambda id_, it: (it, id_)),   # delta
            pl.BlockSpec((N, bd), lambda id_, it: (0, id_)),     # A^T
            pl.BlockSpec((ct, N), lambda id_, it: (it, 0)),      # B
            pl.BlockSpec((ct, N), lambda id_, it: (it, 0)),      # C
            pl.BlockSpec((1, bd), lambda id_, it: (0, id_)),     # D
            pl.BlockSpec((ct, 1), lambda id_, it: (it, 0)),      # keep
        ],
        out_specs=[
            pl.BlockSpec((ct, bd), lambda id_, it: (it, id_)),       # y
            pl.BlockSpec((1, N, bd), lambda id_, it: (it, 0, id_)),  # ckpt
            pl.BlockSpec((N, bd), lambda id_, it: (0, id_)),         # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, di), jnp.float32),
            jax.ShapeDtypeStruct((n_t, N, di), jnp.float32),
            jax.ShapeDtypeStruct((N, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        interpret=interpret,
    )(u, delta, At, B, C, D2, keep)


def _bwd_call(u, delta, At, B, C, D2, keep, ckpt, dy, dhf, *, bd, ct,
              interpret):
    T, di = u.shape
    N = At.shape[0]
    n_d, n_t = di // bd, T // ct
    rev = lambda it: n_t - 1 - it  # noqa: E731 - shared reversed time index
    kernel = functools.partial(_bwd_kernel, chunk=ct, n_t=n_t)
    du, ddt, dBp, dCp, dAt, dD = pl.pallas_call(
        kernel,
        grid=(n_d, n_t),
        in_specs=[
            pl.BlockSpec((ct, bd), lambda id_, it: (rev(it), id_)),    # u
            pl.BlockSpec((ct, bd), lambda id_, it: (rev(it), id_)),    # delta
            pl.BlockSpec((N, bd), lambda id_, it: (0, id_)),           # A^T
            pl.BlockSpec((ct, N), lambda id_, it: (rev(it), 0)),       # B
            pl.BlockSpec((ct, N), lambda id_, it: (rev(it), 0)),       # C
            pl.BlockSpec((1, bd), lambda id_, it: (0, id_)),           # D
            pl.BlockSpec((ct, 1), lambda id_, it: (rev(it), 0)),       # keep
            pl.BlockSpec((1, N, bd), lambda id_, it: (rev(it), 0, id_)),
            pl.BlockSpec((ct, bd), lambda id_, it: (rev(it), id_)),    # dy
            pl.BlockSpec((N, bd), lambda id_, it: (0, id_)),           # dhf
        ],
        out_specs=[
            pl.BlockSpec((ct, bd), lambda id_, it: (rev(it), id_)),    # du
            pl.BlockSpec((ct, bd), lambda id_, it: (rev(it), id_)),    # ddt
            pl.BlockSpec((1, ct, N), lambda id_, it: (id_, rev(it), 0)),
            pl.BlockSpec((1, ct, N), lambda id_, it: (id_, rev(it), 0)),
            pl.BlockSpec((N, bd), lambda id_, it: (0, id_)),           # dA^T
            pl.BlockSpec((1, bd), lambda id_, it: (0, id_)),           # dD
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, di), jnp.float32),
            jax.ShapeDtypeStruct((T, di), jnp.float32),
            jax.ShapeDtypeStruct((n_d, T, N), jnp.float32),
            jax.ShapeDtypeStruct((n_d, T, N), jnp.float32),
            jax.ShapeDtypeStruct((N, di), jnp.float32),
            jax.ShapeDtypeStruct((1, di), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, bd), jnp.float32),           # g carry across chunks
            pltpu.VMEM((N, bd), jnp.float32),           # dA accumulator
            pltpu.VMEM((1, bd), jnp.float32),           # dD accumulator
            pltpu.VMEM((ct + 1, N, bd), jnp.float32),   # recomputed states
        ],
        interpret=interpret,
    )(u, delta, At, B, C, D2, keep, ckpt, dy, dhf)
    # Per-channel-block partials -> full dB/dC reductions.
    return du, ddt, dAt.T, dBp.sum(axis=0), dCp.sum(axis=0), dD[0]


@functools.lru_cache(maxsize=None)
def _make_diff_scan(bd, ct, interpret):
    # The kernels take f32 operands (single rows of a packed sub-32-bit
    # block are not addressable by a dynamic sublane load) and A
    # transposed to [N, di], so the state is lane-dense [N, bd].
    def _f32(*xs):
        return tuple(x.astype(jnp.float32) for x in xs)

    def _run(u, delta, A, B, C, D2, keep):
        y, ckpt, hf = _fwd_call(*_f32(u, delta, A.T, B, C, D2), keep,
                                bd=bd, ct=ct, interpret=interpret)
        return y.astype(u.dtype), ckpt, hf.T

    @jax.custom_vjp
    def scan(u, delta, A, B, C, D2, keep):
        y, _, hf = _run(u, delta, A, B, C, D2, keep)
        return y, hf

    def fwd(u, delta, A, B, C, D2, keep):
        y, ckpt, hf = _run(u, delta, A, B, C, D2, keep)
        return (y, hf), (u, delta, A, B, C, D2, keep, ckpt)

    def bwd(res, cts):
        u, delta, A, B, C, D2, keep, ckpt = res
        dy, dhf = cts
        du, ddt, dA, dB, dC, dD = _bwd_call(
            *_f32(u, delta, A.T, B, C, D2), keep, ckpt,
            *_f32(dy, dhf.T), bd=bd, ct=ct, interpret=interpret)
        return (du.astype(u.dtype), ddt.astype(delta.dtype),
                dA.astype(A.dtype), dB.astype(B.dtype), dC.astype(C.dtype),
                dD[None].astype(D2.dtype),
                np.zeros(keep.shape, jax.dtypes.float0))

    scan.defvjp(fwd, bwd)
    return scan


def selective_scan(
    u: jnp.ndarray,
    delta: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray,
    seg: jnp.ndarray,
    *,
    block_d: int = 128,
    chunk: int = 64,
    interpret: bool | None = None,
    return_state: bool = False,
):
    """u, delta [T, di]; A [di, N]; B, C [T, N]; D [di]; seg [T] int32.
    Returns y [T, di], or ``(y, h_final [di, N])`` with
    ``return_state=True``.  Differentiable (chunk-checkpointed custom
    VJP); ``interpret=None`` resolves via ``ops.default_interpret``."""
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    T, di = u.shape
    bd = min(block_d, di)
    ct = min(chunk, T)
    if di % bd or T % ct:
        raise ValueError(f"di={di} % {bd} or T={T} % {ct} != 0")

    prev = jnp.concatenate([seg[:1], seg[:-1]])
    keep = ((seg > 0) & (seg == prev)).at[0].set(False)
    keep = keep.astype(jnp.float32)[:, None]  # [T, 1], 1.0 = carry state
    D2 = D[None, :]  # [1, di]

    fn = _make_diff_scan(bd, ct, bool(interpret))
    y, hf = fn(u, delta, A, B, C, D2, keep)
    return (y, hf) if return_state else y
