"""Selective-state-space blocks: Mamba-1 (falcon-mamba) and Mamba-2 (zamba2).

Segment-aware for packed post-balanced streams: the recurrent state
resets at example boundaries (seg change) so balancing rearrangements
stay consequence-invariant for SSMs too.

Training path, two backends behind ``mamba1_scan``/``mamba2_scan``
(``backend=``):

  "scan"    chunked sequential scan -- outer ``lax.scan`` over chunks
            carries only the small state; the chunk body is
            ``jax.checkpoint``ed so backward keeps per-chunk states
            instead of per-step residuals (the standard memory
            treatment for long-sequence SSM training).
  "pallas"  the fused kernel (``kernels/selective_scan.py``): channel
            blocks across the grid, time walked in VMEM-resident
            chunks, chunk-checkpointed custom VJP.  Mamba-2's
            per-head scalar decay maps onto the same kernel by
            broadcasting head quantities over the head dim (the
            broadcasts sit outside the kernel's custom_vjp, so their
            gradient reductions are plain JAX transposes).

Decode path: O(1) per-token state update (this is why the long_500k
shape is SSM/hybrid-only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "causal_conv1d",
    "mamba1_scan",
    "mamba2_scan",
    "mamba1_block",
    "mamba2_block",
    "mamba1_decode_step",
    "mamba2_decode_step",
]


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, seg: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv, segment-aware.  x [B,T,C]; w [K,C]; seg [B,T]."""
    K = w.shape[0]
    out = x * w[-1]
    for i in range(1, K):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        sseg = jnp.pad(seg, ((0, 0), (i, 0)))[:, : seg.shape[1]]
        ok = (sseg == seg) & (seg > 0)
        out = out + shifted * ok[..., None] * w[K - 1 - i]
    return out


def _chunked_scan(step_fn, state0, xs, chunk: int):
    """lax.scan over chunks; chunk body checkpointed; xs leaves are [T, ...]."""
    T = jax.tree_util.tree_leaves(xs)[0].shape[0]
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T

    def pad_t(a):
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

    xs_p = jax.tree_util.tree_map(pad_t, xs)
    xs_c = jax.tree_util.tree_map(
        lambda a: a.reshape((n_chunks, chunk) + a.shape[1:]), xs_p
    )

    @jax.checkpoint
    def chunk_body(state, chunk_xs):
        return jax.lax.scan(step_fn, state, chunk_xs)

    state_f, ys = jax.lax.scan(chunk_body, state0, xs_c)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((n_chunks * chunk,) + a.shape[2:])[:T], ys
    )
    return state_f, ys


def _fit_block(size: int, target: int) -> int:
    """Largest block <= target dividing size (kernel divisibility)."""
    for b in range(min(target, size), 0, -1):
        if size % b == 0:
            return b
    return 1


def mamba1_scan(u, delta, A, B, C, D, seg, *, chunk: int = 256, h0=None,
                backend: str = "scan", block_d: int = 128):
    """Selective scan.  Shapes (single stream; vmap over batch):
      u [T, di], delta [T, di], A [di, N], B [T, N], C [T, N], D [di],
      seg [T].  Returns (y [T, di], h_final [di, N])."""
    if backend == "pallas":
        if h0 is not None:
            raise ValueError("pallas selective scan starts from h=0 "
                             "(h0 is a scan-backend knob)")
        from repro.kernels.ops import selective_scan_op

        T, di = u.shape
        return selective_scan_op(
            u, delta, A, B, C, D, seg,
            block_d=_fit_block(di, block_d), chunk=_fit_block(T, chunk),
            return_state=True)
    if backend != "scan":
        raise ValueError(f"unknown ssm backend {backend!r}")
    keep = (seg > 0) & (seg == jnp.concatenate([seg[:1], seg[:-1]]))
    keep = keep.at[0].set(False)  # first token always starts a segment

    def step(h, inp):
        u_t, d_t, B_t, C_t, k_t = inp
        dA = jnp.exp(d_t[:, None] * A)  # [di, N]
        h = jnp.where(k_t, h, 0.0) * dA + (d_t * u_t)[:, None] * B_t[None, :]
        y = (h * C_t[None, :]).sum(-1) + D * u_t
        return h, y

    h0 = jnp.zeros((u.shape[1], A.shape[1]), jnp.float32) if h0 is None else h0
    hf, y = _chunked_scan(
        step, h0, (u.astype(jnp.float32), delta.astype(jnp.float32),
                   B.astype(jnp.float32), C.astype(jnp.float32), keep), chunk
    )
    return y.astype(u.dtype), hf


def mamba2_scan(x, delta, A_log, B, C, D, seg, *, chunk: int = 256, h0=None,
                backend: str = "scan", block_d: int = 128):
    """Mamba-2 SSD (scalar decay per head).  Shapes (single stream):
      x [T, H, P], delta [T, H], A_log [H], B [T, N], C [T, N], D [H],
      seg [T].  Returns (y [T, H, P], h_final [H, P, N])."""
    A = -jnp.exp(A_log.astype(jnp.float32))  # [H]
    if backend == "pallas":
        if h0 is not None:
            raise ValueError("pallas selective scan starts from h=0 "
                             "(h0 is a scan-backend knob)")
        from repro.kernels.ops import selective_scan_op

        T, H, P = x.shape
        N = B.shape[-1]
        # Broadcast per-head scalars over the head dim: channel (h, p)
        # runs the mamba1 recurrence with dt/A/D of head h.
        u2 = x.reshape(T, H * P)
        d2 = jnp.repeat(delta, P, axis=1)
        A2 = jnp.broadcast_to(jnp.repeat(A, P)[:, None], (H * P, N))
        D2 = jnp.repeat(D, P)
        y, hf = selective_scan_op(
            u2, d2, A2, B, C, D2, seg,
            block_d=_fit_block(H * P, block_d), chunk=_fit_block(T, chunk),
            return_state=True)
        return y.reshape(T, H, P), hf.reshape(H, P, N)
    if backend != "scan":
        raise ValueError(f"unknown ssm backend {backend!r}")
    keep = (seg > 0) & (seg == jnp.concatenate([seg[:1], seg[:-1]]))
    keep = keep.at[0].set(False)

    def step(h, inp):
        x_t, d_t, B_t, C_t, k_t = inp  # [H,P], [H], [N], [N], scalar
        dA = jnp.exp(d_t * A)  # [H]
        h = jnp.where(k_t, h, 0.0) * dA[:, None, None] + (
            (d_t[:, None] * x_t)[..., None] * B_t[None, None, :]
        )
        y = (h * C_t[None, None, :]).sum(-1) + D[:, None] * x_t
        return h, y

    H, P = x.shape[1], x.shape[2]
    N = B.shape[-1]
    h0 = jnp.zeros((H, P, N), jnp.float32) if h0 is None else h0
    hf, y = _chunked_scan(
        step, h0, (x.astype(jnp.float32), delta.astype(jnp.float32),
                   B.astype(jnp.float32), C.astype(jnp.float32), keep), chunk
    )
    return y.astype(x.dtype), hf


# ----------------------------------------------------------------------
# Full blocks (projections + conv + scan + gate), matching param layout
# in repro.models.model.
# ----------------------------------------------------------------------
def _per_stream_scan(backend, scan, streams, weights):
    """``scan(*streams, *weights)`` over [B, ...] streams.  The Pallas
    kernel runs per DP shard (XLA cannot partition a Mosaic kernel); the
    lax.scan backend is left to XLA's sharding propagation."""
    if backend == "pallas":
        from repro.kernels.ops import per_dp_shard

        return per_dp_shard(scan, *streams, replicated=weights)
    return scan(*streams, *weights)


def mamba1_block(p, x, seg, *, ssm_state: int, chunk: int = 256,
                 backend: str = "scan", block_d: int = 128):
    """x [B,T,d] -> [B,T,d].  p: dict of this block's params."""
    xz = jnp.einsum("btd,de->bte", x, p["in_proj"])  # [B,T,2*di]
    xi, z = jnp.split(xz, 2, axis=-1)
    xi = causal_conv1d(xi, p["conv_w"], seg)
    xi = jax.nn.silu(xi)
    dbc = jnp.einsum("bte,ef->btf", xi, p["x_proj"])  # [B,T,dt_rank+2N]
    dt_rank = p["dt_proj"].shape[0]
    dt, Bm, Cm = jnp.split(dbc, [dt_rank, dt_rank + ssm_state], axis=-1)
    delta = jax.nn.softplus(jnp.einsum("btr,re->bte", dt, p["dt_proj"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    def scan(u, delta, Bm, Cm, seg, A, D):
        def one(u_s, delta_s, B_s, C_s, seg_s):
            return mamba1_scan(u_s, delta_s, A, B_s, C_s, D, seg_s,
                               chunk=chunk, backend=backend,
                               block_d=block_d)[0]
        return jax.vmap(one)(u, delta, Bm, Cm, seg)

    y = _per_stream_scan(backend, scan, (xi, delta, Bm, Cm, seg),
                         (A, p["D"]))
    y = y * jax.nn.silu(z)
    return jnp.einsum("bte,ed->btd", y, p["out_proj"])


def mamba2_block(p, x, seg, *, ssm_state: int, headdim: int, chunk: int = 256,
                 backend: str = "scan", block_d: int = 128):
    """x [B,T,d] -> [B,T,d] (Mamba-2, n_groups=1)."""
    di = p["out_proj"].shape[0]
    H = di // headdim
    zxbcdt = jnp.einsum("btd,de->bte", x, p["in_proj"])
    z, xi, Bm, Cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + ssm_state, 2 * di + 2 * ssm_state], axis=-1
    )
    xi = causal_conv1d(xi, p["conv_w"], seg)
    xi = jax.nn.silu(xi)
    delta = jax.nn.softplus(dt + p["dt_bias"])  # [B,T,H]
    xh = xi.reshape(xi.shape[0], xi.shape[1], H, headdim)

    def scan(x, delta, Bm, Cm, seg, A_log, D):
        def one(x_s, delta_s, B_s, C_s, seg_s):
            return mamba2_scan(x_s, delta_s, A_log, B_s, C_s, D, seg_s,
                               chunk=chunk, backend=backend,
                               block_d=block_d)[0]
        return jax.vmap(one)(x, delta, Bm, Cm, seg)

    y = _per_stream_scan(backend, scan, (xh, delta, Bm, Cm, seg),
                         (p["A_log"], p["D"]))
    y = y.reshape(x.shape[0], x.shape[1], di)
    y = y * jax.nn.silu(z)
    return jnp.einsum("bte,ed->btd", y, p["out_proj"])


# ----------------------------------------------------------------------
# Decode: O(1) state update per new token.
# ----------------------------------------------------------------------
def mamba1_decode_step(p, x_t, state, *, ssm_state: int):
    """x_t [B,d]; state dict {conv: [B,K-1,di], h: [B,di,N]}."""
    xz = jnp.einsum("bd,de->be", x_t, p["in_proj"])
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_in = jnp.concatenate([state["conv"], xi[:, None, :]], axis=1)  # [B,K,di]
    xi = (conv_in * p["conv_w"][None]).sum(axis=1)
    new_conv = conv_in[:, 1:]
    xi = jax.nn.silu(xi)
    dbc = jnp.einsum("be,ef->bf", xi, p["x_proj"])
    dt_rank = p["dt_proj"].shape[0]
    dt, Bm, Cm = jnp.split(dbc, [dt_rank, dt_rank + ssm_state], axis=-1)
    delta = jax.nn.softplus(jnp.einsum("br,re->be", dt, p["dt_proj"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(delta[..., None] * A[None])  # [B,di,N]
    h = state["h"] * dA + (delta * xi)[..., None] * Bm[:, None, :]
    y = (h * Cm[:, None, :]).sum(-1) + p["D"] * xi
    y = y * jax.nn.silu(z)
    out = jnp.einsum("be,ed->bd", y.astype(x_t.dtype), p["out_proj"])
    return out, {"conv": new_conv, "h": h}


def mamba2_decode_step(p, x_t, state, *, ssm_state: int, headdim: int):
    di = p["out_proj"].shape[0]
    H = di // headdim
    zxbcdt = jnp.einsum("bd,de->be", x_t, p["in_proj"])
    z, xi, Bm, Cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + ssm_state, 2 * di + 2 * ssm_state], axis=-1
    )
    conv_in = jnp.concatenate([state["conv"], xi[:, None, :]], axis=1)
    xi = (conv_in * p["conv_w"][None]).sum(axis=1)
    new_conv = conv_in[:, 1:]
    xi = jax.nn.silu(xi)
    delta = jax.nn.softplus(dt + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(delta * A[None])  # [B,H]
    xh = xi.reshape(-1, H, headdim)
    h = state["h"] * dA[..., None, None] + (
        (delta[..., None] * xh)[..., None] * Bm[:, None, None, :]
    )
    y = (h * Cm[:, None, None, :]).sum(-1) + p["D"][None, :, None] * xh
    y = y.reshape(-1, di) * jax.nn.silu(z)
    out = jnp.einsum("be,ed->bd", y.astype(x_t.dtype), p["out_proj"])
    return out, {"conv": new_conv, "h": h}
