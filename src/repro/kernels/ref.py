"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention_ref", "grouped_matmul_ref", "selective_scan_ref"]

NEG_INF = -2.0**30


def flash_attention_ref(q, k, v, q_seg, kv_seg, q_pos, kv_pos, *,
                        causal=True, window=None):
    """q [B,H,Tq,D]; k,v [B,H,Tkv,D]; seg/pos [B,T*].  Segment-aware
    softmax attention; rows with no valid key output 0."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = (q_seg[:, None, :, None] == kv_seg[:, None, None, :]) & (
        q_seg[:, None, :, None] > 0
    )
    if causal:
        mask &= kv_pos[:, None, None, :] <= q_pos[:, None, :, None]
    if window is not None:
        mask &= q_pos[:, None, :, None] - kv_pos[:, None, None, :] < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def selective_scan_ref(u, delta, A, B, C, D, seg):
    """Mamba-1 selective scan oracle.  u,delta [T,di]; A [di,N];
    B,C [T,N]; D [di]; seg [T].  State resets at segment boundaries."""
    T, di = u.shape
    N = A.shape[1]
    keep = (seg > 0) & (seg == jnp.concatenate([seg[:1], seg[:-1]]))
    keep = keep.at[0].set(False)

    def step(h, t):
        dA = jnp.exp(delta[t][:, None] * A)
        h = jnp.where(keep[t], h, 0.0) * dA + (delta[t] * u[t])[:, None] * B[t][None, :]
        y = (h * C[t][None, :]).sum(-1) + D * u[t]
        return h, y

    h0 = jnp.zeros((di, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, jnp.arange(T))
    return ys.astype(u.dtype)


def grouped_matmul_ref(x, w, group_offsets):
    """Grouped-GEMM oracle: x [M, K]; w [E, K, N]; group_offsets [E+1].
    Row s uses w[e] for offsets[e] <= s < offsets[e+1]; rows at or past
    offsets[E] are zero.  One dense matmul per expert (no [M, K, N]
    gather), so it runs at real MoE widths."""
    E = w.shape[0]
    rows = jnp.arange(x.shape[0])[:, None]
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for e in range(E):
        live = (rows >= group_offsets[e]) & (rows < group_offsets[e + 1])
        out = out + jnp.where(live, jnp.dot(x.astype(jnp.float32),
                                            w[e].astype(jnp.float32)), 0.0)
    return out.astype(x.dtype)
