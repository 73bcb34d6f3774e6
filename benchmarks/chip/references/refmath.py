"""Arithmetic shared by the plain references: seeded weights, float32
operations at full matmul precision, and the fp8 rounding of the
lower-precision control.  Nothing here imports the program."""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    seed = int(seed) % (1 << 63)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(shapes: dict, seed: int, dtype):
    """Weights made on the device in one jitted call.  ``shapes`` is a
    nested dict whose leaves are ``(shape, std)``: leaf ``path`` draws a
    truncated normal (+-3 std) from ``fold_in(key(seed), crc32(path))``;
    a std of None is a norm scale, initialised to one."""

    def make(key):
        def leaf(path, spec):
            shape, std = spec
            if std is None:
                return jnp.ones(shape, dtype)
            k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()))
            x = jax.random.truncated_normal(k, -3.0, 3.0, shape, jnp.float32)
            return (x * std).astype(dtype)
        return jax.tree_util.tree_map_with_path(leaf, shapes, is_leaf=_is_spec)

    return jax.jit(make)(seed_key(seed))


def matrix(*shape) -> tuple:
    """A weight matrix's spec: std 1/sqrt(fan_in)."""
    return shape, 1.0 / math.sqrt(shape[-2])


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda v: jnp.linalg.norm(v.astype(jnp.float32).ravel()), tree)


def leaf_norms(tree) -> dict[str, float]:
    """Each leaf's float32 norm, by key path; one jitted call for the tree."""
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_leaves_with_path(_norms(tree))}


@jax.custom_vjp
def fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale (its largest
    magnitude maps to 448).  Gradients pass straight through: the
    backward matmuls take the rounded forward operands and float32
    cotangents."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return jnp.clip(x / scale, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32) * scale


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))


def matmul(quant):
    """einsum at full float32 precision; ``quant="fp8"`` rounds both
    operands first (the control)."""
    q = fp8 if quant == "fp8" else (lambda x: x)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a.astype(jnp.float32)), q(b.astype(jnp.float32)),
                          precision=HIGHEST)
    return mm


def rms_norm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layer_norm(x, scale=None, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y if scale is None else y * scale


def gelu(x):
    """GELU, tanh form."""
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, pos, theta):
    """x [T, heads, hd]: rotate-half rotary embedding at positions pos."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_block(T: int) -> int:
    """Query rows per attention or logits block."""
    return next(r for r in (1024, 768, 512, 256, 128, 64, 32, 16, 8, 1) if T % r == 0)


def attention(mm, q, k, v, n, causal):
    """q [T,H,hd], k/v [T,Hkv,hd] (grouped-query when Hkv < H); keys at
    or past n are masked, and with ``causal`` keys after the query."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    rows = row_block(T)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows).reshape(rows, Hkv, g, hd)
        s = mm("qhgd,khd->hgqk", qb, k) / math.sqrt(hd)
        qpos = i * rows + jnp.arange(rows)
        ok = kpos[None, :] < n
        if causal:
            ok = ok & (kpos[None, :] <= qpos[:, None])
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return mm("hgqk,khd->qhgd", p, v).reshape(rows, H, hd)

    return jax.lax.map(jax.checkpoint(block), jnp.arange(T // rows)).reshape(T, H, hd)


def block(mm, lp, x, n, *, heads, kv_heads, theta, causal, norm, mlp):
    """One pre-norm transformer block: attention with rotary positions
    0..T-1, then the MLP, each added to the residual."""
    T = x.shape[0]
    hd = lp["wq"].shape[-1] // heads
    pos = jnp.arange(T)
    h = norm(x, lp.get("attn_norm"))
    q = rope(mm("td,de->te", h, lp["wq"]).reshape(T, heads, hd), pos, theta)
    k = rope(mm("td,de->te", h, lp["wk"]).reshape(T, kv_heads, hd), pos, theta)
    v = mm("td,de->te", h, lp["wv"]).reshape(T, kv_heads, hd)
    o = attention(mm, q, k, v, n, causal)
    x = x + mm("te,ed->td", o.reshape(T, heads * hd), lp["wo"])
    return x + mlp(mm, norm(x, lp.get("mlp_norm")), lp)


def swiglu(mm, h, lp):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, lp["w_gate"]))
              * mm("td,df->tf", h, lp["w_up"]), lp["w_down"])


def gelu_mlp(mm, h, lp):
    return mm("tf,fd->td", gelu(mm("td,df->tf", h, lp["w_in"])), lp["w_out"])


def layer(layers: dict, i: int) -> dict:
    return jax.tree_util.tree_map(lambda a: a[i], layers)
