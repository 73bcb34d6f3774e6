"""Attention for packed (post-balanced) batches: the unified backend.

Everything below is segment-aware: post-balancing produces per-shard
PACKED token streams (no padding, paper Alg 1/3), so attention must not
leak across example boundaries.  Convention: ``segment id 0 = padding``,
positive ids are example ids; positions restart at 0 per example.

Every attention site in the repo (encoder stacks, the LLM backbone,
enc-dec cross attention, decode) funnels through :func:`attention`,
selected by ``backend``:

  * ``reference``       full [Tq, Tkv] score matrix (oracle; small shapes).
  * ``chunked``         flash-style online-softmax over KV blocks
                        (lax.scan) with a recompute-based custom VJP --
                        the portable pure-jnp path.
  * ``chunked_unrolled``  same, scans unrolled (roofline cost probes).
  * ``flash``           the Pallas TPU kernel
                        (``repro.kernels.flash_attention``): fwd + bwd
                        kernels, custom VJP, block-level segment
                        sparsity.  Compiles via Mosaic on TPU; falls
                        back to interpret execution off-TPU.
  * ``flash_interpret`` the same kernel forced through the Pallas
                        interpreter (CPU-container validation mode).
  * ``windowed[...]``   window-chunked wrapper over any of the above
                        (see ``_windowed``); e.g. ``windowed_flash``.

Supports GQA (n_kv_heads < n_heads), RoPE applied by the caller,
sliding-window (h2o-danube / mistral), qk-norm (qwen3, applied by the
caller), causal & bidirectional, and cross-attention (whisper decoder).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["attention", "make_segment_mask", "windowed_variant",
           "ATTENTION_BACKENDS"]

ATTENTION_BACKENDS = ("reference", "chunked", "chunked_unrolled", "flash",
                      "flash_interpret")

NEG_INF = -2.0**30


def make_segment_mask(
    q_seg: jnp.ndarray,
    kv_seg: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    *,
    causal: bool,
    window: int | None,
) -> jnp.ndarray:
    """Boolean [.., Tq, Tkv] mask: True = attend."""
    same = (q_seg[..., :, None] == kv_seg[..., None, :]) & (q_seg[..., :, None] > 0)
    if causal:
        same &= kv_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        same &= q_pos[..., :, None] - kv_pos[..., None, :] < window
    return same


def _gqa_scores(q, k):
    """q [B,Tq,H,D], k [B,Tkv,Hkv,D] -> scores [B,H,Tq,Tkv]."""
    B, Tq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Tq, Hkv, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k)
    return s.reshape(B, Hkv * g, Tq, k.shape[1])


def _gqa_out(p, v):
    """p [B,H,Tq,Tkv], v [B,Tkv,Hkv,D] -> [B,Tq,H,D]."""
    B, H, Tq, Tkv = p.shape
    Hkv = v.shape[2]
    g = H // Hkv
    pg = p.reshape(B, Hkv, g, Tq, Tkv)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pg, v)
    return o.reshape(B, Tq, H, v.shape[-1])


def _reference(q, k, v, mask, scale):
    s = _gqa_scores(q, k).astype(jnp.float32) * scale
    s = jnp.where(mask[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # Fully-masked rows (padding queries) -> zero output.
    p = jnp.where(mask[:, None, :, :].any(axis=-1, keepdims=True), p, 0.0)
    return _gqa_out(p.astype(q.dtype), v)


def _chunked(q, k, v, q_seg, kv_seg, q_pos, kv_pos, *, causal, window,
             scale, block_q, block_kv, unroll=1):
    """Flash-style online softmax; scan over KV blocks.  Returns
    (out, m, l) -- softmax row statistics feed the custom backward."""
    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    bq = min(block_q, Tq)
    bkv = min(block_kv, Tkv)
    nq = -(-Tq // bq)
    nk = -(-Tkv // bkv)
    pad_q = nq * bq - Tq
    pad_k = nk * bkv - Tkv

    def padq(x, val=0):
        return jnp.pad(x, [(0, 0), (0, pad_q)] + [(0, 0)] * (x.ndim - 2),
                       constant_values=val)

    def padk(x, val=0):
        return jnp.pad(x, [(0, 0), (0, pad_k)] + [(0, 0)] * (x.ndim - 2),
                       constant_values=val)

    q = padq(q)
    q_seg = padq(q_seg)          # pad -> seg 0 = masked out
    q_pos = padq(q_pos)
    k = padk(k)
    v = padk(v)
    kv_seg = padk(kv_seg)
    kv_pos = padk(kv_pos, val=np.iinfo(np.int32).max if causal else 0)

    # Blocked views.
    qb = q.reshape(B, nq, bq, H, D)
    qsb = q_seg.reshape(B, nq, bq)
    qpb = q_pos.reshape(B, nq, bq)
    kb = k.reshape(B, nk, bkv, k.shape[2], D)
    vb = v.reshape(B, nk, bkv, v.shape[2], D)
    ksb = kv_seg.reshape(B, nk, bkv)
    kpb = kv_pos.reshape(B, nk, bkv)

    def process_block(qi, qs, qp, kj, vj, ks, kp):
        # qi [B,bq,H,D]; kj [B,bkv,Hkv,D]
        s = _gqa_scores(qi, kj).astype(jnp.float32) * scale  # [B,H,bq,bkv]
        m = make_segment_mask(qs, ks, qp, kp, causal=causal, window=window)
        return jnp.where(m[:, None], s, NEG_INF)

    def kv_scan(carry, blk):
        m_run, l_run, acc = carry
        kj, vj, ks, kp = blk

        def one_q(qi, qs, qp, m_r, l_r, a_r):
            s = process_block(qi, qs, qp, kj, vj, ks, kp)  # [B,H,bq,bkv]
            m_new = jnp.maximum(m_r, s.max(axis=-1))
            # Masked entries must contribute exactly zero (fully-masked
            # rows would otherwise see exp(NEG_INF - NEG_INF) = 1).
            p = jnp.exp(s - m_new[..., None]) * (s > NEG_INF / 2)
            corr = jnp.exp(m_r - m_new)
            l_new = l_r * corr + p.sum(axis=-1)
            pv = _gqa_out(p.astype(vj.dtype), vj)  # [B,bq,H,D]
            a_new = a_r * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
            return m_new, l_new, a_new

        m2, l2, a2 = jax.vmap(one_q, in_axes=(1, 1, 1, 1, 1, 1), out_axes=1)(
            qb, qsb, qpb, m_run, l_run, acc
        )
        return (m2, l2, a2), None

    m0 = jnp.full((B, nq, H, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, H, bq), jnp.float32)
    a0 = jnp.zeros((B, nq, bq, H, D), jnp.float32)
    blocks = (
        jnp.moveaxis(kb, 1, 0),
        jnp.moveaxis(vb, 1, 0),
        jnp.moveaxis(ksb, 1, 0),
        jnp.moveaxis(kpb, 1, 0),
    )
    (m_f, l_f, acc_f), _ = jax.lax.scan(kv_scan, (m0, l0, a0), blocks,
                                        unroll=unroll)
    l_safe = jnp.where(l_f == 0, 1.0, l_f)  # fully-masked query rows
    out = acc_f / l_safe.transpose(0, 1, 3, 2)[..., None]
    out = out.reshape(B, nq * bq, H, D)[:, :Tq]
    return out.astype(q.dtype), m_f, l_safe


# ----------------------------------------------------------------------
# Flash custom VJP: backward recomputes score blocks instead of storing
# per-KV-block residuals (without this, the scan's saved residuals are
# O(Tq * Tkv) and the train step does not fit HBM).
# ----------------------------------------------------------------------
def _flash_bwd_blocks(res, do, *, causal, window, scale, block_q, block_kv):
    q, k, v, q_seg, kv_seg, q_pos, kv_pos, out, m_f, l_f = res
    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    bq = min(block_q, Tq)
    bkv = min(block_kv, Tkv)
    nq = -(-Tq // bq)
    nk = -(-Tkv // bkv)
    pad_q = nq * bq - Tq
    pad_k = nk * bkv - Tkv

    def padq(x, val=0):
        return jnp.pad(x, [(0, 0), (0, pad_q)] + [(0, 0)] * (x.ndim - 2),
                       constant_values=val)

    def padk(x, val=0):
        return jnp.pad(x, [(0, 0), (0, pad_k)] + [(0, 0)] * (x.ndim - 2),
                       constant_values=val)

    qp_ = padq(q)
    do_ = padq(do.astype(jnp.float32))
    out_ = padq(out.astype(jnp.float32))
    qs_ = padq(q_seg)
    qpos_ = padq(q_pos)
    kp_ = padk(k)
    vp_ = padk(v)
    ks_ = padk(kv_seg)
    kpos_ = padk(kv_pos, val=np.iinfo(np.int32).max if causal else 0)

    qb = qp_.reshape(B, nq, bq, H, D)
    dob = do_.reshape(B, nq, bq, H, D)
    outb = out_.reshape(B, nq, bq, H, D)
    qsb = qs_.reshape(B, nq, bq)
    qpb = qpos_.reshape(B, nq, bq)
    kb = kp_.reshape(B, nk, bkv, Hkv, D)
    vb = vp_.reshape(B, nk, bkv, Hkv, D)
    ksb = ks_.reshape(B, nk, bkv)
    kpb = kpos_.reshape(B, nk, bkv)

    # Delta = rowsum(do * o)  [B,nq,H,bq]
    Dl = (dob * outb).sum(-1).transpose(0, 1, 3, 2)

    def kv_step(dq_acc, blk):
        kj, vj, ks, kp = blk  # [B,bkv,Hkv,D], seg/pos [B,bkv]

        def one_q(qi, qs, qp, m_r, l_r, doi, Di):
            s = _gqa_scores(qi, kj).astype(jnp.float32) * scale  # [B,H,bq,bkv]
            msk = make_segment_mask(qs, ks, qp, kp, causal=causal, window=window)
            s = jnp.where(msk[:, None], s, NEG_INF)
            p = jnp.exp(s - m_r[..., None]) * (s > NEG_INF / 2)
            p = p / l_r[..., None]
            # dv_j contribution: p^T do  -> [B,bkv,Hkv,D]
            pg = p.reshape(B, Hkv, g, bq, bkv)
            dog = doi.reshape(B, bq, Hkv, g, D)
            dv = jnp.einsum("bhgqk,bqhgd->bkhd", pg, dog)
            # dp = do . v^T  [B,H,bq,bkv]
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, vj.astype(jnp.float32))
            dp = dp.reshape(B, H, bq, bkv)
            ds = p * (dp - Di[..., None]) * scale
            dsg = ds.reshape(B, Hkv, g, bq, bkv)
            dq = jnp.einsum("bhgqk,bkhd->bqhgd", dsg, kj.astype(jnp.float32))
            dq = dq.reshape(B, bq, H, D)
            qg = qi.reshape(B, bq, Hkv, g, D)
            dk = jnp.einsum("bhgqk,bqhgd->bkhd", dsg, qg.astype(jnp.float32))
            return dq, dk, dv

        dq_b, dk_b, dv_b = jax.vmap(one_q, in_axes=(1, 1, 1, 1, 1, 1, 1),
                                    out_axes=1)(qb, qsb, qpb, m_f, l_f, dob, Dl)
        # dq_b [B,nq,bq,H,D] accumulates; dk/dv summed over q blocks.
        return dq_acc + dq_b, (dk_b.sum(axis=1), dv_b.sum(axis=1))

    dq0 = jnp.zeros((B, nq, bq, H, D), jnp.float32)
    blocks = (
        jnp.moveaxis(kb, 1, 0),
        jnp.moveaxis(vb, 1, 0),
        jnp.moveaxis(ksb, 1, 0),
        jnp.moveaxis(kpb, 1, 0),
    )
    dq_f, (dk_blocks, dv_blocks) = jax.lax.scan(kv_step, dq0, blocks)
    dq = dq_f.reshape(B, nq * bq, H, D)[:, :Tq].astype(q.dtype)
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(B, nk * bkv, Hkv, D)[:, :Tkv]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(B, nk * bkv, Hkv, D)[:, :Tkv]
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _make_flash(causal, window, scale, block_q, block_kv, unroll):
    @jax.custom_vjp
    def flash(q, k, v, q_seg, kv_seg, q_pos, kv_pos):
        out, _, _ = _chunked(q, k, v, q_seg, kv_seg, q_pos, kv_pos,
                             causal=causal, window=window, scale=scale,
                             block_q=block_q, block_kv=block_kv, unroll=unroll)
        return out

    def fwd(q, k, v, q_seg, kv_seg, q_pos, kv_pos):
        out, m, l = _chunked(q, k, v, q_seg, kv_seg, q_pos, kv_pos,
                             causal=causal, window=window, scale=scale,
                             block_q=block_q, block_kv=block_kv, unroll=unroll)
        return out, (q, k, v, q_seg, kv_seg, q_pos, kv_pos, out, m, l)

    def bwd(res, do):
        dq, dk, dv = _flash_bwd_blocks(
            res, do, causal=causal, window=window, scale=scale,
            block_q=block_q, block_kv=block_kv,
        )
        zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
        _, _, _, qs, ks, qp, kp, *_ = res
        return dq, dk, dv, zero(qs), zero(ks), zero(qp), zero(kp)

    flash.defvjp(fwd, bwd)
    return flash


# ----------------------------------------------------------------------
# Window-chunked segment attention (beyond-paper S-Perf optimization).
#
# Post-balancing packs examples into long per-shard streams (e.g. 64k
# tokens of 4k-token examples).  Plain flash over the stream computes
# T_stream^2 score blocks even though segment masking zeroes all
# cross-example pairs -- 16x wasted FLOPs at train_4k.  But balancing
# gives a hard bound: every segment is <= the example max length W.  A
# segment therefore spans at most two consecutive W-sized stream chunks,
# so chunk i's queries only ever need keys from chunks {i-1, i}:
# attention over [nw, W] x [nw, 2W] windows is EXACT and costs
# T*2W instead of T^2.
# ----------------------------------------------------------------------
def _windowed(q, k, v, q_seg, kv_seg, q_pos, kv_pos, *, causal, window,
              backend, block_q, block_kv, chunk_w):
    B, T, H, D = q.shape
    if k.shape[1] != T:
        raise ValueError("windowed attention requires self-attention layout")
    W = chunk_w
    nw = -(-T // W)
    pad = nw * W - T

    def padt(x, val=0):
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
                       constant_values=val)

    def chunks(x):
        x = padt(x)
        return x.reshape((B, nw, W) + x.shape[2:])

    def with_prev(x, val=0):
        xc = chunks(x)
        prev = jnp.concatenate(
            [jnp.full_like(xc[:, :1], val), xc[:, :-1]], axis=1)
        return jnp.concatenate([prev, xc], axis=2)  # [B, nw, 2W, ...]

    qc = chunks(q).reshape((B * nw, W, H, D))
    qs = chunks(q_seg).reshape(B * nw, W)
    qp = chunks(q_pos).reshape(B * nw, W)
    kc = with_prev(k).reshape((B * nw, 2 * W, k.shape[2], D))
    vc = with_prev(v).reshape((B * nw, 2 * W, v.shape[2], D))
    ks = with_prev(kv_seg, val=0).reshape(B * nw, 2 * W)  # pad seg 0 = masked
    kp = with_prev(kv_pos, val=np.iinfo(np.int32).max if causal else 0)
    kp = kp.reshape(B * nw, 2 * W)

    out = attention(
        qc, kc, vc, q_seg=qs, kv_seg=ks, q_pos=qp, kv_pos=kp,
        causal=causal, window=window, backend=backend,
        block_q=block_q, block_kv=block_kv,
    )
    return out.reshape(B, nw * W, H, D)[:, :T]


# ----------------------------------------------------------------------
# Pallas flash backend: the TPU kernel (fwd + custom-VJP bwd + block
# skipping) behind the model-level [B,T,H,D] / GQA / ragged-length
# calling convention.
# ----------------------------------------------------------------------
def _pallas_flash(q, k, v, q_seg, kv_seg, q_pos, kv_pos, *, causal, window,
                  block_q, block_kv, interpret):
    from repro.kernels.ops import flash_attention_op, per_dp_shard
    from repro.utils import round_up

    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    bq = min(block_q, round_up(Tq, 8))
    bk = min(block_kv, round_up(Tkv, 8))
    pad_q = round_up(Tq, bq) - Tq
    pad_k = round_up(Tkv, bk) - Tkv

    def padt(x, n):
        return jnp.pad(x, [(0, 0), (0, n)] + [(0, 0)] * (x.ndim - 2))

    # Pad to tile multiples; padded slots carry seg 0 => masked out, and
    # padded query rows are sliced off (their cotangents never reach q).
    qt = jnp.moveaxis(padt(q, pad_q), 1, 2)  # [B,H,Tq',D]
    kt = jnp.moveaxis(padt(k, pad_k), 1, 2)
    vt = jnp.moveaxis(padt(v, pad_k), 1, 2)
    kernel = functools.partial(
        flash_attention_op, causal=causal,
        window=None if window is None else int(window),
        block_q=bq, block_kv=bk, interpret=interpret)
    out = per_dp_shard(
        kernel, qt, kt, vt,
        padt(q_seg.astype(jnp.int32), pad_q),
        padt(kv_seg.astype(jnp.int32), pad_k),
        padt(q_pos.astype(jnp.int32), pad_q),
        padt(kv_pos.astype(jnp.int32), pad_k))
    return jnp.moveaxis(out, 1, 2)[:, :Tq]


def windowed_variant(backend: str) -> str:
    """Name of the window-chunked wrapper around ``backend``."""
    if backend.startswith("windowed"):
        return backend
    if backend.startswith("chunked"):
        return backend.replace("chunked", "windowed")
    return "windowed_" + backend


def _windowed_inner(backend: str) -> str:
    suffix = backend[len("windowed"):].lstrip("_")
    if suffix in ("", "unrolled"):
        return "chunked" + ("_" + suffix if suffix else "")
    return suffix


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_seg: jnp.ndarray,
    kv_seg: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    causal: bool = True,
    window: int | None = None,
    backend: str | None = None,
    impl: str | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    chunk_w: int | None = None,
) -> jnp.ndarray:
    """Segment-aware GQA attention behind a selectable ``backend``
    (module docstring lists them; ``impl`` is the legacy alias).

    Shapes: q [B,Tq,H,D]; k,v [B,Tkv,Hkv,D]; seg/pos [B,T*] int32.
    Returns [B,Tq,H,D].
    """
    backend = backend or impl or "chunked"
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(f"n_heads {q.shape[2]} not multiple of kv heads {k.shape[2]}")
    scale = 1.0 / np.sqrt(q.shape[-1])
    if backend.startswith("windowed"):
        if chunk_w is None:
            raise ValueError("windowed attention needs chunk_w (max segment len)")
        return _windowed(q, k, v, q_seg, kv_seg, q_pos, kv_pos, causal=causal,
                         window=window, backend=_windowed_inner(backend),
                         block_q=block_q, block_kv=block_kv, chunk_w=chunk_w)
    if backend == "reference":
        mask = make_segment_mask(q_seg, kv_seg, q_pos, kv_pos, causal=causal, window=window)
        return _reference(q, k, v, mask, scale)
    if backend in ("flash", "flash_interpret"):
        return _pallas_flash(
            q, k, v, q_seg, kv_seg, q_pos, kv_pos, causal=causal,
            window=window, block_q=block_q, block_kv=block_kv,
            interpret=True if backend == "flash_interpret" else None,
        )
    if backend in ("chunked", "chunked_unrolled"):
        unroll = 10**9 if backend == "chunked_unrolled" else 1
        flash = _make_flash(causal, window, scale, block_q, block_kv,
                            min(unroll, -(-k.shape[1] // min(block_kv, k.shape[1]))))
        return flash(q, k, v, q_seg.astype(jnp.int32), kv_seg.astype(jnp.int32),
                     q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32))
    raise ValueError(f"unknown attention backend {backend!r}")
