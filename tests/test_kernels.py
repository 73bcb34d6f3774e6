"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle
(interpret=True executes the kernel body on CPU -- the same path the
model-level ``flash_interpret`` backend selects)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import count_live_tiles, live_tile_mask
from repro.kernels.grouped_gemm import count_live_group_tiles
from repro.kernels.ops import (
    flash_attention_op,
    grouped_matmul_op,
    selective_scan_op,
)
from repro.kernels.ref import flash_attention_ref, selective_scan_ref
from repro.models.ssm import mamba1_block, mamba1_scan, mamba2_block


def _segs(rng, B, T, n_seg):
    """Random packed segment layout with a padded tail."""
    seg = np.zeros((B, T), np.int32)
    pos = np.zeros((B, T), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, T), size=n_seg - 1, replace=False))
        bounds = np.concatenate([[0], cuts, [T - rng.integers(0, T // 4)]])
        for s in range(len(bounds) - 1):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if hi <= lo:
                continue
            seg[b, lo:hi] = s + 1
            pos[b, lo:hi] = np.arange(hi - lo)
    return jnp.asarray(seg), jnp.asarray(pos)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Tq,Tkv,D,causal,window",
    [
        (1, 2, 128, 128, 64, True, None),
        (2, 2, 256, 256, 64, True, None),
        (1, 4, 128, 128, 128, True, 64),     # sliding window
        (1, 2, 128, 256, 64, False, None),   # cross-attn shape
        (2, 1, 384, 384, 32, True, None),    # 3 kv blocks
    ],
)
def test_flash_attention_matches_ref(B, H, Tq, Tkv, D, causal, window, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, Tq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, H, Tkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, H, Tkv, D)), dtype)
    q_seg, q_pos = _segs(rng, B, Tq, 3)
    if Tq == Tkv:
        kv_seg, kv_pos = q_seg, q_pos
    else:
        kv_seg, kv_pos = _segs(rng, B, Tkv, 3)
    got = flash_attention_op(q, k, v, q_seg, kv_seg, q_pos, kv_pos,
                             causal=causal, window=window, interpret=True)
    want = flash_attention_ref(q, k, v, q_seg, kv_seg, q_pos, kv_pos,
                               causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_attention_padding_rows_zero():
    rng = np.random.default_rng(1)
    B, H, T, D = 1, 2, 128, 64
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    seg = jnp.zeros((B, T), jnp.int32)  # all padding
    pos = jnp.zeros((B, T), jnp.int32)
    out = flash_attention_op(q, q, q, seg, seg, pos, pos, interpret=True)
    assert np.allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "T,di,N,block_d,chunk",
    [
        (128, 128, 16, 128, 64),
        (256, 256, 16, 128, 64),
        (64, 128, 8, 64, 32),
        (192, 384, 4, 128, 64),
    ],
)
def test_selective_scan_matches_ref(T, di, N, block_d, chunk, dtype):
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(T, di)), dtype)
    delta = jnp.asarray(np.abs(rng.normal(0.05, 0.02, size=(T, di))), dtype)
    A = jnp.asarray(-np.abs(rng.normal(1.0, 0.3, size=(di, N))), jnp.float32)
    B = jnp.asarray(rng.normal(size=(T, N)), dtype)
    C = jnp.asarray(rng.normal(size=(T, N)), dtype)
    D = jnp.asarray(rng.normal(size=(di,)), jnp.float32)
    seg = np.ones(T, np.int32)
    seg[T // 2 :] = 2  # two packed segments: state must reset
    seg[-8:] = 0  # padded tail
    seg = jnp.asarray(seg)
    got = selective_scan_op(u, delta, A, B, C, D, seg,
                            block_d=block_d, chunk=chunk, interpret=True)
    want = selective_scan_ref(u, delta, A, B, C, D, seg)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_selective_scan_segment_reset_isolates_examples():
    """Output of segment 2 must be identical whether or not segment 1
    precedes it in the stream (consequence-invariance at kernel level)."""
    rng = np.random.default_rng(3)
    T, di, N = 128, 128, 8
    u = jnp.asarray(rng.normal(size=(T, di)), jnp.float32)
    delta = jnp.asarray(np.abs(rng.normal(0.05, 0.02, size=(T, di))), jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(1.0, 0.3, size=(di, N))), jnp.float32)
    B = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    D = jnp.zeros((di,), jnp.float32)
    half = T // 2
    seg = jnp.asarray(np.r_[np.ones(half), 2 * np.ones(half)].astype(np.int32))
    y_packed = selective_scan_op(u, delta, A, B, C, D, seg, block_d=64,
                                 chunk=32, interpret=True)
    y_alone = selective_scan_op(u[half:], delta[half:], A, B[half:], C[half:],
                                D, seg[half:], block_d=64, chunk=32,
                                interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_packed[half:]), np.asarray(y_alone), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize(
    "causal,window",
    [(True, None), (False, None), (True, 64)],
)
def test_flash_attention_vjp_matches_ref_autodiff(causal, window):
    """jax.grad through the Pallas custom VJP (dq/dk/dv kernels) must
    match autodiff through the dense oracle to fp32 tolerance."""
    rng = np.random.default_rng(7)
    B, H, T, D = 2, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    seg, pos = _segs(rng, B, T, 4)

    def make_loss(fn):
        def loss(q, k, v):
            o = fn(q, k, v, seg, seg, pos, pos, causal=causal, window=window)
            return jnp.sum(jnp.sin(o.astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2))

    flash_fn = lambda *a, **kw: flash_attention_op(*a, interpret=True, **kw)
    got = make_loss(flash_fn)(q, k, v)
    want = make_loss(flash_attention_ref)(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=2e-5, rtol=2e-5,
            err_msg=f"d{name} mismatch (causal={causal} window={window})")


def test_flash_attention_block_skip_parity():
    """Block-skipping is a pure FLOP optimization: outputs and gradients
    must be bit-identical with it on or off."""
    rng = np.random.default_rng(8)
    B, H, T, D = 1, 2, 384, 32
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    seg, pos = _segs(rng, B, T, 5)

    def run(block_skip):
        def loss(x):
            o = flash_attention_op(x, x, x, seg, seg, pos, pos,
                                   interpret=True, block_skip=block_skip)
            return jnp.sum(o * o)
        out = flash_attention_op(q, q, q, seg, seg, pos, pos,
                                 interpret=True, block_skip=block_skip)
        return out, jax.grad(loss)(q)

    out_on, g_on = run(True)
    out_off, g_off = run(False)
    np.testing.assert_array_equal(np.asarray(out_on), np.asarray(out_off))
    np.testing.assert_array_equal(np.asarray(g_on), np.asarray(g_off))


def test_flash_block_skip_visits_fewer_tiles():
    """A multi-segment packed stream must skip KV tiles: segment-range
    disjointness + the causal frontier prune most of the grid."""
    T, blk = 1024, 128
    seg = np.repeat(np.arange(1, 9), T // 8).astype(np.int32)[None]
    pos = np.tile(np.arange(T // 8), 8).astype(np.int32)[None]
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    visited, total = count_live_tiles(seg, seg, pos, pos, block_q=blk,
                                      block_kv=blk, causal=True, window=None)
    assert visited < total, (visited, total)
    # Segments align with tiles here, so only the diagonal survives.
    assert visited == T // blk
    live = live_tile_mask(seg, seg, pos, pos, block_q=blk, block_kv=blk,
                          causal=True, window=None)
    np.testing.assert_array_equal(np.asarray(live[0]), np.eye(T // blk, dtype=bool))


def test_flash_fully_padded_tail_tiles_skipped_and_zero():
    rng = np.random.default_rng(9)
    B, H, T, D = 1, 1, 256, 32
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    seg = np.zeros((B, T), np.int32)
    pos = np.zeros((B, T), np.int32)
    seg[0, :100] = 1
    pos[0, :100] = np.arange(100)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    out = flash_attention_op(q, q, q, seg, seg, pos, pos, interpret=True)
    assert np.allclose(np.asarray(out[0, 0, 100:]), 0.0)
    visited, total = count_live_tiles(seg, seg, pos, pos, block_q=128,
                                      block_kv=128, causal=True, window=None)
    assert (visited, total) == (1, 4)  # only the (q0, k0) tile is live


# ----------------------------------------------------------------------
# Grouped GEMM (MoE expert dispatch).
# ----------------------------------------------------------------------
def _group_layout(rng, M, E, *, empty=(), pad=0):
    """Random per-expert row counts summing to M - pad, with the experts
    in ``empty`` forced to zero rows.  Returns (sizes [E], offsets [E+1])."""
    live = [e for e in range(E) if e not in empty]
    sizes = np.zeros(E, np.int64)
    remaining = M - pad
    for e in live[:-1]:
        sizes[e] = rng.integers(0, remaining + 1)
        remaining -= sizes[e]
    sizes[live[-1]] = remaining
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return sizes, jnp.asarray(offs, jnp.int32)


def _grouped_oracle(x, w, offsets):
    """Dense per-row gather oracle: row s uses w[expert-of-s]; padding
    rows (s >= offsets[E]) produce zeros."""
    M = x.shape[0]
    E = w.shape[0]
    rows = jnp.arange(M)
    eid = jnp.searchsorted(offsets[1:], rows, side="right")  # [M] in [0, E]
    live = (eid < E) & (rows < offsets[E])
    w_row = w[jnp.minimum(eid, E - 1)]  # [M, K, N]
    out = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                     w_row.astype(jnp.float32))
    return jnp.where(live[:, None], out, 0.0).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N,E,bm,bn,empty,pad",
    [
        (256, 64, 128, 4, 128, 128, (), 0),
        (256, 64, 128, 4, 64, 64, (1,), 37),    # empty expert + padding tail
        (384, 32, 96, 8, 128, 32, (0, 5), 10),  # first expert empty
        (128, 48, 64, 2, 128, 64, (), 0),       # single m-tile
    ],
)
def test_grouped_matmul_matches_oracle(M, K, N, E, bm, bn, empty, pad, dtype):
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(E, K, N)), dtype)
    _, offs = _group_layout(rng, M, E, empty=empty, pad=pad)
    got = grouped_matmul_op(x, w, offs, block_m=bm, block_n=bn, interpret=True)
    want = _grouped_oracle(x, w, offs)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


def test_grouped_matmul_vjp_matches_oracle_autodiff():
    """dx (transposed-gmm kernel) and dw (tgmm kernel) must match
    autodiff through the dense gather oracle, including zero gradients
    for empty experts and padding rows."""
    rng = np.random.default_rng(11)
    M, K, N, E = 256, 64, 96, 4
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    sizes, offs = _group_layout(rng, M, E, empty=(2,), pad=21)

    def make_loss(fn):
        def loss(x, w):
            o = fn(x, w, offs)
            return jnp.sum(jnp.sin(o.astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1))

    kernel_fn = lambda x, w, o: grouped_matmul_op(
        x, w, o, block_m=64, block_n=32, interpret=True)
    (dx, dw) = make_loss(kernel_fn)(x, w)
    (dx_ref, dw_ref) = make_loss(_grouped_oracle)(x, w)
    # dx sums N=96 f32 products whose partial sums reach |dx| ~ 17; the
    # kernel and the oracle add them in different orders, so an entry
    # that cancels to near zero carries rounding error of the size of
    # the largest entries (3.5e-5 seen), not of its own.  Scale the
    # absolute tolerance by the array's magnitude (~6 f32 ulps of max).
    dx_ref = np.asarray(dx_ref)
    np.testing.assert_allclose(np.asarray(dx), dx_ref,
                               atol=2e-5 * max(1.0, np.abs(dx_ref).max()),
                               rtol=2e-5, err_msg="dx")
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               atol=2e-4, rtol=2e-4, err_msg="dw")
    # Empty expert and padding rows get exactly zero gradient.
    assert np.all(np.asarray(dw)[2] == 0.0)
    assert np.all(np.asarray(dx)[int(offs[E]):] == 0.0)


def test_count_live_group_tiles_accounting():
    # Sizes [100, 0, 28, 128] with bm=64: expert 0 spans tiles {0,1},
    # expert 1 is empty, expert 2 spans tile {1}, expert 3 tiles {2,3}.
    assert count_live_group_tiles([100, 0, 28, 128], 64) == 5
    # Balanced tile-aligned groups: exactly one tile each.
    assert count_live_group_tiles([64, 64, 64, 64], 64) == 4
    # Dense sweep would be n_m * E = 4 * 4 = 16 in both cases.


# ----------------------------------------------------------------------
# Selective-scan custom VJP (satellite: gradient + segment-reset
# coverage for the training-grade kernel).
# ----------------------------------------------------------------------
def _scan_inputs(rng, T, di, N, *, n_pad=8):
    u = jnp.asarray(rng.normal(size=(T, di)), jnp.float32)
    delta = jnp.asarray(np.abs(rng.normal(0.05, 0.02, size=(T, di))), jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(1.0, 0.3, size=(di, N))), jnp.float32)
    B = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(di,)), jnp.float32)
    seg = np.ones(T, np.int32)
    seg[T // 3:] = 2  # packed multi-segment stream (state resets inside
    seg[2 * T // 3:] = 3  # chunks, not only at chunk boundaries)
    if n_pad:
        seg[-n_pad:] = 0  # padded tail rows
    return u, delta, A, B, C, D, jnp.asarray(seg)


@pytest.mark.parametrize(
    "T,di,N,block_d,chunk",
    [
        (128, 128, 8, 64, 32),
        (128, 64, 8, 64, 128),   # single chunk covering all of T
        (96, 48, 4, 16, 8),      # edge divisors: tiny blocks, T%chunk==0
        (64, 32, 8, 32, 64),     # single chunk == T, single d-block pair
    ],
)
def test_selective_scan_vjp_matches_scan_autodiff(T, di, N, block_d, chunk):
    """jax.grad through the kernel's chunk-checkpointed custom VJP must
    match autodiff through the lax.scan reference for every input, on a
    packed multi-segment stream with a seg==0 padded tail."""
    rng = np.random.default_rng(12)
    u, delta, A, B, C, D, seg = _scan_inputs(rng, T, di, N)

    def kernel_loss(u, delta, A, B, C, D):
        y = selective_scan_op(u, delta, A, B, C, D, seg,
                              block_d=block_d, chunk=chunk, interpret=True)
        return jnp.sum(jnp.sin(y))

    def ref_loss(u, delta, A, B, C, D):
        y, _ = mamba1_scan(u, delta, A, B, C, D, seg, backend="scan")
        return jnp.sum(jnp.sin(y))

    got = jax.grad(kernel_loss, argnums=tuple(range(6)))(u, delta, A, B, C, D)
    want = jax.grad(ref_loss, argnums=tuple(range(6)))(u, delta, A, B, C, D)
    for name, g, w in zip(["du", "ddelta", "dA", "dB", "dC", "dD"], got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5,
            err_msg=f"{name} mismatch (block_d={block_d} chunk={chunk})")


def test_selective_scan_padding_rows_isolated_grad():
    """seg==0 rows reset the state every step, so a padding-row input
    can only reach its own row's output: with the loss masked to valid
    rows, du/ddelta/dB/dC on the padded tail are exactly zero."""
    rng = np.random.default_rng(13)
    T, di, N = 64, 32, 4
    u, delta, A, B, C, D, seg = _scan_inputs(rng, T, di, N, n_pad=16)
    valid = (np.asarray(seg) > 0)[:, None]

    def loss(u, delta, B, C):
        y = selective_scan_op(u, delta, A, B, C, D, seg,
                              block_d=16, chunk=16, interpret=True)
        return jnp.sum(jnp.where(valid, y * y, 0.0))

    du, ddt, dB, dC = jax.grad(loss, argnums=(0, 1, 2, 3))(u, delta, B, C)
    for name, g in [("du", du), ("ddelta", ddt), ("dB", dB), ("dC", dC)]:
        assert np.all(np.asarray(g)[-16:] == 0.0), name
        assert np.any(np.asarray(g)[:-16] != 0.0), name


def test_selective_scan_final_state_matches_scan_backend():
    rng = np.random.default_rng(14)
    T, di, N = 128, 64, 8
    u, delta, A, B, C, D, seg = _scan_inputs(rng, T, di, N, n_pad=0)
    y_k, hf_k = selective_scan_op(u, delta, A, B, C, D, seg, block_d=32,
                                  chunk=32, interpret=True, return_state=True)
    # chunk must divide T for the scan oracle: its chunk padding runs
    # keep=False steps that zero the carried state.
    y_s, hf_s = mamba1_scan(u, delta, A, B, C, D, seg, backend="scan",
                            chunk=64)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_s),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hf_k), np.asarray(hf_s),
                               atol=1e-4, rtol=1e-4)


def _batch_segs(rng, B, T):
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        cut = int(rng.integers(T // 4, 3 * T // 4))
        tail = int(rng.integers(0, T // 8))
        seg[b, :cut] = 1
        seg[b, cut:T - tail] = 2
    return jnp.asarray(seg)


def test_mamba1_block_backend_parity():
    """Full mamba1 block (proj + conv + scan + gate), pallas vs scan
    backend: forward and input gradient must agree."""
    rng = np.random.default_rng(15)
    Bt, T, d, di, N, K, dt_rank = 2, 64, 32, 64, 8, 4, 2
    p = {
        "in_proj": jnp.asarray(rng.normal(0, 0.1, size=(d, 2 * di)), jnp.float32),
        "conv_w": jnp.asarray(rng.normal(0, 0.3, size=(K, di)), jnp.float32),
        "x_proj": jnp.asarray(rng.normal(0, 0.1, size=(di, dt_rank + 2 * N)), jnp.float32),
        "dt_proj": jnp.asarray(rng.normal(0, 0.1, size=(dt_rank, di)), jnp.float32),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": jnp.asarray(rng.normal(0, 0.1, size=(di, d)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(Bt, T, d)), jnp.float32)
    seg = _batch_segs(rng, Bt, T)

    def run(backend):
        def loss(x):
            y = mamba1_block(p, x, seg, ssm_state=N, backend=backend,
                             block_d=32, chunk=32)
            return jnp.sum(jnp.sin(y)), y
        (l, y), g = jax.value_and_grad(loss, has_aux=True)(x)
        return y, g

    y_p, g_p = run("pallas")
    y_s, g_s = run("scan")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_s),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_s),
                               atol=2e-5, rtol=2e-5)


def test_mamba2_block_backend_parity():
    """Mamba-2 maps onto the mamba-1 kernel by broadcasting per-head
    scalars over the head dim; block outputs and grads must agree."""
    rng = np.random.default_rng(16)
    Bt, T, d, di, N, K, P = 2, 64, 32, 64, 8, 4, 16
    H = di // P
    p = {
        "in_proj": jnp.asarray(
            rng.normal(0, 0.1, size=(d, 2 * di + 2 * N + H)), jnp.float32),
        "conv_w": jnp.asarray(rng.normal(0, 0.3, size=(K, di)), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "out_proj": jnp.asarray(rng.normal(0, 0.1, size=(di, d)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(Bt, T, d)), jnp.float32)
    seg = _batch_segs(rng, Bt, T)

    def run(backend):
        def loss(x):
            y = mamba2_block(p, x, seg, ssm_state=N, headdim=P,
                             backend=backend, block_d=32, chunk=32)
            return jnp.sum(jnp.sin(y)), y
        (l, y), g = jax.value_and_grad(loss, has_aux=True)(x)
        return y, g

    y_p, g_p = run("pallas")
    y_s, g_s = run("scan")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_s),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_s),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_segment_isolation():
    """Cross-segment attention must be exactly zero: perturbing segment 1
    cannot change segment 2's outputs."""
    rng = np.random.default_rng(4)
    B, H, T, D = 1, 2, 256, 64
    half = T // 2
    seg = np.r_[np.ones(half), 2 * np.ones(half)].astype(np.int32)[None]
    pos = np.r_[np.arange(half), np.arange(half)].astype(np.int32)[None]
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    q = rng.normal(size=(B, H, T, D)).astype(np.float32)
    q2 = q.copy()
    q2[:, :, :half] += 1.0  # perturb segment 1 only
    outs = []
    for qq in (q, q2):
        qq = jnp.asarray(qq)
        outs.append(np.asarray(
            flash_attention_op(qq, qq, qq, seg, seg, pos, pos, interpret=True)
        ))
    np.testing.assert_allclose(outs[0][:, :, half:], outs[1][:, :, half:],
                               atol=1e-5)
    assert not np.allclose(outs[0][:, :, :half], outs[1][:, :, :half])


# ----------------------------------------------------------------------
# The Mosaic layouts at the shapes the chip runs: GQA with kv-side seg
# rows / q-side columns and a scalar-prefetched live mask (flash), f32
# row-by-row recurrences over a lane-dense [N, bd] state (scan).
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bq,bk", [(256, 256), (128, 256), (256, 128)])
def test_flash_gqa_mosaic_layout_matches_ref(bq, bk):
    """Head dim 128, GQA group 4, unequal q/kv blocks and several packed
    segments: the forward and all three gradients match the oracle."""
    rng = np.random.default_rng(20)
    B, H, Hkv, T, D = 2, 8, 2, 512, 128
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    seg, pos = _segs(rng, B, T, 5)
    rep = lambda x: jnp.repeat(x, H // Hkv, axis=1)  # noqa: E731

    def flash(q, k, v):
        return flash_attention_op(q, k, v, seg, seg, pos, pos, block_q=bq,
                                  block_kv=bk, interpret=True)

    def oracle(q, k, v):
        return flash_attention_ref(q, rep(k), rep(v), seg, seg, pos, pos)

    do = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    got, vjp = jax.vjp(flash, q, k, v)
    want, vjp_ref = jax.vjp(oracle, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for name, g, w in zip("qkv", vjp(do), vjp_ref(do)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=f"d{name}")


def test_selective_scan_bf16_inputs_run_in_f32():
    """bf16 operands are widened to f32 before the kernel (single rows of
    a packed bf16 block are not addressable on the chip): the result
    equals the kernel on the widened inputs, rounded once to bf16, and
    the state comes back as [di, N]."""
    rng = np.random.default_rng(21)
    T, di, N = 128, 256, 16
    u, delta, A, B, C, D, seg = _scan_inputs(rng, T, di, N)
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    y16, h16 = selective_scan_op(bf(u), bf(delta), A, bf(B), bf(C), D, seg,
                                 block_d=128, chunk=64, interpret=True,
                                 return_state=True)
    f32 = lambda x: bf(x).astype(jnp.float32)  # noqa: E731
    y32, h32 = selective_scan_op(f32(u), f32(delta), A, f32(B), f32(C), D,
                                 seg, block_d=128, chunk=64, interpret=True,
                                 return_state=True)
    assert y16.dtype == jnp.bfloat16 and h16.shape == (di, N)
    np.testing.assert_array_equal(np.asarray(y16), np.asarray(bf(y32)))
    np.testing.assert_array_equal(np.asarray(h16), np.asarray(h32))


def test_grouped_matmul_ref_matches_gather_oracle():
    """kernels/ref.grouped_matmul_ref (one matmul per expert, what the
    chip smoke compares against) equals the per-row gather oracle."""
    from repro.kernels.ref import grouped_matmul_ref

    rng = np.random.default_rng(22)
    M, K, N, E = 256, 64, 96, 4
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    _, offs = _group_layout(rng, M, E, empty=(1,), pad=19)
    np.testing.assert_allclose(np.asarray(grouped_matmul_ref(x, w, offs)),
                               np.asarray(_grouped_oracle(x, w, offs)),
                               atol=1e-4, rtol=1e-5)
