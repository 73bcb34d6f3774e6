"""DP-mesh check for the Pallas kernel call sites and the training loop.

Run in a subprocess with 4 host devices (set below, before JAX loads).
Each kernel call site -- flash attention, the grouped-GEMM MoE FFN, the
Mamba-1/2 selective scan -- runs under ``jax.set_mesh`` on a 4-way DP
mesh and must (a) trace through ``shard_map`` (XLA cannot partition a
Mosaic kernel) and (b) give the outputs and gradients of the same call
without a mesh.  Then ``launch.train.train`` runs 3 steps under
``--mesh host`` and must compile nothing after the first step and agree
with the single-device loop step for step.  Prints one ``ok <name>``
line per check; exits non-zero on any mismatch.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.kernels.ops import per_dp_shard
from repro.launch.mesh import make_mesh
from repro.launch.train import parse_args, train
from repro.models.attention import attention
from repro.models.moe import moe_ffn
from repro.models.ssm import mamba1_block, mamba2_block

N_DEV = 4
MESH = make_mesh((N_DEV, 1), ("data", "model"))


def _segs(rng, B, T):
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        cut = int(rng.integers(T // 4, 3 * T // 4))
        seg[b, :cut] = 1
        seg[b, cut:T - int(rng.integers(0, T // 8))] = 2
    pos = np.zeros_like(seg)
    for b in range(B):
        for s in (1, 2):
            idx = np.nonzero(seg[b] == s)[0]
            pos[b, idx] = np.arange(idx.size)
    return jnp.asarray(seg), jnp.asarray(pos)


def check_site(name, fn, x, *rest):
    """``fn(x, *rest)`` -> output; compares value and d/dx, d/drest of
    sum(sin(out)) with and without the DP mesh (x and every [B, ...]
    ``rest`` array sharded over "data" under the mesh)."""
    def loss(x, *rest):
        return jnp.sum(jnp.sin(fn(x, *rest).astype(jnp.float32)))

    grad = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(1 + len(rest)))))
    want_l, want_g = grad(x, *rest)
    with jax.set_mesh(MESH):
        shard = NamedSharding(MESH, P("data"))
        args = [jax.device_put(a, shard) if a.shape[0] == x.shape[0]
                else jax.device_put(a, NamedSharding(MESH, P()))
                for a in (x, *rest)]
        jaxpr = str(jax.make_jaxpr(grad)(*args))
        got_l, got_g = grad(*args)
    assert "shard_map" in jaxpr, f"{name}: kernel not run per DP shard"
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5,
                               err_msg=name)
    for i, (g, w) in enumerate(zip(got_g, want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5, err_msg=f"{name} grad {i}")
    print(f"ok {name}", flush=True)


def kernel_sites():
    rng = np.random.default_rng(0)
    B, T = N_DEV, 64

    # Flash attention (GQA 4/2), packed segments and a padded tail.
    H, Hkv, D = 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    seg, pos = _segs(rng, B, T)
    check_site("flash_attention",
               lambda q, k, v: attention(q, k, v, q_seg=seg, kv_seg=seg,
                                         q_pos=pos, kv_pos=pos, causal=True,
                                         backend="flash", block_q=32,
                                         block_kv=32),
               q, k, v)

    # Grouped-GEMM MoE FFN: replicated expert weights, top-2 of 4.
    d, f, E = 16, 32, 4
    router_w = jnp.asarray(rng.normal(0, 0.5, size=(d, E)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, T, d)), jnp.float32)
    w_gate = jnp.asarray(rng.normal(0, 0.1, size=(E, d, f)), jnp.float32)
    w_up = jnp.asarray(rng.normal(0, 0.1, size=(E, d, f)), jnp.float32)
    w_down = jnp.asarray(rng.normal(0, 0.1, size=(E, f, d)), jnp.float32)
    valid = jnp.asarray(np.asarray(seg) > 0)
    check_site("moe_grouped",
               lambda x, wg, wu, wd: moe_ffn(x, router_w, wg, wu, wd, top_k=2,
                                             valid=valid, backend="grouped",
                                             block_m=32, block_n=16)[0],
               x, w_gate, w_up, w_down)

    # Mamba-1 and Mamba-2 blocks on the Pallas scan.
    di, N, K, dt_rank, Ph = 64, 8, 4, 2, 16
    H2 = di // Ph
    p1 = {
        "in_proj": rng.normal(0, 0.1, size=(d, 2 * di)),
        "conv_w": rng.normal(0, 0.3, size=(K, di)),
        "x_proj": rng.normal(0, 0.1, size=(di, dt_rank + 2 * N)),
        "dt_proj": rng.normal(0, 0.1, size=(dt_rank, di)),
        "dt_bias": np.zeros(di),
        "A_log": np.log(np.tile(np.arange(1, N + 1)[None], (di, 1))),
        "D": np.ones(di),
        "out_proj": rng.normal(0, 0.1, size=(di, d)),
    }
    p2 = {
        "in_proj": rng.normal(0, 0.1, size=(d, 2 * di + 2 * N + H2)),
        "conv_w": rng.normal(0, 0.3, size=(K, di)),
        "dt_bias": np.zeros(H2),
        "A_log": np.zeros(H2),
        "D": np.ones(H2),
        "out_proj": rng.normal(0, 0.1, size=(di, d)),
    }
    p1, p2 = ({k: jnp.asarray(a, jnp.float32) for k, a in p.items()}
              for p in (p1, p2))
    check_site("mamba1_pallas",
               lambda x, A_log, D: mamba1_block(
                   {**p1, "A_log": A_log, "D": D}, x, seg, ssm_state=N,
                   backend="pallas", block_d=32, chunk=32),
               x, p1["A_log"], p1["D"])
    check_site("mamba2_pallas",
               lambda x, A_log, D: mamba2_block(
                   {**p2, "A_log": A_log, "D": D}, x, seg, ssm_state=N,
                   headdim=Ph, backend="pallas", block_d=32, chunk=32),
               x, p2["A_log"], p2["D"])

    with jax.set_mesh(MESH):
        try:
            per_dp_shard(lambda a: a, jnp.zeros((6, 2)))
        except ValueError as e:
            assert "does not divide" in str(e), e
        else:
            raise AssertionError("a batch of 6 split over 4 shards")
    print("ok non_dividing_batch_raises", flush=True)


def train_loop():
    """3 DP steps under --mesh host against the single-device loop."""
    cfg = dataclasses.replace(get_config("mllm_10b").smoke(),
                              attention_impl="flash")

    def run(mesh):
        return train(cfg, parse_args(["--arch", "mllm_10b", "--d", "4",
                                      "--per", "2", "--steps", "3",
                                      "--mesh", mesh]))

    dp, one = run("host"), run("none")
    assert [r["compiles"] for r in dp][1:] == [0, 0], dp
    assert [r["compiles"] for r in one][1:] == [0, 0], one
    for a, b in zip(dp, one):
        assert math.isfinite(a["loss"]) and math.isfinite(a["grad_norm"]), a
        for key in ("loss", "grad_norm"):
            assert abs(a[key] - b[key]) <= 1e-3 * abs(b[key]), (key, a, b)
    print("ok train_mesh_host", flush=True)


if __name__ == "__main__":
    assert len(jax.devices()) == N_DEV, jax.devices()
    kernel_sites()
    train_loop()
