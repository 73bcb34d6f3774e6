"""Multi-device check for the Node-wise All-to-All Communicator.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(set by the pytest wrapper).  Exits non-zero on any mismatch.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.balancing import post_balance
from repro.core.communicator import apply_comm_plan, build_comm_plan, plan_to_device
from repro.core.cost_model import CostModel
from repro.core.nodewise import nodewise_rearrange
from repro.launch.mesh import make_mesh


def reference_exchange(pi, x_global, cap_in, cap_out, feat):
    """Pure numpy oracle: place each example's tokens at its destination."""
    from repro.core.communicator import _layout

    d = pi.d
    lengths = pi.lengths
    src_starts, _ = _layout(pi.src_inst, pi.src_slot, lengths, d)
    dst_starts, _ = _layout(pi.dst_inst, pi.dst_slot, lengths, d)
    out = np.zeros((d * cap_out,) + feat, x_global.dtype)
    for k in range(pi.n):
        l = int(lengths[k])
        s0 = int(pi.src_inst[k]) * cap_in + int(src_starts[k])
        t0 = int(pi.dst_inst[k]) * cap_out + int(dst_starts[k])
        out[t0 : t0 + l] = x_global[s0 : s0 + l]
    return out


def run_case(mesh, dp_axes, d, seed, mode, nodewise=False):
    rng = np.random.default_rng(seed)
    lens = [rng.integers(1, 40, size=rng.integers(1, 6)) for _ in range(d)]
    pi = post_balance(lens, d, CostModel())
    if nodewise:
        pi = nodewise_rearrange(pi, 2)
    cap_in = int(max(l.sum() for l in lens))
    cap_out = int(max(l.sum() for l in pi.dest_lengths()) or 1)
    feat = (4,)
    x = rng.normal(size=(d * cap_in,) + feat).astype(np.float32)
    # Zero out the pad region of each source shard so the oracle matches.
    from repro.core.communicator import _layout

    _, totals = _layout(pi.src_inst, pi.src_slot, pi.lengths, d)
    for i in range(d):
        x[i * cap_in + int(totals[i]) : (i + 1) * cap_in] = 0

    plan = build_comm_plan(pi, cap_in, cap_out)
    arrays = plan_to_device(plan)
    sharding = NamedSharding(mesh, P(dp_axes))
    xg = jax.device_put(jnp.asarray(x), sharding)
    arrays = {
        k: jax.device_put(v, NamedSharding(mesh, P(dp_axes)))
        for k, v in arrays.items()
    }

    fn = jax.jit(
        lambda xx, aa: apply_comm_plan(xx, aa, mesh, dp_axes, mode=mode),
    )
    got = np.asarray(fn(xg, arrays))
    want = reference_exchange(pi, x, cap_in, cap_out, feat)
    if not np.allclose(got, want, atol=1e-6):
        bad = np.argwhere(~np.isclose(got, want, atol=1e-6))
        print(f"FAIL mode={mode} d={d} seed={seed} nodewise={nodewise} "
              f"mismatches={len(bad)} first={bad[:5]}")
        return False
    print(f"ok mode={mode} d={d} seed={seed} nodewise={nodewise}")
    return True


def check_ragged_lowers(mesh, dp_axes, d, seed):
    """ragged_all_to_all does not execute on XLA:CPU; assert it traces
    and lowers (the TPU-target path)."""
    rng = np.random.default_rng(seed)
    lens = [rng.integers(1, 40, size=3) for _ in range(d)]
    pi = post_balance(lens, d, CostModel())
    cap_in = int(max(l.sum() for l in lens))
    cap_out = int(max(l.sum() for l in pi.dest_lengths()))
    plan = build_comm_plan(pi, cap_in, cap_out)
    arrays = plan_to_device(plan)
    x = jnp.zeros((d * cap_in, 4), jnp.float32)
    lowered = jax.jit(
        lambda xx, aa: apply_comm_plan(xx, aa, mesh, dp_axes, mode="ragged")
    ).lower(x, arrays)
    txt = lowered.as_text()
    assert "ragged" in txt or "ragged-all-to-all" in txt, "no ragged op in HLO"
    print("ok ragged lowering contains ragged-all-to-all")
    return True


def main():
    n_dev = len(jax.devices())
    assert n_dev == 8, f"expected 8 host devices, got {n_dev}"
    ok = True
    # Flat DP mesh.
    mesh = make_mesh((8,), ("data",))
    for mode in ("a2a", "allgather", "gather"):
        for seed in (0, 1, 2):
            ok &= run_case(mesh, ("data",), 8, seed, mode)
    ok &= run_case(mesh, ("data",), 8, 3, "a2a", nodewise=True)
    ok &= check_ragged_lowers(mesh, ("data",), 8, 5)
    # Multi-pod style mesh: DP spans ("pod", "data").
    mesh2 = make_mesh((2, 4), ("pod", "data"))
    for mode in ("a2a", "gather"):
        ok &= run_case(mesh2, ("pod", "data"), 8, 4, mode)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
