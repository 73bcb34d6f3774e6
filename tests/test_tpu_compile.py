"""Compile the Pallas kernels for a described TPU v5e, at the widths the
chip runs, without a chip: the TPU compiler refuses here what Mosaic
would refuse there (unaligned blocks, unsupported ops, too much VMEM).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_gemm import grouped_matmul
from repro.kernels.selective_scan import selective_scan


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip cannot be read back from
    the persistent cache without the chip: keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

# mllm_10b's LLM attention: 28 query heads over 4 KV heads, head dim 128,
# 4096-token streams, 512x512 tiles.
T, H, HKV, D, BLOCK = 4096, 28, 4, 128, 512
# MoE expert FFN: 8192 routed rows, d_model 4096, expert width 1536.
M, K, N, E = 8192, 4096, 1536, 8
# Mamba-1: d_inner 8192, state 16.
DI, STATE = 8192, 16


def _flash(q, k, v, seg, pos):
    return flash_attention(q, k, v, seg, seg, pos, pos, block_q=BLOCK,
                           block_kv=BLOCK, interpret=False)


def _gmm(x, w, offsets):
    return grouped_matmul(x, w, offsets, interpret=False)


def _scan(u, dt, A, B, C, Dp, seg):
    return selective_scan(u, dt, A, B, C, Dp, seg, interpret=False)


def _case(name):
    """(function, argument shapes) for one compile."""
    flash_args = [((1, H, T, D), bf16), ((1, HKV, T, D), bf16),
                  ((1, HKV, T, D), bf16), ((1, T), i32), ((1, T), i32)]
    gmm_args = [((M, K), bf16), ((E, K, N), bf16), ((E + 1,), i32)]
    scan_args = [((T, DI), f32), ((T, DI), f32), ((DI, STATE), f32),
                 ((T, STATE), f32), ((T, STATE), f32), ((DI,), f32),
                 ((T,), i32)]

    def grad_of(fn, n_diff):
        def g(*args):
            loss = lambda *d: fn(*d, *args[n_diff:]).astype(f32).sum()  # noqa: E731
            return jax.grad(loss, argnums=tuple(range(n_diff)))(*args[:n_diff])
        return g

    return {
        "flash_fwd": (_flash, flash_args),
        "flash_bwd": (grad_of(_flash, 3), flash_args),
        "grouped_gemm_fwd": (_gmm, gmm_args),
        "grouped_gemm_bwd": (grad_of(_gmm, 2), gmm_args),
        "scan_fwd": (_scan, scan_args),
        "scan_bwd": (grad_of(_scan, 6), scan_args),
    }[name]


@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd", "grouped_gemm_fwd",
                                  "grouped_gemm_bwd", "scan_fwd", "scan_bwd"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_cut_mllm_10b_train_step_fits_one_v5e(one_chip, no_persistent_cache,
                                              monkeypatch):
    """The whole train step chip_smoke.py runs -- mllm_10b at published
    widths, depth and vocabulary cut, 4 DP instances on one chip --
    compiles for one v5e and fits its 16 GB with the AdamW state."""
    import sys
    from pathlib import Path

    import repro.kernels.ops as ops

    # This process runs on the CPU, where the model's kernels would take
    # the interpreter: steer them to Mosaic, and drop traces made before.
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    jax.clear_caches()

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke
    from repro.core.orchestrator import MLLMGlobalOrchestrator
    from repro.data.pipeline import PrefetchingLoader
    from repro.launch.train import _sampler_for
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import init_train_state, make_train_step

    cfg = chip_smoke.cut_mllm_10b()
    d, per = chip_smoke.DP_INSTANCES, chip_smoke.EXAMPLES_PER_INSTANCE
    orch = MLLMGlobalOrchestrator(cfg, d, vocab=cfg.vocab_size)
    sampler = _sampler_for(cfg)
    probe = [sampler(np.random.default_rng(s), per) for s in range(d)]
    loader = PrefetchingLoader(orch, orch.default_capacities(probe, margin=3.0),
                               examples_per_instance=per, sampler=sampler)
    try:
        batch, _, _ = next(loader)
    finally:
        loader.close()
    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)

    step = make_train_step(cfg, AdamWConfig(lr=1e-3))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        *on_chip(state), on_chip(batch)).compile()
    assert "tpu_custom_call" in compiled.as_text()  # flash attention
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    jax.clear_caches()
    assert live < 15.75e9, f"{live / 1e9:.2f} GB"
