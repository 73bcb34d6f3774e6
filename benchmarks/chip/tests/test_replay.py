"""The untimed replay: its quick compiles stay out of the persistent
compilation cache, and its jitted tree functions give what the same
operations give one by one."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import run  # noqa: E402
import test_correctness as tc  # noqa: E402

CACHE_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_dir(tmp_path):
    """JAX's persistent compilation cache in ``tmp_path``, set as run.py
    sets it (every program kept), and put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        yield tmp_path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_replay_keeps_quick_compiles_out_of_the_cache(cache_dir):
    runner = run.load_module(HERE / "runners/train.py")

    def entries():
        return {p.name for p in cache_dir.rglob("*") if p.is_file()}

    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
    written = entries()
    assert written, "a fast compile is written while every program is kept"
    with runner.replay_cache():
        assert jax.config.jax_persistent_cache_min_compile_time_secs == \
            runner.REPLAY_MIN_COMPILE_S
        jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(9)).block_until_ready()
        assert entries() == written
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    jax.jit(lambda x: x * 7.0 - 4.0)(jnp.ones(11)).block_until_ready()
    assert entries() > written


def test_jitted_replay_matches_its_operations_one_by_one(train_mods, monkeypatch):
    """reference_readings with the replay's jitted tree functions (the
    float32 cast, the accumulator's zeros, sums and division, the rows
    placed between the gradient programs, the clip norm's sums of
    squares, the leaf norms and the parameter change) against the same
    functions run eagerly, one operation at a time, on the small cell's
    checked batches; the gradient programs are the same in both."""
    runner, ref, _ = train_mods
    cell = tc.small_train_cell()
    checked = [f for f, _ in tc._packed(runner, ref, cell)]
    model, opt = cell.config["model"], cell.config["optimizer"]
    jitted = runner.reference_readings(checked, model, opt, tc.SEED, ref)
    mllm = sys.modules[ref.adam_step.__module__]
    for mod, names in ((runner, ("_f32", "_change_norms")),
                       (mllm, ("_place_rows", "_take_rows", "_zeros_like", "_accumulate",
                               "_divide", "_square_sums")),
                       (sys.modules[mllm.leaf_norms.__module__], ("_norms",))):
        for name in names:
            monkeypatch.setattr(mod, name, getattr(mod, name).__wrapped__)
    eager = runner.reference_readings(checked, model, opt, tc.SEED, ref)
    assert jitted["losses"] == pytest.approx(eager["losses"], rel=1e-6)
    for key in ("grad_norms", "delta_norms", "raw_grad_norms"):
        assert jitted[key].keys() == eager[key].keys()
        for leaf, v in eager[key].items():
            assert jitted[key][leaf] == pytest.approx(v, rel=1e-6, abs=1e-12), (key, leaf)


@pytest.fixture(scope="module")
def train_mods():
    return tc.train_mods.__wrapped__()
