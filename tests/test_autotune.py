"""Autotuner contract: cache roundtrip, resolve precedence (env
override > cache > default), prediction-pruned measurement sweeps, and
the roofline predictors' block sensitivity."""
import json

import jax
import pytest

from repro.kernels import autotune
from repro.launch.roofline import get_hw


@pytest.fixture
def cache(tmp_path):
    return str(tmp_path / "tune.json")


def test_cache_key_is_order_insensitive():
    a = autotune.cache_key("flash", {"Tq": 128, "D": 64})
    b = autotune.cache_key("flash", {"D": 64, "Tq": 128})
    assert a == b == "flash|D=64|Tq=128"


def test_autotune_picks_fastest_and_caches(cache):
    times = {(32, 32): 5.0, (64, 64): 1.0, (128, 128): 3.0}
    calls = []

    def run_fn(blocks):
        calls.append(blocks)
        # Simulated kernel: no sleeping needed, measurement keys off the
        # perf counter so equal walltimes tie-break by candidate order --
        # instead inject distinct fake durations via a busy wait.
        import time
        t0 = time.perf_counter()
        while (time.perf_counter() - t0) * 1e3 < times[blocks] / 10:
            pass

    res = autotune.autotune("flash", {"Tq": 128}, list(times), run_fn,
                            repeat=1, cache_path=cache)
    assert res["blocks"] == (64, 64)
    assert res["cached"] is False
    assert all(b in calls for b in times)

    # Second call: served from cache, run_fn untouched.
    calls.clear()
    res2 = autotune.autotune("flash", {"Tq": 128}, list(times), run_fn,
                             repeat=1, cache_path=cache)
    assert res2["blocks"] == (64, 64)
    assert res2["cached"] is True
    assert calls == []


def test_autotune_prunes_predicted_losers(cache):
    ran = []
    preds = {(32, 32): 1.0, (64, 64): 1.1, (128, 128): 50.0}

    res = autotune.autotune(
        "scan", {"T": 64}, list(preds), ran.append,
        predict_fn=lambda b: preds[b], prune=4.0, repeat=1,
        cache_path=cache, use_cache=False)
    assert (128, 128) not in ran  # predicted 50x off: never measured
    assert (32, 32) in ran and (64, 64) in ran
    # Pruned candidate still appears in the record, unmeasured.
    by_blocks = {tuple(c["blocks"]): c for c in res["candidates"]}
    assert by_blocks[(128, 128)]["measured_ms"] is None


def test_autotune_no_measurable_candidates_raises(cache):
    with pytest.raises(ValueError):
        autotune.autotune("scan", {"T": 64}, [], lambda b: None,
                          cache_path=cache, use_cache=False)


def test_resolve_precedence(cache, monkeypatch):
    key = {"Tq": 128, "D": 64}
    default = (128, 128)
    # 1. Nothing cached: default.
    assert autotune.resolve("flash", key, default, cache_path=cache) == default
    # 2. Cached winner beats default...
    autotune.autotune("flash", key, [(64, 32)], lambda b: None, repeat=1,
                      cache_path=cache)
    assert autotune.resolve("flash", key, default, cache_path=cache) == (64, 32)
    # ...but only when enabled.
    assert autotune.resolve("flash", key, default, enabled=False,
                            cache_path=cache) == default
    # 3. Env override beats everything, including enabled=False.
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "scan=16x8,flash=256x128")
    assert autotune.resolve("flash", key, default, cache_path=cache) == (256, 128)
    assert autotune.resolve("flash", key, default, enabled=False,
                            cache_path=cache) == (256, 128)
    assert autotune.resolve("scan", key, default, cache_path=cache) == (16, 8)
    # Kernels not named in the override are unaffected.
    assert autotune.resolve("grouped", key, default, cache_path=cache) == default


def test_corrupt_cache_is_ignored(cache):
    with open(cache, "w") as f:
        f.write("{not json")
    assert autotune.resolve("flash", {"T": 1}, (8, 8), cache_path=cache) == (8, 8)
    # And autotune can still write a fresh cache over it.
    autotune.autotune("flash", {"T": 1}, [(4, 4)], lambda b: None, repeat=1,
                      cache_path=cache)
    with open(cache) as f:
        data = json.load(f)
    kind = jax.devices()[0].device_kind
    assert data[autotune.cache_key("flash", {"T": 1, "device": kind})][
        "blocks"] == [4, 4]


def test_cached_winner_is_keyed_by_device_kind(cache):
    """A winner recorded for another device kind is never applied here,
    and the default cache lives inside the checkout."""
    with open(cache, "w") as f:
        json.dump({autotune.cache_key("flash", {"T": 1, "device": "TPU v4"}):
                   {"blocks": [4, 4]}}, f)
    assert autotune.resolve("flash", {"T": 1}, (8, 8), cache_path=cache) == (8, 8)
    from repro.utils import CHECKOUT_CACHE

    assert autotune.default_cache_path().startswith(str(CHECKOUT_CACHE))


def test_candidate_enumerators_respect_divisibility():
    for bq, bk in autotune.flash_candidates(384, 256):
        assert 384 % bq == 0 and 256 % bk == 0
    for bd, ct in autotune.scan_candidates(192, 96):
        assert 96 % bd == 0 and 192 % ct == 0
    for bm, bn in autotune.grouped_candidates(256, 96):
        assert 256 % bm == 0 and 96 % bn == 0
    assert (128, 64) in autotune.flash_candidates(128, 64)


def test_predictors_penalize_tiny_blocks():
    """Same FLOPs, more grid steps: the step-overhead term must make an
    explosion of tiny tiles strictly slower in every predictor."""
    hw = get_hw("TPU v5 lite")
    assert autotune.predict_scan((16, 16), T=4096, di=4096, N=16, hw=hw) > \
        autotune.predict_scan((128, 256), T=4096, di=4096, N=16, hw=hw)
    assert autotune.predict_flash(
        (32, 32), heads=8, Tq=4096, Tkv=4096, D=128, hw=hw) > \
        autotune.predict_flash(
            (256, 256), heads=8, Tq=4096, Tkv=4096, D=128, hw=hw)
    assert autotune.predict_grouped(
        (32, 32), M=4096, K=4096, N=4096, E=8, hw=hw) > \
        autotune.predict_grouped(
            (256, 256), M=4096, K=4096, N=4096, E=8, hw=hw)


def test_predict_grouped_rewards_tile_skip():
    """Fewer live tiles (balanced routing over many experts) must
    predict faster than a dense sweep at the same shape."""
    hw = get_hw("TPU v5 lite")
    dense = autotune.predict_grouped((128, 128), M=4096, K=512, N=512, E=8,
                                     live_tiles=4096 // 128 * 8, hw=hw)
    skip = autotune.predict_grouped((128, 128), M=4096, K=512, N=512, E=8,
                                    hw=hw)  # default: n_m + E - 1 live
    assert skip < dense
