"""Observability plane tests: quantile sketch rank error, registry
semantics, OpenMetrics exposition, canonical ledger formulas, flight
recorder crash safety, alert routing, kernel hooks.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.obs import (AlertBridge, FlightRecorder, GapWaterfall,
                       MetricsRegistry, QuantileSketch, StepLedger,
                       get_registry, goodput_fraction,
                       phase_imbalance, read_flight_record,
                       render_openmetrics, set_registry, simulated_mfu,
                       straggler_overhead, write_openmetrics)

# ----------------------------------------------------------------------
# Quantile sketch: GK rank-error guarantee on adversarial streams.
# ----------------------------------------------------------------------
QS = (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)


def _assert_rank_error(data, eps=0.005, qs=QS):
    """The sketch answer's true rank must be within eps*n (+1 slack for
    the discrete ceil) of the target rank -- checked against the exact
    sorted stream, which is what np.quantile also reads off."""
    sk = QuantileSketch(eps=eps)
    sk.extend(data)
    xs = np.sort(np.asarray(data, dtype=np.float64))
    n = len(xs)
    for q in qs:
        v = sk.quantile(q)
        target = max(1, int(np.ceil(q * n)))
        # 1-based rank interval of v in the stream.
        rank_lo = int(np.searchsorted(xs, v, side="left")) + 1
        rank_hi = int(np.searchsorted(xs, v, side="right"))
        margin = eps * n + 1
        assert rank_lo - margin <= target <= rank_hi + margin, (
            f"q={q}: answer {v} has rank [{rank_lo}, {rank_hi}], "
            f"target {target}, margin {margin:.1f} (n={n})")


@pytest.mark.parametrize("stream", [
    "ascending", "descending", "constant", "normal", "heavy_tail",
    "few_distinct", "alternating",
])
def test_sketch_rank_error_adversarial(stream):
    n = 20_000
    rng = np.random.default_rng(0)
    data = {
        "ascending": np.arange(n, dtype=float),
        "descending": np.arange(n, dtype=float)[::-1],
        "constant": np.full(n, 7.0),
        "normal": rng.normal(size=n),
        "heavy_tail": rng.lognormal(mean=0.0, sigma=3.0, size=n),
        "few_distinct": rng.choice([1.0, 2.0, 5.0], size=n),
        "alternating": np.where(np.arange(n) % 2 == 0, 1e-6, 1e6),
    }[stream]
    _assert_rank_error(data)


def test_sketch_memory_sublinear():
    sk = QuantileSketch(eps=0.01)
    sk.extend(np.random.default_rng(1).normal(size=50_000))
    sk.quantile(0.5)  # force drain
    # GK keeps O((1/eps) log(eps n)) tuples -- far below n.
    assert len(sk._tuples) < 2_000


def test_sketch_edge_cases():
    sk = QuantileSketch()
    assert np.isnan(sk.quantile(0.5))
    sk.add(3.0)
    assert sk.quantile(0.0) == 3.0 and sk.quantile(1.0) == 3.0
    with pytest.raises(ValueError):
        sk.quantile(1.5)
    with pytest.raises(ValueError):
        QuantileSketch(eps=0.7)


def test_sketch_state_roundtrip():
    sk = QuantileSketch(eps=0.01)
    sk.extend(np.random.default_rng(2).uniform(size=5_000))
    clone = QuantileSketch.from_state_dict(
        json.loads(json.dumps(sk.state_dict())))
    for q in QS:
        assert clone.quantile(q) == sk.quantile(q)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(min_value=-1e9, max_value=1e9), min_size=1,
                max_size=400))
def test_sketch_rank_error_property(xs):
    _assert_rank_error(xs, eps=0.01, qs=(0.5, 0.95))


def test_sketch_quantiles_monotone():
    sk = QuantileSketch()
    sk.extend(np.random.default_rng(3).exponential(size=10_000))
    vs = sk.quantiles(sorted(QS))
    assert vs == sorted(vs)


# ----------------------------------------------------------------------
# Registry semantics.
# ----------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests", labels=("phase",))
    c.inc(phase="llm")
    c.inc(2.0, phase="llm")
    c.inc(phase="vision")
    assert c.labels(phase="llm").value == 3.0
    assert c.labels(phase="vision").value == 1.0
    with pytest.raises(ValueError):
        c.labels(phase="llm").inc(-1.0)
    with pytest.raises(ValueError):
        c.labels(shard="0")  # wrong label name

    g = reg.gauge("temp")
    g.set(4.0)
    g.labels().add(1.0)
    assert g.labels().value == 5.0

    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    pairs = h.labels().bucket_counts()
    assert pairs[-1][0] == float("inf") and pairs[-1][1] == 4
    cums = [c for _, c in pairs]
    assert cums == sorted(cums)  # cumulative => monotone
    assert h.labels().mean() == pytest.approx(138.875)


def test_registry_reregistration_semantics():
    reg = MetricsRegistry()
    a = reg.counter("x", labels=("k",))
    assert reg.counter("x", labels=("k",)) is a  # idempotent
    with pytest.raises(ValueError):
        reg.gauge("x")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x", labels=("other",))  # label conflict


def test_snapshot_counters_flat_naming():
    reg = MetricsRegistry()
    reg.counter("kernel_hits", labels=("kernel",)).inc(kernel="flash")
    reg.counter("steps").inc(5)
    reg.gauge("mfu").set(0.4)  # gauges excluded
    snap = reg.snapshot_counters()
    assert snap == {"kernel_hits{kernel=flash}": 1.0, "steps": 5.0}
    assert reg.snapshot_counters(prefix="kernel_") == {
        "kernel_hits{kernel=flash}": 1.0}


def test_default_registry_swap():
    prev = get_registry()
    mine = MetricsRegistry()
    try:
        assert set_registry(mine) is prev
        assert get_registry() is mine
    finally:
        set_registry(prev)


# ----------------------------------------------------------------------
# OpenMetrics exposition.
# ----------------------------------------------------------------------
def test_render_openmetrics_format():
    reg = MetricsRegistry()
    reg.counter("train_steps", "steps so far").inc(3)
    reg.gauge("mfu", "model flops util").set(0.416)
    h = reg.histogram("step_ms", "step wall", labels=("phase",),
                      buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v, phase="llm")
    text = render_openmetrics(reg)
    assert text.endswith("# EOF\n")
    assert "# TYPE train_steps_total counter" in text
    assert "train_steps_total 3" in text  # counters get _total
    assert "mfu 0.416" in text
    assert 'step_ms_bucket{phase="llm",le="1"} 1' in text
    assert 'step_ms_bucket{phase="llm",le="10"} 2' in text
    assert 'step_ms_bucket{phase="llm",le="+Inf"} 3' in text
    assert 'step_ms_count{phase="llm"} 3' in text
    for suffix in ("p50", "p95", "p99"):
        assert f"step_ms_{suffix}" in text
    # Every non-comment line is "name{labels} value".
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name and value not in ("",)
        float(value)  # parses


def test_render_openmetrics_label_escaping():
    reg = MetricsRegistry()
    reg.counter("c", labels=("k",)).inc(k='a"b\\c')
    assert '{k="a\\"b\\\\c"}' in render_openmetrics(reg)


def test_write_openmetrics_atomic(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("g").set(1.0)
    path = str(tmp_path / "metrics.prom")
    write_openmetrics(path, reg)
    assert open(path).read().endswith("# EOF\n")
    assert not os.path.exists(path + ".tmp")  # tmp replaced, not left


# ----------------------------------------------------------------------
# Canonical ledger formulas.
# ----------------------------------------------------------------------
def _fake_report(phase_costs, *, solve_ms=None, exposed_ms=0.0,
                 replanned=False):
    return types.SimpleNamespace(
        phase_costs={k: np.asarray(v, dtype=np.float64)
                     for k, v in phase_costs.items()},
        phase_solve_ms=solve_ms or {k: 1.0 for k in phase_costs},
        exposed_ms=exposed_ms, replanned=replanned, coeff_version=-1)


def test_simulated_mfu_matches_old_benchmark_proxy():
    """The ledger formula must equal the proxy `benchmarks/common.py`
    computed inline before the dedup (sum of means / sum of maxes)."""
    rng = np.random.default_rng(4)
    costs = {p: rng.uniform(1.0, 10.0, size=8) for p in
             ("llm", "vision", "audio")}
    old_proxy = (sum(float(np.mean(c)) for c in costs.values())
                 / sum(float(np.max(c)) for c in costs.values()))
    assert simulated_mfu(costs) == pytest.approx(old_proxy, rel=1e-12)
    assert straggler_overhead(costs) == pytest.approx(1.0 - old_proxy)


def test_simulated_mfu_on_real_orchestrator_report():
    """Same equality on a genuine plan (not synthetic cost dicts)."""
    from repro.configs import get_config
    from repro.core.orchestrator import MLLMGlobalOrchestrator
    from repro.data.synthetic import TaskMix, sample_examples

    cfg = get_config("mllm_10b").smoke()
    rng = np.random.default_rng(5)
    examples = [sample_examples(rng, 3, TaskMix(), ("vision", "audio"))
                for _ in range(4)]
    orch = MLLMGlobalOrchestrator(cfg, 4, vocab=512)
    caps = orch.default_capacities(examples, margin=3.0)
    _, report = orch.plan_and_pack(examples, caps, rng)
    old_proxy = (sum(float(np.mean(c)) for c in report.phase_costs.values())
                 / sum(float(np.max(c)) for c in report.phase_costs.values()))
    assert simulated_mfu(report.phase_costs) == pytest.approx(old_proxy)
    assert 0.0 < simulated_mfu(report.phase_costs) <= 1.0


def test_formula_edge_cases():
    assert simulated_mfu({}) == 1.0
    assert simulated_mfu({"llm": []}) == 1.0
    assert phase_imbalance([5.0, 5.0, 5.0]) == 0.0
    assert phase_imbalance([1.0, 3.0]) == pytest.approx(0.5)
    assert phase_imbalance([]) == 0.0
    # goodput: exposed host latency discounts the MFU.
    assert goodput_fraction(100.0, 0.0, 0.8) == pytest.approx(0.8)
    assert goodput_fraction(100.0, 25.0, 0.8) == pytest.approx(0.6)
    assert goodput_fraction(100.0, 1e9, 0.8) == 0.0  # clamped
    assert goodput_fraction(0.0, 5.0, 0.8) == 0.8  # no wall measured


def test_step_ledger_records_series_and_alerts():
    reg = MetricsRegistry()
    led = StepLedger(d=4, registry=reg)
    rep = _fake_report({"llm": [2.0, 2.0, 2.0, 4.0],
                        "vision": [1.0, 1.0, 1.0, 1.0]},
                       exposed_ms=5.0)
    events = led.record_step(0, report=rep, step_ms=50.0,
                             metrics={"loss": 2.5, "tokens": 128.0})
    assert events == []
    # replan + MoE drop spike both alert on the next step.
    rep2 = _fake_report({"llm": [2.0, 2.0, 2.0, 4.0]}, replanned=True)
    events = led.record_step(1, report=rep2, step_ms=50.0,
                             metrics={"moe_dropped_frac": 0.2})
    kinds = sorted(e["alert"] for e in events)
    assert kinds == ["moe_drop_spike", "stale_plan_replanned"]
    # below-threshold drop fraction stays quiet
    assert led.record_step(2, metrics={"moe_dropped_frac": 0.01}) == []

    assert reg.get("train_steps").labels().value == 3.0
    assert reg.get("train_tokens").labels().value == 128.0
    mfu = reg.get("train_mfu_simulated").labels().value
    assert 0.0 < mfu < 1.0
    assert reg.get("train_metric").labels(name="loss").value == 2.5
    # per-phase imbalance series tracked for the anomaly monitor
    assert [s for s, _ in led.series["mfu_simulated"]] == [0, 1]
    assert led.series["imbalance_llm"][0][1] == pytest.approx(
        4.0 / 2.5 - 1.0)
    s = led.summary()
    assert s["steps"] == 3 and s["tokens"] == 128.0
    assert s["step_ms_p50"] == pytest.approx(50.0)


def test_step_ledger_hw_mfu():
    cfg = types.SimpleNamespace(active_param_count=lambda: 1e9)
    led = StepLedger(cfg, d=2, registry=MetricsRegistry(), peak_flops=1e12,
                     chips=2)
    led.record_step(0, step_ms=3000.0, metrics={"tokens": 100.0})
    # 6e9 flops/token * 100 tokens / (3 s * 1e12 * 2 chips)
    assert led.series["mfu_hw"][0][1] == pytest.approx(0.1)


# ----------------------------------------------------------------------
# Flight recorder: crash safety.
# ----------------------------------------------------------------------
def test_flight_recorder_roundtrip(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    with FlightRecorder(path, meta={"arch": "mllm_10b"},
                        flush_every=100) as rec:
        for i in range(5):
            rec.record("step", step=i)
    events = read_flight_record(path)
    assert [e["kind"] for e in events] == ["meta"] + ["step"] * 5
    assert events[0]["arch"] == "mllm_10b"
    assert all("ts" in e for e in events)


def test_flight_recorder_torn_tail_tolerated(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    rec = FlightRecorder(path, meta={})
    rec.record("step", step=0)
    rec.flush()
    # Crash mid-write of the next buffer: a torn final line on disk.
    with open(path, "a") as f:
        f.write('{"kind": "step", "st')
    events = read_flight_record(path)
    assert [e["kind"] for e in events] == ["meta", "step"]


def test_flight_recorder_mid_file_corruption_raises(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    rec = FlightRecorder(path, meta={})
    rec.record("step", step=0)
    rec.flush()
    with open(path, "a") as f:
        f.write("GARBAGE NOT JSON\n")
    rec.record("step", step=1)
    rec.flush()
    with pytest.raises(ValueError, match="corrupt flight record"):
        read_flight_record(path)


def test_flight_recorder_survives_sigkill(tmp_path):
    """Kill a recording process mid-step: the record must be valid JSONL
    up to the last explicit flush (ISSUE acceptance semantics)."""
    path = str(tmp_path / "flight.jsonl")
    child = textwrap.dedent(f"""
        import os, signal
        from repro.obs import FlightRecorder
        rec = FlightRecorder({path!r}, meta={{"run": "crashy"}},
                             flush_every=1000)
        for i in range(10):
            rec.record("step", step=i)
        rec.flush()
        for i in range(10, 15):          # never flushed
            rec.record("step", step=i)
        os.kill(os.getpid(), signal.SIGKILL)
    """)
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == -signal.SIGKILL
    events = read_flight_record(path)
    assert [e["kind"] for e in events] == ["meta"] + ["step"] * 10
    assert [e["step"] for e in events[1:]] == list(range(10))


# ----------------------------------------------------------------------
# Alert routing.
# ----------------------------------------------------------------------
def test_alert_bridge_routes_all_signal_shapes(tmp_path):
    reg = MetricsRegistry()
    path = str(tmp_path / "flight.jsonl")
    with FlightRecorder(path, meta={}) as rec:
        bridge = AlertBridge(rec, reg)
        bridge.on_drift({"llm": True, "vision": False}, step=7)
        bridge.on_checkpoint_fallback("/ckpt/step_4.corrupt", restored_step=2)
        bridge.on_preemptions(2, step=8)   # below storm threshold
        bridge.on_preemptions(3, step=9)   # storm
        bridge.on_ledger_events([{"alert": "moe_drop_spike", "step": 10,
                                  "moe_dropped_frac": 0.2}])
    events = [e for e in read_flight_record(path) if e["kind"] == "alert"]
    assert [e["alert"] for e in events] == [
        "cost_model_drift", "checkpoint_corruption_fallback",
        "preemption_storm", "moe_drop_spike"]
    assert events[0]["phase"] == "llm" and events[0]["step"] == 7
    snap = reg.snapshot_counters(prefix="alerts")
    assert snap["alerts{alert=cost_model_drift}"] == 1.0
    assert "alerts{alert=preemption_storm}" in snap


def test_ledger_flags_inconsistent_clocks():
    """exposed_ms > step_ms means the host and step clocks disagree;
    the ledger must surface that as an alert event, not clamp silently."""
    led = StepLedger(d=2, registry=MetricsRegistry())
    rep = _fake_report({"llm": [1.0, 1.0]}, exposed_ms=25.0)
    events = led.record_step(0, report=rep, step_ms=10.0)
    bad = [e for e in events if e["alert"] == "measurement_inconsistent"]
    assert len(bad) == 1
    assert bad[0]["exposed_ms"] == 25.0 and bad[0]["step_ms"] == 10.0
    # the clamp still applies to the goodput gauge itself
    assert 0.0 <= led.series["goodput_frac"][-1][1] <= 1.0


def test_step_timing_carries_preemption_fields():
    from repro.serving.engine.engine import StepTiming

    t = StepTiming(step=0, schedule_ms=0.1, prefill_ms=0.0, decode_ms=0.2,
                   n_prefill_seqs=0, prefill_tokens=0, n_decode_seqs=1)
    assert t.n_preempted == 0 and t.recompute_tokens == 0  # defaults
    t2 = StepTiming(step=1, schedule_ms=0.1, prefill_ms=0.0, decode_ms=0.2,
                    n_prefill_seqs=0, prefill_tokens=0, n_decode_seqs=1,
                    n_preempted=2, recompute_tokens=96)
    assert t2.n_preempted == 2 and t2.recompute_tokens == 96


# ----------------------------------------------------------------------
# Kernel hooks.
# ----------------------------------------------------------------------
def test_autotune_resolve_counts_outcomes(tmp_path, monkeypatch):
    from repro.kernels import autotune

    prev = set_registry(MetricsRegistry())
    try:
        monkeypatch.delenv("REPRO_KERNEL_BLOCKS", raising=False)
        cache = str(tmp_path / "cache.json")
        autotune.resolve("flash", {"seq": 128}, (128, 128),
                         cache_path=cache)                    # miss
        autotune.resolve("flash", {"seq": 128}, (128, 128),
                         enabled=False, cache_path=cache)     # disabled
        monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "flash=256x128")
        assert autotune.resolve("flash", {"seq": 128}, (128, 128),
                                cache_path=cache) == (256, 128)  # override
        snap = get_registry().snapshot_counters(prefix="kernel_")
        assert snap["kernel_autotune_resolves{kernel=flash,outcome=miss}"] == 1
        assert snap["kernel_autotune_resolves{kernel=flash,outcome=disabled}"] == 1
        assert snap["kernel_autotune_resolves{kernel=flash,outcome=override}"] == 1
    finally:
        set_registry(prev)


def test_tile_skip_fraction_matches_live_tiles():
    from repro.kernels.flash_attention import (count_live_tiles,
                                               tile_skip_fraction)

    # two streams of 32, causal: upper-triangle KV tiles are skipped
    seg = np.repeat([1, 2], 32)[None, :]
    pos = np.concatenate([np.arange(32), np.arange(32)])[None, :]
    kw = dict(block_q=16, block_kv=16, causal=True, window=None)
    frac = tile_skip_fraction(seg, seg, pos, pos, **kw)
    visited, total = count_live_tiles(seg, seg, pos, pos, **kw)
    assert frac == pytest.approx(1.0 - visited / total)
    assert 0.0 < frac < 1.0  # causal + cross-segment => real skips


def test_group_tile_skip_fraction():
    from repro.kernels.grouped_gemm import group_tile_skip_fraction

    assert group_tile_skip_fraction([0, 0, 0], block_m=4) == 0.0
    # 16 rows over 4 m-tiles x 3 experts = 12 grid cells; expert 0 owns
    # tiles {0,1}, expert 2 owns {2,3}, the empty expert owns none.
    assert group_tile_skip_fraction([8, 0, 8], block_m=4) == pytest.approx(
        1.0 - 4.0 / 12.0)
    # perfectly aligned groups touch exactly one tile column each
    assert group_tile_skip_fraction([8, 8], block_m=4) == pytest.approx(0.5)
