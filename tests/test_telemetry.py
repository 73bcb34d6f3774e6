"""Telemetry & online cost-model calibration tests (ISSUE 4).

Covers: trace ring buffer, NNLS nonnegativity,
planted-coefficient recovery (property test), convergence from a
3x-miscalibrated prior, CUSUM drift detection (fires on a step-change,
quiet on stationary noise), the end-to-end orchestrator acceptance bar
(calibrated imbalance within 5% of oracle on identical token streams),
and the serving-side breakdown + weight calibration."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.cost_model import (
    CostModel,
    ServingCostModel,
    encoder_cost_model,
    length_features,
    llm_cost_model,
    serving_cost_model,
)
from repro.core.dispatcher import BatchPostBalancingDispatcher
from repro.core.orchestrator import MLLMGlobalOrchestrator
from repro.data.synthetic import TaskMix, sample_examples
from repro.telemetry import (
    AdaptiveCostModel,
    AdaptiveOrchestration,
    AdaptiveServingCostModel,
    DriftDetector,
    PhaseCalibrator,
    PhaseSample,
    RecursiveFit,
    ServingCalibrator,
    TraceBuffer,
    nnls_fit,
)


def _varied_features(rng, n, *, padding=False, lo=16, hi=2048):
    """Identifiable design: batch size AND length scale vary across
    rows, so the linear and quadratic columns decorrelate."""
    rows = []
    for _ in range(n):
        b = int(rng.integers(2, 48))
        top = int(rng.integers(lo + 1, hi))
        rows.append(length_features(rng.integers(lo, top + 1, size=b), padding))
    return np.stack(rows)


# ----------------------------------------------------------------------
# Feature basis.
# ----------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_features_consistent_with_cost(seed):
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, 500, size=rng.integers(1, 40))
    for cm in (CostModel(alpha=0.7, beta=3e-3),
               CostModel(alpha=0.7, beta=3e-3, padding=True),
               CostModel(alpha=0.7, beta=3e-3, conv_attention=True)):
        f = cm.feature_vector(lens)
        assert np.isclose(float(cm.cost_from_features(f)), cm.cost(lens))
    ids = rng.integers(0, 4, size=lens.size)
    cm = CostModel(alpha=1.0, beta=1e-3, padding=True)
    F = cm.segment_features(lens.astype(float), ids, 4)
    np.testing.assert_allclose(cm.cost_from_features(F),
                               cm.segment_costs(lens.astype(float), ids, 4))


def test_dispatch_plan_carries_features():
    rng = np.random.default_rng(3)
    cm = CostModel(alpha=1.0, beta=1e-3)
    disp = BatchPostBalancingDispatcher(4, cm)
    plan = disp.plan([rng.integers(1, 200, size=8) for _ in range(4)])
    assert plan.features.shape == (4, 4)
    np.testing.assert_allclose(cm.cost_from_features(plan.features), plan.costs)


# ----------------------------------------------------------------------
# Trace buffer.
# ----------------------------------------------------------------------
def test_trace_ring_evicts_oldest():
    buf = TraceBuffer(capacity=8)
    for i in range(20):
        buf.add(PhaseSample.from_lengths("llm", [i + 1], 1.0, step=i))
    assert len(buf) == 8 and buf.dropped == 12
    steps = [s.step for s in buf.samples()]
    assert steps == list(range(12, 20))  # oldest-first, newest kept


def test_trace_filters_and_design_matrix():
    buf = TraceBuffer()
    buf.add(PhaseSample.from_lengths("llm", [5, 6], 2.0, step=0))
    buf.add(PhaseSample.from_lengths("vision", [7], 3.0, step=0))
    buf.add(PhaseSample("llm", 1, 1, np.ones(4), 0.5))
    X, y = buf.design_matrix("llm")
    assert X.shape == (2, 4) and y.tolist() == [2.0, 0.5]
    assert buf.phases() == ["llm", "vision"]
    assert [s.step for s in buf.samples("vision")] == [0]


# ----------------------------------------------------------------------
# NNLS fitting.
# ----------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_nnls_never_negative(seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rng.integers(3, 30), 3)) * [1.0, 100.0, 1e4]
    y = rng.normal(size=X.shape[0]) - 5.0  # adversarial: negative targets
    c = nnls_fit(X, y, ridge=1e-3, prior=[0.5, 0.0, 0.0])
    assert (c >= 0).all()


def test_nnls_zero_samples_returns_prior():
    c = nnls_fit(np.zeros((0, 2)), np.zeros(0), ridge=1e-3, prior=[2.0, 3.0])
    assert c.tolist() == [2.0, 3.0]


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fit_recovers_planted_coeffs(seed):
    rng = np.random.default_rng(seed)
    alpha = float(rng.uniform(0.2, 3.0))
    beta = float(rng.uniform(1e-4, 5e-3))
    truth = CostModel(alpha=alpha, beta=beta)
    cal = PhaseCalibrator(truth.with_coeffs(1.0, 1e-3), min_samples=12)
    X = _varied_features(rng, 120)
    y = truth.cost_from_features(X) * (1 + rng.normal(0, 0.02, size=len(X)))
    cal.observe(X, y)
    est = cal.estimate
    assert cal.calibrated
    assert est.alpha == pytest.approx(alpha, rel=0.15)
    assert est.beta == pytest.approx(beta, rel=0.3)
    # lam (the only thing balancing consumes) is recovered tightly
    assert cal.cost_model().lam == pytest.approx(beta / alpha, rel=0.3)


def test_convergence_from_3x_miscalibrated_prior_within_k_samples():
    rng = np.random.default_rng(7)
    truth = CostModel(alpha=1.0, beta=8e-4)
    prior = truth.with_coeffs(1.0, 3 * 8e-4)
    adapt = AdaptiveCostModel(prior, phase="llm")
    K = 48
    for step in range(K):
        F = _varied_features(rng, 4)
        t = truth.cost_from_features(F) * (1 + rng.normal(0, 0.03, size=4))
        adapt.observe(F, t, step=step)
    assert adapt.calibrated
    assert adapt.calibrator.n_observed <= 4 * K
    assert adapt.current().lam == pytest.approx(truth.lam, rel=0.2)
    assert adapt.version >= 1  # swap-in bumped the plan version


def test_ssm_phase_calibrates_to_zero_beta():
    # A truly linear phase (SSM: beta = 0) must reach confidence with
    # beta pinned at the NNLS boundary, not be stuck "uncertain".
    rng = np.random.default_rng(11)
    truth = CostModel(alpha=2.0, beta=0.0)
    cal = PhaseCalibrator(truth.with_coeffs(1.0, 1e-3))
    X = _varied_features(rng, 100)
    cal.observe(X, truth.cost_from_features(X)
                * (1 + rng.normal(0, 0.02, size=100)))
    assert cal.calibrated
    # lam collapses to ~0 (>= 10x below the prior's 5e-4): the quad
    # term's residual ridge pull has negligible balancing impact.
    assert cal.cost_model().lam < 5e-5
    assert cal.estimate.alpha == pytest.approx(2.0, rel=0.1)


def test_recursive_fit_tracks_planted_slope():
    rng = np.random.default_rng(5)
    rls = RecursiveFit(2, prior=[1.0, 0.0], ridge=1e-2)
    for _ in range(300):
        x = np.array([rng.uniform(10, 1000), rng.uniform(1e3, 1e6)])
        y = 0.5 * x[0] + 2e-3 * x[1] + rng.normal(0, 1.0)
        rls.update(x, y)
    c = rls.coeffs
    assert (c >= 0).all()
    assert c[0] == pytest.approx(0.5, rel=0.2)
    assert c[1] == pytest.approx(2e-3, rel=0.2)


# ----------------------------------------------------------------------
# Drift detection.
# ----------------------------------------------------------------------
def test_cusum_quiet_on_stationary_noise():
    rng = np.random.default_rng(0)
    det = DriftDetector()
    fired = sum(det.update(r) for r in rng.normal(0, 0.05, size=5000))
    assert fired == 0 and det.events == 0


def test_cusum_fires_on_step_change():
    rng = np.random.default_rng(1)
    det = DriftDetector()
    for r in rng.normal(0, 0.05, size=200):
        assert not det.update(r)
    fired = False
    for r in rng.normal(0.5, 0.05, size=100):  # 10-sigma mean shift
        if det.update(r):
            fired = True
            break
    assert fired and det.events == 1


def test_calibrator_drift_recovers_new_regime():
    rng = np.random.default_rng(9)
    regime_a = CostModel(alpha=1.0, beta=5e-4)
    regime_b = CostModel(alpha=1.0, beta=2.5e-3)  # resolution-shift analog
    adapt = AdaptiveCostModel(regime_a.with_coeffs(1.0, 1e-3), phase="llm")

    def feed(truth, steps, start):
        drifts = 0
        for step in range(start, start + steps):
            F = _varied_features(rng, 4)
            t = truth.cost_from_features(F) * (1 + rng.normal(0, 0.03, size=4))
            drifts += bool(adapt.observe(F, t, step=step))
        return drifts

    assert feed(regime_a, 40, 0) == 0  # converging on A is not drift
    assert adapt.calibrated
    assert adapt.current().lam == pytest.approx(regime_a.lam, rel=0.2)
    v = adapt.version
    assert feed(regime_b, 60, 40) >= 1  # step-change flagged
    assert adapt.drift_events >= 1
    assert adapt.version > v
    assert adapt.current().lam == pytest.approx(regime_b.lam, rel=0.25)


# ----------------------------------------------------------------------
# End-to-end orchestrator acceptance (ISSUE 4 bar).
# ----------------------------------------------------------------------
def _stream_fingerprint(batch):
    """Order-invariant fingerprint of the packed token payload: the
    multiset of per-example (segment-sorted) token tuples."""
    tokens, seg = batch["tokens"], batch.get("llm_seg", batch.get("seg"))
    per_ex = {}
    text_seg = batch.get("llm_seg")
    if text_seg is not None:
        # multimodal layout: text tokens live in their own stream, keyed
        # by destination slots into the llm stream
        dst = batch["text_dst"]
        for i in range(tokens.shape[0]):
            live = dst[i] < text_seg.shape[1]
            sids = text_seg[i][dst[i][live]]
            for s in np.unique(sids):
                per_ex[int(s)] = tuple(tokens[i][live][sids == s].tolist())
    else:
        for i in range(tokens.shape[0]):
            for s in np.unique(seg[i]):
                if s > 0:
                    per_ex[int(s)] = tuple(tokens[i][seg[i] == s].tolist())
    return per_ex


def test_adaptive_orchestrator_end_to_end_matches_oracle():
    """From a 3x-miscalibrated prior, calibrated post-balanced max-cost
    imbalance lands within 5% of the oracle-coefficient run, on
    identical token streams (calibration changes only the plan)."""
    cfg = get_config("mllm_10b")
    d, per, steps = 4, 16, 30
    lam_true = {"llm": 8e-4, "vision": 1.5e-3, "audio": 4e-4}
    oracle = {"llm": llm_cost_model(cfg).with_coeffs(1.0, lam_true["llm"])}
    for e in cfg.encoders:
        oracle[e.name] = encoder_cost_model(e).with_coeffs(
            1.0, lam_true[e.name])
    prior = {k: m.with_coeffs(1.0, m.beta * 3) for k, m in oracle.items()}

    orch_oracle = MLLMGlobalOrchestrator(cfg, d, vocab=512)
    orch_oracle.llm_dispatcher.cost_model = oracle["llm"]
    for n, disp in orch_oracle.enc_dispatchers.items():
        disp.cost_model = oracle[n]
    orch_adapt = MLLMGlobalOrchestrator(
        cfg, d, vocab=512, adaptive=AdaptiveOrchestration(priors=prior))

    noise = np.random.default_rng(0)

    def imbalance(plans):
        mx = mn = 0.0
        for ph, F in plans.features.items():
            c = oracle[ph].cost_from_features(F)
            mx += float(c.max())
            mn += float(c.mean())
        return mx / mn

    imb_a, imb_o = [], []
    for step in range(steps):
        examples = [
            sample_examples(np.random.default_rng(100 * step + i), per,
                            TaskMix(), ("vision", "audio"))
            for i in range(d)
        ]
        plans_o = orch_oracle.plan_phases(examples)
        plans_a = orch_adapt.plan_phases(examples)
        imb_o.append(imbalance(plans_o))
        imb_a.append(imbalance(plans_a))
        times = {ph: oracle[ph].cost_from_features(F)
                 * (1 + noise.normal(0, 0.03, size=d))
                 for ph, F in plans_a.features.items()}
        orch_adapt.observe_phase_times(times, plans=plans_a, step=step)
    half = steps // 2
    cal, orc = np.mean(imb_a[half:]), np.mean(imb_o[half:])
    assert orch_adapt.adaptive.calibrated
    assert cal <= 1.05 * orc, (cal, orc)

    # Identical tokens/streams: pack one batch under both plans and
    # compare the per-example payload multisets.
    examples = [
        sample_examples(np.random.default_rng(9000 + i), per, TaskMix(),
                        ("vision", "audio"))
        for i in range(d)
    ]
    caps = orch_oracle.default_capacities(examples, margin=3.0)
    rng = np.random.default_rng(1)
    batch_o, _ = orch_oracle.plan_and_pack(examples, caps, rng)
    batch_a, rep_a = orch_adapt.plan_and_pack(examples, caps, rng)
    assert _stream_fingerprint(batch_o) == _stream_fingerprint(batch_a)
    assert rep_a.coeff_version >= 0


def test_stale_plan_ahead_replans_on_coefficient_swap():
    cfg = get_config("olmo_1b")
    truth = CostModel(alpha=1.0, beta=8e-4)
    prior = truth.with_coeffs(1.0, 3 * 8e-4)
    orch = MLLMGlobalOrchestrator(
        cfg, 4, vocab=512,
        adaptive=AdaptiveOrchestration(priors={"llm": prior}))
    rng = np.random.default_rng(2)
    examples = [
        sample_examples(np.random.default_rng(i), 8, TaskMix(), ())
        for i in range(4)
    ]
    caps = orch.default_capacities(examples, margin=3.0)
    plans = orch.plan_phases(examples, caps)
    assert plans.coeff_version == 0
    # Calibration swaps coefficients in while the plan sits in flight.
    adapt = orch.adaptive.models["llm"]
    noise = np.random.default_rng(3)
    step = 0
    while not adapt.calibrated:
        F = _varied_features(noise, 4)
        adapt.observe(F, truth.cost_from_features(F)
                      * (1 + noise.normal(0, 0.02, size=4)), step=step)
        step += 1
        assert step < 200, "calibration did not converge"
    assert orch.adaptive.version != plans.coeff_version
    _, report = orch.plan_and_pack(examples, caps, rng, plans)
    assert report.replanned and orch.replans == 1
    assert report.coeff_version == orch.adaptive.version
    # A fresh plan is up to date and is NOT re-planned.
    plans2 = orch.plan_phases(examples, caps)
    _, report2 = orch.plan_and_pack(examples, caps, rng, plans2)
    assert not report2.replanned and orch.replans == 1


def test_observe_requires_adaptive_and_exactly_one_source():
    cfg = get_config("olmo_1b")
    orch = MLLMGlobalOrchestrator(cfg, 2, vocab=512)
    with pytest.raises(ValueError):
        orch.observe_phase_times({"llm": 1.0}, report=None, plans=None)
    orch2 = MLLMGlobalOrchestrator(
        cfg, 2, vocab=512,
        adaptive=AdaptiveOrchestration(priors={"llm": CostModel()}))
    with pytest.raises(ValueError):
        orch2.observe_phase_times({"llm": 1.0})


# ----------------------------------------------------------------------
# Serving-side calibration.
# ----------------------------------------------------------------------
def test_serving_calibrator_recovers_weights_and_decode_cost():
    rng = np.random.default_rng(4)
    c_text, c_vis, c_aud, c_dec = 0.01, 0.04, 0.025, 0.004
    cal = ServingCalibrator(("vision", "audio"))
    for _ in range(60):
        nt = int(rng.integers(10, 500))
        nv = int(rng.integers(0, 300))
        na = int(rng.integers(0, 200))
        t = (c_text * nt + c_vis * nv + c_aud * na) * (1 + rng.normal(0, 0.02))
        cal.observe_prefill({"text": nt, "vision": nv, "audio": na}, t)
        b = int(rng.integers(1, 16))
        cal.observe_decode(b, c_dec * b * (1 + rng.normal(0, 0.02)))
    assert cal.calibrated
    w = cal.weights()
    assert w["vision"] == pytest.approx(c_vis / c_text, rel=0.15)
    assert w["audio"] == pytest.approx(c_aud / c_text, rel=0.15)
    assert cal.decode_cost() == pytest.approx(c_dec / c_text, rel=0.15)


def test_adaptive_serving_cost_model_swaps_weights():
    prior = ServingCostModel(CostModel(alpha=1.0, beta=1e-4),
                             modality_weights={"vision": 2.0, "audio": 1.5})
    adapt = AdaptiveServingCostModel(prior)
    # Before calibration: the prior answers.
    assert adapt.weighted_length(10, {"vision": 4}) == 10 + 2.0 * 4
    rng = np.random.default_rng(8)
    c_text, c_vis, c_aud = 0.01, 0.05, 0.012
    for step in range(60):
        nt, nv, na = (int(rng.integers(10, 400)), int(rng.integers(0, 250)),
                      int(rng.integers(0, 150)))
        t = (c_text * nt + c_vis * nv + c_aud * na) * (1 + rng.normal(0, 0.02))
        adapt.observe_prefill({"text": nt, "vision": nv, "audio": na}, t,
                              step=step)
    assert adapt.calibrated and adapt.version >= 1
    assert adapt.modality_weights["vision"] == pytest.approx(5.0, rel=0.2)
    assert adapt.modality_weights["audio"] == pytest.approx(1.2, rel=0.25)
    # Admission maths flow through the calibrated weights.
    wl = adapt.weighted_length(100, {"vision": 10})
    assert wl == pytest.approx(100 + adapt.modality_weights["vision"] * 10)
    # decode_cost untouched without decode samples.
    assert adapt.decode_cost == prior.decode_cost
    s = adapt.summary()
    assert s["calibrated"] and s["prior_weights"]["vision"] == 2.0


def test_serving_cost_model_helper_shared():
    # Satellite: one shared derivation helper for training + serving.
    from repro.serving.engine.scheduler import serving_cost_model as via_sched
    cfg = get_config("llava_next_mistral_7b")
    a = via_sched(cfg)
    b = serving_cost_model(cfg)
    assert a == b
    assert set(a.modality_weights) == {e.name for e in cfg.encoders}
