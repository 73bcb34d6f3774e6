"""Pipeline-parallel 1F1B schedule + encoder bubble-fill tests.

Covers the planning stack end to end (docs/pipeline.md): stage
partitioning, LPT microbatch split, the event-driven 1F1B simulator's
dependency/bubble invariants, EDF + cross-iteration encoder fill
bounds, the exact per-rank closure identity the waterfall relies on,
the staged-config headline gates (fill fraction, MFU uplift), and the
observability fan-out (waterfall components, ledger series, Perfetto
stage lanes, pp mesh/sharding).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.cost_model import (encoder_cost_model, llm_cost_model,
                                   phase_flops_per_unit)
from repro.core.dispatcher import BatchPostBalancingDispatcher
from repro.core.orchestrator import MLLMGlobalOrchestrator
from repro.core.pipeline import (BWD_RATIO, _idle_windows, _simulate_1f1b,
                                 plan_pipeline, split_microbatches)
from repro.data.synthetic import TaskMix, sample_examples
from repro.launch.mesh import (dp_shards_of, make_production_mesh,
                               pp_stages_of)
from repro.obs.decompose import GapWaterfall
from repro.obs.ledger import StepLedger
from repro.obs.registry import MetricsRegistry
from repro.sharding.specs import stage_partition

EPS = 1e-9


def _cfg():
    return get_config("mllm_84b")


def _plan(d=4, per=64, pp=4, m=16, seed=0, bubble_fill=True, enc_scale=1.0):
    """A staged plan over synthetic post-balanced lengths."""
    cfg = _cfg()
    rng = np.random.default_rng(seed)
    model = llm_cost_model(cfg)
    dest = [rng.integers(200, 2000, size=per).astype(np.float64)
            for _ in range(d)]
    # Per-rank encoder cost vectors in their OWN units, roughly balanced
    # (the dispatchers have already run).
    enc = {e.name: enc_scale * rng.uniform(0.95, 1.05, size=d)
           * 4_000_000.0 for e in cfg.encoders}
    return plan_pipeline(cfg, model, dest, enc, pp=pp, n_micro=m,
                         bubble_fill=bubble_fill)


# ----------------------------------------------------------------------
# stage_partition
# ----------------------------------------------------------------------
def test_stage_partition_uniform():
    assert stage_partition(80, 4) == (20, 20, 20, 20)
    # Uneven: extra layers land on the EARLY stages.
    assert stage_partition(10, 4) == (3, 3, 2, 2)
    assert stage_partition(7, 1) == (7,)
    assert sum(stage_partition(45, 6)) == 45


def test_stage_partition_weighted_beats_uniform():
    # Heavy head: a cost-aware split must not exceed the uniform split's
    # max stage cost, and here it must strictly improve.
    costs = np.array([8.0, 8.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])
    part = stage_partition(8, 4, costs)
    assert sum(part) == 8 and len(part) == 4 and min(part) >= 1
    bounds = np.cumsum((0,) + part)
    maxc = max(costs[a:b].sum() for a, b in zip(bounds[:-1], bounds[1:]))
    uni = max(costs[i:i + 2].sum() for i in range(0, 8, 2))
    assert maxc <= uni
    assert maxc == 8.0  # optimal: isolate each heavy layer


def test_stage_partition_errors():
    with pytest.raises(ValueError):
        stage_partition(4, 0)
    with pytest.raises(ValueError):
        stage_partition(4, 5)
    with pytest.raises(ValueError):
        stage_partition(4, 2, np.ones(3))


# ----------------------------------------------------------------------
# split_microbatches
# ----------------------------------------------------------------------
def test_split_microbatches_partitions_everything():
    model = llm_cost_model(_cfg())
    lengths = np.array([100.0, 900.0, 300.0, 500.0, 700.0, 110.0, 250.0])
    assign, costs = split_microbatches(lengths, 3, model)
    assert assign.shape == (7,) and set(assign) <= {0, 1, 2}
    w = model.alpha * lengths + model.beta * lengths**2
    assert np.isclose(costs.sum(), w.sum())
    for i in range(3):
        assert np.isclose(costs[i], w[assign == i].sum())


def test_split_microbatches_balances():
    model = llm_cost_model(_cfg())
    rng = np.random.default_rng(3)
    lengths = rng.integers(100, 2000, size=64).astype(np.float64)
    _, costs = split_microbatches(lengths, 8, model)
    w = model.alpha * lengths + model.beta * lengths**2
    # LPT guarantee: max bin <= mean + max single item.
    assert costs.max() <= w.sum() / 8 + w.max() + EPS
    _, empty = split_microbatches(np.array([]), 4, model)
    assert empty.sum() == 0


# ----------------------------------------------------------------------
# 1F1B simulator
# ----------------------------------------------------------------------
def _check_dependencies(fwd, bwd, f_s, f_e, b_s, b_e):
    pp, m = fwd.shape
    for s in range(pp):
        for i in range(m):
            assert np.isclose(f_e[s, i] - f_s[s, i], fwd[s, i])
            assert np.isclose(b_e[s, i] - b_s[s, i], bwd[s, i])
            if s > 0:
                assert f_s[s, i] >= f_e[s - 1, i] - EPS
            if s < pp - 1:
                assert b_s[s, i] >= b_e[s + 1, i] - EPS
            assert b_s[s, i] >= f_e[s, i] - EPS
        # No two ops overlap on one stage's device.
        spans = sorted(list(zip(f_s[s], f_e[s])) + list(zip(b_s[s], b_e[s])))
        for (a0, b0), (a1, _) in zip(spans, spans[1:]):
            assert a1 >= b0 - EPS


def test_1f1b_dependencies_random_costs():
    rng = np.random.default_rng(7)
    fwd = rng.uniform(1.0, 3.0, size=(4, 8))
    bwd = 2.0 * fwd
    f_s, f_e, b_s, b_e, makespan = _simulate_1f1b(fwd, bwd)
    _check_dependencies(fwd, bwd, f_s, f_e, b_s, b_e)
    assert makespan >= fwd.sum(axis=1).max() + bwd.sum(axis=1).max() - EPS
    assert np.isclose(makespan, max(f_e.max(), b_e.max()))


def test_1f1b_uniform_bubble_identity():
    # Equal stage times f, b: total bubble = pp*(pp-1)*(f+b) exactly.
    pp, m, f, b = 4, 8, 1.0, 2.0
    fwd = np.full((pp, m), f)
    bwd = np.full((pp, m), b)
    f_s, f_e, b_s, b_e, makespan = _simulate_1f1b(fwd, bwd)
    assert np.isclose(makespan, (m + pp - 1) * (f + b))
    busy = fwd.sum() + bwd.sum()
    assert np.isclose(pp * makespan - busy, pp * (pp - 1) * (f + b))
    windows = _idle_windows(f_s, f_e, b_s, b_e, makespan)
    idle = [sum(w1 - w0 for w0, w1 in ws) for ws in windows]
    assert np.isclose(sum(idle), pp * makespan - busy)
    # Stage 0 never waits in the uniform case; last stage idles most at
    # the start (deepest warm-up), plus its cool-down mirror.
    assert idle[0] <= idle[-1] + EPS


# ----------------------------------------------------------------------
# bubble fill: dependency bounds on the emitted events
# ----------------------------------------------------------------------
def test_fill_respects_dependency_bounds():
    plan = _plan(d=2, per=48, pp=4, m=8, seed=1)
    ev = plan.events
    assert ev, "critical-rank events must be kept by default"
    f0_start = {e.micro: e.start for e in ev if e.kind == "F" and e.stage == 0}
    b0_end = {e.micro: e.end for e in ev if e.kind == "B" and e.stage == 0}
    kinds = {e.kind for e in ev}
    assert kinds >= {"F", "B"}
    for e in ev:
        assert e.end >= e.start - EPS
        if e.kind == "encF" and e.micro >= 0:
            # Encoder forward for micro i must finish before F(0, i).
            assert e.end <= f0_start[e.micro] + 1e-6
        if e.kind == "encB" and e.micro >= 0:
            # Encoder backward for micro i releases at end of B(0, i).
            assert e.start >= b0_end[e.micro] - 1e-6
    # Per stage, all spans (LLM + encoder fill) are mutually disjoint.
    for s in range(plan.pp):
        spans = sorted((e.start, e.end) for e in ev if e.stage == s)
        for (a0, b0), (a1, _) in zip(spans, spans[1:]):
            assert a1 >= b0 - 1e-6


def test_closure_identity_exact():
    # useful + sum_s idle_s == pp * rank_total, per rank, by construction
    # -- this is what makes the waterfall's pipeline algebra close.
    for fill in (True, False):
        plan = _plan(d=3, per=32, pp=4, m=8, seed=2, bubble_fill=fill)
        lhs = plan.stage_busy.sum(axis=1) + plan.stage_idle.sum(axis=1)
        assert np.allclose(lhs, plan.pp * plan.rank_total)
        assert np.allclose(plan.stage_busy.sum(axis=1), plan.useful)
        assert (plan.stage_idle >= -1e-6).all()


def test_fill_conservation_and_uplift():
    fill = _plan(d=4, per=64, pp=4, m=16, seed=3)
    nofill = _plan(d=4, per=64, pp=4, m=16, seed=3, bubble_fill=False)
    # Identical work on both sides of the comparison.
    assert np.allclose(fill.useful, nofill.useful)
    assert np.allclose(fill.makespan_1f1b, nofill.makespan_1f1b)
    # No-fill runs the whole encoder as prologue+epilogue.
    assert np.allclose(nofill.rank_total, nofill.rank_total_nofill)
    assert nofill.filled.sum() == 0.0
    # Fill can only help, and never places more than the bubble holds.
    assert (fill.rank_total <= nofill.rank_total + 1e-6).all()
    assert fill.filled.sum() <= fill.bubble_total.sum() + 1e-6
    assert 0.0 <= fill.fill_fraction <= 1.0 + 1e-9
    assert fill.mfu_uplift >= 0.0


def test_staged_config_headline_gates():
    """The benchmark's acceptance gates, on the library entrypoint."""
    cfg = _cfg()
    rng = np.random.default_rng(0)
    model = llm_cost_model(cfg)
    d = 4
    dest = [rng.integers(200, 2000, size=64).astype(np.float64)
            for _ in range(d)]
    # Realistic encoder load: per-rank encoder cost from its own f(S).
    enc = {}
    for e in cfg.encoders:
        em = encoder_cost_model(e)
        ls = rng.integers(256, 1500, size=(d, 48)).astype(np.float64)
        enc[e.name] = (em.alpha * ls + em.beta * ls**2).sum(axis=1)
    plan = plan_pipeline(cfg, model, dest, enc, pp=4, n_micro=16)
    assert plan.fill_fraction >= 0.5
    assert plan.mfu_uplift > 0.0
    assert plan.projected_mfu > plan.projected_mfu_nofill
    assert plan.partition == (20, 20, 20, 20)
    d_ = plan.to_dict()
    assert d_["fill_fraction"] == plan.fill_fraction
    assert d_["pp"] == 4 and d_["n_micro"] == 16


def test_plan_pipeline_validation():
    cfg = _cfg()
    model = llm_cost_model(cfg)
    with pytest.raises(ValueError):
        plan_pipeline(cfg, model, [np.ones(4)], {}, pp=1)
    # No encoders: pure 1F1B, zero fill, uplift 0.
    plan = plan_pipeline(cfg, model, [np.full(8, 500.0)], {}, pp=2, n_micro=4)
    assert plan.filled.sum() == 0.0
    assert np.isclose(plan.mfu_uplift, 0.0)
    # n_micro defaults to 2*pp.
    plan = plan_pipeline(cfg, model, [np.full(8, 500.0)], pp=4)
    assert plan.n_micro == 8


# ----------------------------------------------------------------------
# cost units: encoder costs rescaled onto the LLM unit
# ----------------------------------------------------------------------
def test_phase_flops_per_unit():
    cfg = _cfg()
    flops = phase_flops_per_unit(cfg)
    assert set(flops) == {"llm"} | {e.name for e in cfg.encoders}
    assert all(v > 0 for v in flops.values())
    # The 84B backbone dwarfs the encoders per cost unit.
    assert flops["llm"] > flops["vision"]
    assert flops["llm"] > flops["audio"]


# ----------------------------------------------------------------------
# dispatcher: per-stage post-balanced loads
# ----------------------------------------------------------------------
def test_dispatcher_stage_costs():
    cfg = _cfg()
    model = llm_cost_model(cfg)
    frac = np.asarray(stage_partition(cfg.n_layers, 4), np.float64)
    frac /= frac.sum()
    rng = np.random.default_rng(5)
    lengths = [rng.integers(100, 2000, size=32) for _ in range(4)]
    disp = BatchPostBalancingDispatcher(4, model, stage_fractions=frac)
    plan = disp.plan(lengths)
    assert plan.stage_costs.shape == (4, 4)
    # Stage loads decompose the per-rank cost exactly.
    assert np.allclose(plan.stage_costs.sum(axis=0), plan.costs)
    assert np.allclose(plan.stage_costs, np.outer(frac, plan.costs))
    # Without stage_fractions the matrix is empty (pp = 1 runs).
    plain = BatchPostBalancingDispatcher(4, model).plan(lengths)
    assert plain.stage_costs.size == 0


# ----------------------------------------------------------------------
# orchestrator integration (plan-only)
# ----------------------------------------------------------------------
def test_orchestrator_pipeline_mode():
    cfg = _cfg()
    d = 4
    rng = np.random.default_rng(11)
    examples = [sample_examples(rng, 16, TaskMix(), ("vision", "audio"))
                for _ in range(d)]
    orch = MLLMGlobalOrchestrator(cfg, d, pp=4, microbatches=8, vocab=512)
    assert orch.stage_fractions is not None
    plans = orch.plan_phases(examples)
    plan = plans.pipeline
    assert plan is not None and plan.pp == 4 and plan.d == d
    assert plan.n_micro == 8
    assert "pipeline" in plans.phase_solve_ms
    # The LLM dispatcher carries the per-stage decomposition too.
    assert plans.llm_plan.stage_costs.shape == (4, d)
    # pp=1 (default config) keeps the legacy path: no pipeline plan.
    plain = MLLMGlobalOrchestrator(cfg, d, vocab=512).plan_phases(examples)
    assert plain.pipeline is None


def test_orchestrator_staged_config_knobs():
    from repro.configs.mllm_84b import STAGED_CONFIG
    assert STAGED_CONFIG.pp_stages == 4
    assert STAGED_CONFIG.pp_microbatches == 16
    assert STAGED_CONFIG.pp_bubble_fill
    assert _cfg().pp_stages == 1  # default config unchanged
    d = 2
    rng = np.random.default_rng(13)
    examples = [sample_examples(rng, 8, TaskMix(), ("vision",))
                for _ in range(d)]
    # Config knobs flow through when the ctor args are omitted.
    orch = MLLMGlobalOrchestrator(STAGED_CONFIG, d, vocab=512)
    assert orch.pp == 4 and orch.microbatches == 16
    plans = orch.plan_phases(examples)
    assert plans.pipeline is not None and plans.pipeline.n_micro == 16


# ----------------------------------------------------------------------
# mesh + sharding
# ----------------------------------------------------------------------
def test_mesh_pp_validation():
    with pytest.raises(ValueError):
        make_production_mesh(pp=3)  # must divide the 16-wide data axis
    with pytest.raises(ValueError):
        make_production_mesh(pp=0)


def test_mesh_pp_axes_abstract():
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((4, 4, 16), ("pp", "data", "model"))
    assert pp_stages_of(mesh) == 4
    assert dp_shards_of(mesh) == 4  # pp is NOT a DP axis
    flat = AbstractMesh((16, 16), ("data", "model"))
    assert pp_stages_of(flat) == 1
    assert dp_shards_of(flat) == 16


def test_param_specs_pp_shards_layer_dim():
    import jax
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.models.model import init_params
    from repro.sharding.specs import param_specs

    cfg = _cfg().smoke()  # n_layers=2 -> divisible by pp=2
    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    mesh = AbstractMesh((2, 2, 2), ("pp", "data", "model"))
    specs = param_specs(cfg, params_shape, mesh)

    def leaves(tree, stacked=False):
        if isinstance(tree, dict):
            for k, v in tree.items():
                yield from leaves(v, stacked or k in ("layers", "enc_layers"))
        else:
            yield stacked, tree

    saw_pp = False
    for stacked, spec in leaves(specs):
        parts = tuple(spec)
        if stacked and parts and parts[0] == "pp":
            saw_pp = True
        if not stacked:
            assert "pp" not in parts  # only stacked layer dims shard on pp
    assert saw_pp
    # pp=1 mesh: unchanged legacy specs (no pp axis anywhere).
    flat = AbstractMesh((2, 2), ("data", "model"))
    for _, spec in leaves(param_specs(cfg, params_shape, flat)):
        assert "pp" not in tuple(spec)
    assert isinstance(P(), P)  # silence unused-import pedantry


# ----------------------------------------------------------------------
# observability fan-out
# ----------------------------------------------------------------------
def test_waterfall_pipeline_mode_closure():
    # Pure-LLM pipeline (no encoder fill): the 1F1B bubbles are a large,
    # honest gap, so relative closure is a meaningful check -- the
    # near-zero-gap regime is floored by GAP_FLOOR instead.
    plan = _plan(d=4, per=64, pp=4, m=8, seed=4, enc_scale=0.0)
    wf = GapWaterfall(registry=MetricsRegistry())
    crit = float(plan.rank_total.max())
    true_scale = 0.004  # ms per cost unit
    rng = np.random.default_rng(6)
    last = None
    for step in range(12):
        step_ms = crit * true_scale * (1.0 + rng.normal(0, 0.005)) + 2.0
        last = wf.observe(step, step_ms=step_ms, exposed_ms=2.0,
                          pipeline=plan)
    comps = last.components
    assert last.gap > 0.2  # bubbles dominate: the gap is real
    for k in range(plan.pp):
        assert f"pipeline_bubble_s{k}" in comps
        assert comps[f"pipeline_bubble_s{k}"] >= -1e-9
    assert "imbalance_llm" in comps and comps["imbalance_llm"] >= -1e-9
    # Out-of-sample closure: the named components explain the gap.
    assert wf.closure()["max_closure_err"] <= 0.05
    # The plan rides along on the report automatically.
    rep = type("R", (), {"phase_costs": {}, "exposed_ms": 0.0,
                         "pipeline": plan})()
    w2 = GapWaterfall(registry=MetricsRegistry())
    out = w2.observe(0, report=rep, step_ms=crit * true_scale)
    assert "pipeline_bubble_s0" in out.components


def test_ledger_record_pipeline():
    plan = _plan(d=2, per=32, pp=4, m=8, seed=8)
    ledger = StepLedger(d=2, registry=MetricsRegistry())
    ledger.record_pipeline(0, plan)
    ledger.record_pipeline(1, plan)
    for s in range(plan.pp):
        series = ledger.series[f"pipeline_bubble_s{s}"]
        assert len(series) == 2
        assert 0.0 <= series[0][1] <= 1.0
    assert ledger.series["pipeline_fill_fraction"][0][1] == pytest.approx(
        plan.fill_fraction)
    assert ledger.series["pipeline_mfu_uplift"][0][1] == pytest.approx(
        plan.mfu_uplift)
