"""phases.py: device time by step phase and chip, program spans and the
idle gaps they name, and the per-step readings -- on hand-made events,
and on the small trace recorded on a TPU v5 lite that test_trace.py
reads."""
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
import run  # noqa: E402

phases = run.load_module(HERE / "phases.py")
trace = run.load_module(HERE / "trace.py")
RECORDED = HERE / "testdata" / "small.xplane.pb"

EVENTS = {
    "device": {
        "/device:TPU:0": [
            ("fusion.1", 0.0, 1.0, False), ("while.2", 1.0, 3.0, False),
            ("fusion.3", 1.5, 2.5, False), ("all-reduce.4", 3.0, 3.5, False),
            ("copy.5", 4.0, 4.5, False)],
        "/device:TPU:1": [
            ("fusion.1", 0.0, 1.0, False), ("while.2", 1.0, 2.0, False),
            ("fusion.3", 1.2, 1.8, False), ("all-reduce.4", 2.0, 3.5, False),
            ("copy.5", 4.0, 4.5, False)],
    },
    "host": [("dispatch", 0.0, 0.1), ("wait_step", 3.5, 5.0)],
}
OP_PHASE = {"fusion.1": "encoder.vision", "while.2": "llm", "fusion.3": "llm",
            "all-reduce.4": "llm"}
PROGRAM = [("loader.pack", 3.4, 4.2), ("dispatch.solve.llm", 3.6, 3.9),
           ("loader.next", 4.6, 4.9), ("loader.sample", 10.0, 11.0)]


def test_phase_time_per_chip_keeps_collectives_apart():
    r = phases.reduce_program(EVENTS, 2, OP_PHASE, PROGRAM)
    assert r["phase_s"] == {
        "encoder.vision": pytest.approx([1.0, 1.0]),
        "llm": pytest.approx([2.0, 1.0]),
        "llm/collective": pytest.approx([0.5, 1.5]),
        "unscoped": pytest.approx([0.5, 0.5]),
    }
    # The self times of each chip add up to its busy time.
    for chip, plane in enumerate(sorted(EVENTS["device"])):
        busy = trace.union_length(o[1:3] for o in EVENTS["device"][plane])
        assert sum(v[chip] for v in r["phase_s"].values()) == pytest.approx(busy)


def test_program_spans_are_clipped_to_the_window():
    r = phases.reduce_program(EVENTS, 2, OP_PHASE, PROGRAM)
    assert r["program_spans"] == pytest.approx(
        {"loader.pack": 0.8, "dispatch.solve.llm": 0.3, "loader.next": 0.3})


def test_idle_gaps_named_by_the_program_spans_over_their_middle():
    r = phases.reduce_program(EVENTS, 2, OP_PHASE, PROGRAM)
    base = trace.reduce_events(EVENTS, chips=2)["breakdown"]["idle_gaps"]
    assert [t for _, t in r["idle_gaps_program"]] == pytest.approx(
        [t for _, t in base])
    assert r["idle_gaps_program"] == [
        ["loader.pack+dispatch.solve.llm", pytest.approx(0.5)],
        ["loader.next", pytest.approx(0.5)]]


def test_per_step_readings():
    r = phases.reduce_program(EVENTS, 2, OP_PHASE, PROGRAM)
    out = phases.per_step(
        r, 2,
        [{"llm": (30, 100), "vision": (10, 40)}, {"llm": (50, 100), "vision": (0, 40)}],
        [{"llm": 2}, {}, {"llm": 1, "vision.exchange": 1}])
    assert out["phase_ms"]["llm"] == pytest.approx(1e3 * 1.5 / 2)
    assert out["rank_spread.llm"] == pytest.approx(100 * (2.0 / 1.5 - 1))
    assert out["unscoped_share"] == pytest.approx(100 * 1.0 / 8.0)
    assert out["exchange_ms"] == 0
    assert out["pad_frac"] == pytest.approx({"llm": 60.0, "vision": 87.5})
    assert out["resamples"] == {"llm": 3, "vision.exchange": 1}


def test_recorded_trace_without_phases_is_all_unscoped():
    if not RECORDED.is_file():
        pytest.fail(f"{RECORDED} is missing: run record_trace.py on a TPU")
    spans = ("dispatch", "wait_step", "loader_wait")
    ev = trace.load_events(RECORDED, set(spans))
    base = trace.reduce_events(ev, chips=1)
    r = phases.reduce_program(ev, 1, {}, phases.load_program_spans(RECORDED))
    assert set(r["phase_s"]) == {"unscoped"}
    assert r["phase_s"]["unscoped"][0] == pytest.approx(base["busy_s"])
    assert r["program_spans"] == {}
    assert [t for _, t in r["idle_gaps_program"]] == pytest.approx(
        [t for _, t in base["breakdown"]["idle_gaps"]])
    assert {n for n, _ in r["idle_gaps_program"]} == {"no program span"}


def test_capture_takes_the_compiled_step_text_from_the_runner():
    """The wrapped runner hands its loader's reports and its record on,
    and the step is compiled from the first batch's shapes, on one
    device (the runner's batch placement is then a device) as on a mesh."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    def step(params, opt_state, batch):
        return params * 2, opt_state, {"loss": (batch["x"] * params).sum()}

    reports = [types.SimpleNamespace(stream_tokens={"llm": (1, 2)}, resamples={})] * 3

    def build(*args, **kwargs):
        items = iter([({"x": np.ones(4, np.float32)}, r, 0.0) for r in reports])
        loader = type("L", (), {"__next__": lambda self: next(items),
                                "close": lambda self: None})()
        return types.SimpleNamespace(
            loader=loader, params=jnp.ones(4), opt_state=jnp.zeros(4), mesh=None,
            batch_sharding=jax.devices()[0], step=jax.jit(step))

    def run_cell():
        prog = runner.build()
        for _ in range(3):
            next(prog.loader)
        prog.loader.close()
        return {"steps": [{}, {}]}

    runner = types.SimpleNamespace(build=build, run=run_cell)
    capture = phases._Capture()
    capture.wrap(runner)
    rec = runner.run()
    assert capture.rec is rec and capture.reports == reports
    text = capture.hlo_text()
    assert "f32[4]" in text and "ENTRY" in text
