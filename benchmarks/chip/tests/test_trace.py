"""trace.py: the reduction from a profiler trace to busy time, kernel and
collective time and the breakdown -- on hand-made events, and on a small
trace recorded on a TPU v5 lite (``testdata/small.xplane.pb``, made by
``record_trace.py``)."""
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
import run  # noqa: E402

trace = run.load_module(HERE / "trace.py")
RECORDED = HERE / "testdata" / "small.xplane.pb"


def test_union_and_gaps():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace._gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_op_name_is_the_hlo_name():
    text = ('%transpose_jvp_jit_flash_attention_op___.3 = (bf16[4,1024,128]) '
            'custom-call(s32[16] %reshape.87), custom_call_target="tpu_custom_call"')
    assert trace.op_name(text) == "transpose_jvp_jit_flash_attention_op___.3"
    assert trace.op_name("dispatch") == "dispatch"


def test_self_time_of_nested_events():
    ops = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 4.0), ("flash.3", 5.0, 7.0),
           ("copy.4", 5.5, 6.0), ("fusion.5", 11.0, 12.0)]
    t = trace.self_times(ops)
    assert t == pytest.approx({"while.1": 5.0, "fusion.2": 3.0, "flash.3": 1.5,
                               "copy.4": 0.5, "fusion.5": 1.0})
    assert sum(t.values()) == pytest.approx(trace.union_length(o[1:] for o in ops))


def test_reduce_hand_made_events():
    ev = {
        "device": {"/device:TPU:0": [
            ("fusion.1", 0.0, 1.0, False), ("flash_fwd", 1.0, 1.5, True),
            ("all-reduce.3", 1.2, 2.0, False), ("convolution.7", 3.0, 4.0, False)]},
        "host": [("dispatch", 0.0, 0.1), ("loader_wait", 2.0, 3.0),
                 ("wait_step", 3.0, 4.0)],
    }
    r = trace.reduce_events(ev, chips=1)
    assert r["window_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["kernels"] == {"flash_fwd": pytest.approx(0.5)}
    assert r["collective_s"] == pytest.approx(0.8)
    assert r["collective_exposed_s"] == pytest.approx(0.5)
    assert r["breakdown"]["idle_gaps"] == [["loader_wait", pytest.approx(1.0)]]
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names[0] in ("fusion.1", "convolution.7") and "flash_fwd" in names


def test_reduce_recorded_trace():
    if not RECORDED.is_file():
        pytest.fail(f"{RECORDED} is missing: run record_trace.py on a TPU")
    spans = ("dispatch", "wait_step", "loader_wait")
    ops = trace.load_events(RECORDED, set(spans))["device"]["/device:TPU:0"]
    assert {p for *_, p in ops} == {True, False}
    r = trace.reduce(RECORDED.parent, spans)
    assert 0 < r["busy_s"] <= r["window_s"]
    kernels = r["kernels"]
    assert kernels and all("flash_attention_op" in name for name in kernels)
    assert 0 < sum(kernels.values()) < r["busy_s"]
    # The flash reader picks these operations by its own pattern.
    flash = run.load_module(HERE / "metrics" / "flash_roofline.py")
    assert all(flash.PATTERN.search(name) for name in kernels)
    assert r["collective_s"] == 0
    top = r["breakdown"]["device_ops"]
    assert len(top) <= 10 and all(t > 0 for _, t in top)
    assert sum(t for _, t in top) <= r["busy_s"] * (1 + 1e-6)
    assert {name for name, _ in r["breakdown"]["idle_gaps"]} <= {
        "dispatch", "wait_step", "loader_wait", "no host span"}
