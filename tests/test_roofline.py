"""Roofline extraction tests: HLO collective parsing + term analysis +
the named hardware presets."""
import jax
import pytest

from repro.launch.roofline import (HW, HW_PRESETS, analyze, collective_bytes,
                                   device_hw, get_hw)

HLO_SAMPLE = """
HloModule jit_step

fused_computation {
  ...
}

ENTRY main {
  %p0 = bf16[16,4096]{1,0} parameter(0)
  %ag = bf16[256,4096]{1,0} all-gather(bf16[16,4096]{1,0} %p0), dimensions={0}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%add
  %rs = bf16[8,128]{1,0} reduce-scatter(bf16[128,128]{1,0} %y), dimensions={0}
  %a2a = bf16[32,64]{1,0} all-to-all(bf16[32,64]{1,0} %z), dimensions={0}
  %cp = f32[4,4]{1,0} collective-permute(f32[4,4]{1,0} %w), source_target_pairs={{0,1}}
  ROOT %r = (bf16[2,2]{1,0}) tuple(%q)
}
"""


def test_collective_bytes_parses_each_kind():
    b = collective_bytes(HLO_SAMPLE)
    assert b["all-gather"] == 256 * 4096 * 2
    assert b["all-reduce"] == 1024 * 4
    assert b["reduce-scatter"] == 8 * 128 * 2
    assert b["all-to-all"] == 32 * 64 * 2
    assert b["collective-permute"] == 4 * 4 * 4
    assert b["total"] == sum(
        b[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute", "ragged-all-to-all")
    )


def test_collective_bytes_ragged_not_double_counted():
    txt = "%r = bf16[64,8]{1,0} ragged-all-to-all(bf16[64,8]{1,0} %x, s32[4]{0} %o)"
    b = collective_bytes(txt)
    assert b["ragged-all-to-all"] == 64 * 8 * 2
    assert b["all-to-all"] == 0


def test_collective_bytes_ignores_plain_ops():
    txt = "%d = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b)"
    assert collective_bytes(txt)["total"] == 0


def test_analyze_terms_and_dominance():
    hw = HW(peak_flops=100.0, hbm_bw=10.0, ici_bw=1.0, name="test", chips=2)
    rep = analyze(
        arch="x", shape="y", mesh_name="m",
        cost={"flops": 1000.0, "bytes accessed": 50.0},
        hlo_text="%ar = f32[25]{0} all-reduce(f32[25]{0} %x)",
        memory={}, model_flops_global=800.0, hw=hw,
    )
    assert rep.compute_s == pytest.approx(10.0)
    assert rep.memory_s == pytest.approx(5.0)
    assert rep.collective_s == pytest.approx(100.0)
    assert rep.dominant == "collective"
    assert rep.useful_ratio == pytest.approx(800.0 / 2000.0)


def test_analyze_zero_flops_safe():
    rep = analyze(arch="x", shape="y", mesh_name="m",
                  cost={"flops": 0.0, "bytes accessed": 0.0}, hlo_text="",
                  memory={}, model_flops_global=1.0,
                  hw=get_hw("TPU v5 lite"))
    assert rep.useful_ratio == 0.0


def test_get_hw_presets():
    for kind, hw in HW_PRESETS.items():
        got = get_hw(kind)
        assert got.name == kind and got.peak_flops == hw.peak_flops
    # chips override rides along without mutating the preset.
    assert get_hw("TPU v4", chips=64).chips == 64
    assert get_hw("TPU v4").chips == HW_PRESETS["TPU v4"].chips


def test_get_hw_env_and_errors(monkeypatch):
    # The table is keyed by device_kind alone: no environment variable
    # or short name selects a preset, and an unknown kind is an error.
    monkeypatch.setenv("REPRO_HW", "TPU v4")
    v5e = get_hw("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    for bad in ("tpu9000", "v5e", "cpu"):
        with pytest.raises(ValueError):
            get_hw(bad)


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True), ("TPU v7x", False),
                                     ("cpu", False)])
def test_device_hw_resolves_device_kind(kind, ok):
    class FakeDevice:
        device_kind = kind

    if ok:
        hw = device_hw(FakeDevice())
        assert hw.name == kind and hw.peak_flops == 197e12
        assert hw.chips == len(jax.devices())
    else:
        with pytest.raises(ValueError, match="no peak table entry"):
            device_hw(FakeDevice())
