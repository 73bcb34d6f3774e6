"""The comparison that decides ``correct``, at a size a CPU test can hold.

The cells' own sizes are read on the chip (see PERF.md); here the same
code runs the program's timed path at small widths, and shows:

* a sound program reads well inside each cell's limits;
* the lower-precision control (the reference rounded to fp8) reads
  several times what the program reads;
* the harness, driven with the timed path broken underneath, comes out
  not correct: a training step that returns its state unchanged; a step
  that leaves out half the batch and takes the mean over the rest.
"""
import functools
import json
import sys
import time
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import faults  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 4242
# The cell's limits are set from chip readings at its own widths; at
# these widths bf16 rounding weighs more.  Readings here (CPU): sound
# program grad 0.0021, change 0.00075; fp8 control grad 0.033, change
# 0.0040; half the batch left out grad 0.45, change 0.21; state left
# unchanged 1 and 1.
SMALL_TRAIN_LIMITS = {"grad": 0.01, "change": 0.01}
TRAIN_BUCKETS = dict(llm_buckets=(128, 256, 512),
                     enc_buckets={"vision": (64, 128), "audio": (96,)})


def small_train_cell():
    config = json.loads((HERE / "configs/mllm_10b_cut.json").read_text())
    config["model"].update(d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
                           vocab_size=512, block_q=64, block_kv=64)
    config["model"]["encoders"] = [
        dict(name="vision", n_layers=2, d_model=128, n_heads=2, d_ff=256, embed_dim=64,
             downsample=1, padded=False, conv_attention=False, tokens_per_example_max=128),
        dict(name="audio", n_layers=2, d_model=128, n_heads=2, d_ff=256, embed_dim=64,
             downsample=2, padded=True, conv_attention=True, tokens_per_example_max=95)]
    mix = json.loads((HERE / "traffic/omni.json").read_text())
    mix["tasks"] = [
        {"name": "asr", "weight": .3, "order": ["audio", "text"],
         "audio": {"lognormal": [40, .5], "clip": [5, 95]},
         "text": {"of": "audio", "normal": [.25, .04], "min": 4}},
        {"name": "caption", "weight": .3, "order": ["vision", "text"],
         "vision": {"choice": [16, 32, 64]}, "text": {"lognormal": [20, .7], "clip": [4, 60]}},
        {"name": "text", "weight": .2, "order": ["text"],
         "text": {"lognormal": [40, .8], "clip": [4, 200]}},
        {"name": "doc", "weight": .2, "order": ["text", "vision", "text"],
         "vision": {"integers": [1, 3], "times": 32},
         "text": {"lognormal": [30, .6], "clip": [8, 100]}}]
    return types.SimpleNamespace(name="small_train", chips=1, config=config, mix=mix,
                                 limits=SMALL_TRAIN_LIMITS)


@pytest.fixture(scope="module")
def train_mods():
    runner = run.load_module(HERE / "runners/train.py")
    ref = run.load_module(HERE / "references/mllm.py")
    counter = run.load_module(HERE / "flops/mllm.py")
    small = types.SimpleNamespace(**{k: getattr(ref, k) for k in dir(ref)
                                     if not k.startswith("__")})
    small.batch_grads = functools.partial(ref.batch_grads, **TRAIN_BUCKETS)
    return runner, small, counter


def _drive(runner, ref, counter, cell, make_train_step=None):
    """The training run's readings and checks, the device check skipped."""
    orig = runner.build
    if make_train_step is not None:
        runner.build = functools.partial(orig, make_train_step=make_train_step)
    try:
        rec = runner.run(cell, SEED, 0.5, None, time.perf_counter(), ref, counter)
    finally:
        runner.build = orig
    return rec


@pytest.fixture(scope="module")
def sound(train_mods):
    return _drive(*train_mods, small_train_cell())


def _values(rec):
    return {c["name"]: c["value"] for c in rec["checks"]
            if c["name"] in ("loss", "grad", "change")}


def test_sound_program_is_correct(sound):
    assert run.verdict(sound["checks"]), sound["checks"]
    assert sound["compiles_in_window"] == 0
    lim = small_train_cell().limits
    for k, v in _values(sound).items():
        if k in lim:
            assert v < lim[k] / 3, (k, v, lim[k])


def test_fp8_control_reads_far_above_the_program(sound, train_mods):
    runner, ref, _ = train_mods
    cell = small_train_cell()
    checked = [f for f, _ in _packed(runner, ref, cell)]
    model, opt = cell.config["model"], cell.config["optimizer"]
    want = runner.reference_readings(checked, model, opt, SEED, ref)
    ctrl = runner.gaps(runner.reference_readings(checked, model, opt, SEED, ref, "fp8"), want)
    got = _values(sound)
    assert any(ctrl[k] >= 3 * got[k] for k in ("loss", "grad", "change")), (ctrl, got)


def _packed(runner, ref, cell):
    prog = runner.build(cell.config, cell.mix, SEED, 1, reference=ref)
    try:
        for _ in range(runner.CHECK_STEPS):
            next(prog.loader)
    finally:
        prog.loader.close()
    return prog.recorder.packed[:runner.CHECK_STEPS]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_training_fault_is_not_correct(train_mods, fault):
    rec = _drive(*train_mods, small_train_cell(), make_train_step=faults.FAULTS[fault])
    assert not run.verdict(rec["checks"]), rec["checks"]
