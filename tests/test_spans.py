"""Spans, step phases and pack counters (``repro.obs.spans``).

* Every phase of a tiny MLLM train step, built by the program's own
  ``make_train_step``, is read back from the compiled HLO text; the
  scopes change nothing but the metadata.
* The packer's per-stream counts, the typed capacity overflow and the
  loader's resamples by stream.
* The program's host spans land on the profiler's host plane with the
  batch index that joins the worker's spans to the consumer's.
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.orchestrator import CapacityOverflow, MLLMGlobalOrchestrator
from repro.data.packing import pack_padded_stream, pack_stream
from repro.data.pipeline import PrefetchingLoader
from repro.data.synthetic import Example
from repro.obs.registry import MetricsRegistry
from repro.obs.spans import PHASES, op_phases, phase, span
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step

CFG = get_config("mllm_10b").smoke()
D = 2


def _examples(text=24):
    return [[Example("vqa", text, 32, 0, ("vision", "text")),
             Example("asr", 10, 0, 40, ("audio", "text"))],
            [Example("doc", 16, 16, 30, ("vision", "audio", "text")),
             Example("text", 30, 0, 0, ("text",))]]


def _packed(orch=None, caps=None, examples=None):
    orch = orch or MLLMGlobalOrchestrator(CFG, D, vocab=CFG.vocab_size)
    examples = examples or _examples()
    caps = caps or orch.default_capacities(examples, margin=2.0)
    batch, report = orch.plan_and_pack(examples, caps, np.random.default_rng(0))
    return orch, caps, batch, report


def _compiled_text(monkeypatch=None):
    """Compiled HLO text of the tiny step; with ``monkeypatch`` every
    phase scope is replaced by a scope-free context."""
    if monkeypatch is not None:
        import repro.models.model as model_mod
        import repro.training.train_step as step_mod

        for mod in (model_mod, step_mod):
            monkeypatch.setattr(mod, "phase", lambda name: contextlib.nullcontext())
    _, _, batch, _ = _packed()
    params, opt = init_train_state(CFG, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(CFG, AdamWConfig()))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    return step.lower(params, opt, batch).compile().as_text()


@pytest.fixture(scope="module")
def scoped_text():
    return _compiled_text()


def _instructions(text):
    """Instruction lines with their metadata stripped."""
    return [re.sub(r",?\s*metadata=\{[^}]*\}", "", line).rstrip()
            for line in text.splitlines() if " = " in line]


def test_op_phases_reads_every_phase_of_the_compiled_step(scoped_text):
    phases = op_phases(scoped_text)
    assert all(any(re.fullmatch(p, v) for p in PHASES) for v in phases.values())
    for name in ("encoder.vision", "encoder.audio", "llm", "lm_head", "optimizer"):
        assert name in phases.values(), name
    names = re.findall(r'op_name="([^"]*)"', scoped_text)
    for name in ("encoder.vision", "llm"):
        assert any(f"jvp({name})" in n and "transpose(" not in n for n in names)
        assert any(f"transpose(jvp({name}))" in n for n in names)
    # An operation named in the text maps by its instruction name.
    first = next(iter(phases))
    assert re.search(rf"%?{re.escape(first)} = ", scoped_text)


def test_scopes_change_only_the_metadata(scoped_text, monkeypatch):
    bare = _compiled_text(monkeypatch)
    assert not op_phases(bare)
    assert _instructions(bare) == _instructions(scoped_text)


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(encoder.audio)/dot_general", "encoder.audio"),
    ("jit(train_step)/transpose(jvp(llm))/while/body/closed_call/mul", "llm"),
    ("jit(train_step)/optimizer/sub", "optimizer"),
    ("jit(train_step)/jvp(exchange.vision)/gather", "exchange.vision"),
    ("jit(train_step)/jvp(llm)/lm_head/reduce", "llm"),
    ("jit(train_step)/jvp(encoder.video)/conv", "encoder.video"),
    ("jit(train_step)/transpose(jvp(exchange.speech))/all-to-all", "exchange.speech"),
    ("jit(train_step)/jvp(encoders)/mul", None),
    ("jit(train_step)/jvp()/mul", None),
    ("jit(llm_helper)/mul", None),
])
def test_op_phases_on_op_names(op_name, want):
    text = (f'  %fusion.7 = f32[4]{{0}} fusion(%p.1), kind=kLoop, '
            f'metadata={{op_name="{op_name}" source_file="m.py" source_line=3}}\n'
            f'  ROOT %add.2 = f32[4]{{0}} add(%fusion.7, %p.1)\n')
    assert op_phases(text) == ({"fusion.7": want} if want else {})


def test_phase_takes_any_encoder_name():
    """An encoder's phase is named after the encoder, whatever the name."""
    def f(x):
        with phase("encoder.video"):
            y = jnp.sin(x)
        with phase("exchange.video"):
            return jnp.cos(y)

    text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert set(op_phases(text).values()) == {"encoder.video", "exchange.video"}


def test_stream_tokens_count_the_packed_arrays():
    reg = MetricsRegistry()
    orch = MLLMGlobalOrchestrator(CFG, D, vocab=CFG.vocab_size, metrics=reg)
    _, caps, batch, report = _packed(orch)
    st = report.stream_tokens
    assert set(st) == {"llm", "text", "vision", "audio"}
    assert st["llm"] == (int((batch["llm_seg"] > 0).sum()), batch["llm_seg"].size)
    assert st["vision"] == (int((batch["enc_vision_seg"] > 0).sum()),
                            batch["enc_vision_seg"].size)
    examples = [ex for insts in _examples() for ex in insts]
    assert st["llm"][0] == sum(ex.total_len(orch.downsample) for ex in examples)
    assert st["text"][0] == sum(ex.text_len for ex in examples)
    assert st["audio"][0] == sum(ex.audio_meta for ex in examples)
    slots = reg.get("orch_stream_slots")
    for name, (real, total) in st.items():
        got_real = slots.labels(stream=name, kind="real").value
        got_pad = slots.labels(stream=name, kind="pad").value
        assert got_real == real and got_real + got_pad == total
        assert 0 < real <= total


@pytest.mark.parametrize("stream", ["llm", "text", "vision", "audio"])
def test_planted_overflow_names_its_stream(stream):
    orch, caps, _, _ = _packed()
    enc = dict(caps.enc_in)
    tight = {"llm": dict(llm=64), "text": dict(text=16),
             "vision": dict(enc_in={**enc, "vision": 8}),
             "audio": dict(enc_in={**enc, "audio": caps.enc_row["audio"] // 2})}[stream]
    small = dataclasses.replace(caps, **tight)
    with pytest.raises(CapacityOverflow) as err:
        orch.plan_and_pack(_examples(), small, np.random.default_rng(0))
    assert err.value.stream.split(".")[0] == stream
    assert isinstance(err.value, ValueError)


def test_packers_raise_the_typed_overflow():
    with pytest.raises(CapacityOverflow) as err:
        pack_stream([np.array([10, 10])], 12, stream="vision")
    assert err.value.stream == "vision"
    with pytest.raises(CapacityOverflow) as err:
        pack_padded_stream([np.array([9])], 16, 8, stream="audio")
    assert err.value.stream == "audio"


def test_loader_counts_resamples_by_stream():
    reg = MetricsRegistry()
    orch = MLLMGlobalOrchestrator(CFG, D, vocab=CFG.vocab_size, metrics=reg)
    caps = orch.default_capacities(_examples(), margin=2.0)
    calls = {"n": 0}

    def sampler(rng, per):
        # The first draw (one call per instance) overflows the LLM stream.
        calls["n"] += 1
        insts = _examples(text=caps.llm if calls["n"] <= D else 24)
        return insts[(calls["n"] - 1) % D][:per]

    loader = PrefetchingLoader(orch, caps, examples_per_instance=2, seed=3,
                               sampler=sampler, plan_ahead=False)
    try:
        _, report, _ = next(loader)
        _, report2, _ = next(loader)
    finally:
        loader.close()
    assert report.resamples == {"llm": 1}
    assert report2.resamples == {}
    assert reg.get("loader_resamples").labels(stream="llm").value == 1


def _host_spans(trace_dir):
    """[(name, start_ns, {stat: value})] of the program's spans."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if re.match(r"(loader|dispatch|ckpt|engine)\.", ev.name):
                    out.append((ev.name, ev.start_ns, dict(ev.stats)))
    return out


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    orch = MLLMGlobalOrchestrator(CFG, D, vocab=CFG.vocab_size)
    caps = orch.default_capacities(_examples(), margin=2.0)

    def sampler(rng, per):
        return _examples()[int(rng.integers(0, D))][:per]

    with jax.profiler.trace(str(tmp_path / "prof")):
        loader = PrefetchingLoader(orch, caps, examples_per_instance=2, seed=1,
                                   sampler=sampler, plan_ahead=True)
        try:
            for _ in range(3):
                next(loader)
        finally:
            loader.close()
        manager = CheckpointManager(str(tmp_path / "ckpt"))
        manager.save(5, {"w": np.ones(3)})
        manager.restore(5)
        with span("engine.decode", step=9, ignored=None):
            pass
    spans = _host_spans(tmp_path / "prof")
    names = {n for n, _, _ in spans}
    assert {"loader.sample", "loader.plan_wait", "loader.pack", "loader.next",
            "dispatch.solve.llm", "dispatch.solve.vision", "dispatch.solve.audio",
            "dispatch.compose", "ckpt.save", "ckpt.restore",
            "engine.decode"} <= names
    steps = {(n, s.get("step")) for n, _, s in spans}
    for i in range(3):
        # the consumer's wait for batch i and the worker's pack of batch i
        assert ("loader.next", i) in steps and ("loader.pack", i) in steps
        assert ("dispatch.compose", i) in steps
    assert ("ckpt.save", 5) in steps and ("engine.decode", 9) in steps
    pack0 = min(t for n, t, s in spans if n == "loader.pack" and s.get("step") == 0)
    solve0 = min(t for n, t, s in spans
                 if n == "dispatch.solve.llm" and s.get("step") == 0)
    assert solve0 < pack0


def test_trace_out_profiles_the_training_loop(tmp_path):
    """``--trace-out DIR`` runs the loop under the profiler: the wait for
    every step's batch is in the capture, and so is the worker's work on
    the batches it packs while the loop runs (it starts before the loop,
    so the first ones are packed before the capture begins)."""
    from repro.launch.train import parse_args, train

    cfg = get_config("olmo_1b").smoke()
    out = tmp_path / "prof"
    records = train(cfg, parse_args(["--arch", "olmo_1b", "--d", "2", "--per", "2",
                                     "--steps", "4", "--trace-out", str(out)]))
    assert len(records) == 4
    steps = {(n, s.get("step")) for n, _, s in _host_spans(out)}
    assert {("loader.next", i) for i in range(4)} <= steps
    assert {("loader.pack", 3), ("dispatch.solve.llm", 4)} <= steps
