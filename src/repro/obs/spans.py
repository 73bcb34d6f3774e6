"""Host spans and device step phases, on the profiler's clock.

Nothing here records or exports anything.  A span is a
``jax.profiler.TraceAnnotation``: under ``jax.profiler.trace`` it lands
on the host plane of the same ``.xplane.pb`` as the device operations,
so host work and device work share one clock; with no profiler running
it is a TraceMe that costs a few microseconds and keeps nothing.  A
phase is a ``jax.named_scope``: the compiled step keeps it as the
``op_name`` metadata of every operation traced inside it (forward ops
as ``jvp(<phase>)``, their backward as ``transpose(jvp(<phase>))``),
and :func:`op_phases` reads it back from the compiled HLO text.  The
device trace itself names operations only, so that text is how a
device operation is attributed to a phase.

Span names (each span carries ``step=`` the batch index, or the engine
step, so the spans of one step join across threads):

  loader.sample      loader worker: draw the batch's examples
  loader.plan_wait   loader worker: wait for the plan-ahead solve
  loader.pack        loader worker: ``plan_and_pack``
  loader.queue_full  loader worker: blocked handing a batch to the queue
  loader.next        consumer: wait in ``next(loader)``
  dispatch.solve.<phase>, dispatch.compose
                     orchestrator: each dispatcher's solve, composition
  engine.prefill, engine.decode   serving engine, per engine step
  ckpt.save, ckpt.restore         checkpoint manager

Open a profile captured with ``jax.profiler.trace`` (``--trace-out`` of
``repro.launch.train``) in TensorBoard's profile plugin or Perfetto.
"""
from __future__ import annotations

import re

import jax

__all__ = ["PHASES", "op_phases", "phase", "span"]

# The step's phases, as patterns of a scope name: each encoder (with its
# connector) and its exchange to the LLM's shards, named after the
# encoder (``encoder.<name>``, ``exchange.<name>``), the LLM backbone,
# the final norm with the LM head and loss, and the optimizer update.
PHASES = (r"(?:encoder|exchange)\.\w+", "llm", "lm_head", "optimizer")

# A phase as a component of an op_name path, inside any jit(...),
# jvp(...) or transpose(...) wrappers.
_PHASE_RE = re.compile(r"(?:^|[/(])(" + "|".join(PHASES) + r")(?=[)/]|$)")
_INSTR_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bop_name="([^"]*)"')


def span(name: str, **ids):
    """A host span named ``name``; ``ids`` (None dropped) become the
    event's stats in the trace."""
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in ids.items() if v is not None})


def phase(name: str):
    """The named scope of one step phase, a name one of :data:`PHASES`
    matches; any other name is a scope that no phase is read from."""
    return jax.named_scope(name)


def op_phases(hlo_text: str) -> dict[str, str]:
    """{operation name: phase} for every instruction of compiled HLO text
    (``jitted.lower(...).compile().as_text()``) whose ``op_name``
    metadata names a phase; the outermost phase on the path wins.
    Operations under no phase get no entry."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        found = _PHASE_RE.search(m.group(2))
        if found is not None:
            out[m.group(1)] = found.group(1)
    return out
