"""Training cells: the program's own pieces, composed as
``launch/train.train`` composes them, driven for a time window.

Set-up builds one object -- the orchestrator (post-balancing on, its
defaults), capacities from a probe batch with the mix's margin, the
prefetching loader with plan-ahead fed by the cell's traffic, the DP
sharding, the jitted and donating train step -- and drives it through
its first ``CHECK_STEPS`` steps, which are the correctness readings.
The window then runs further steps back to back on the same object,
each step read back only after the next one is dispatched, until
``seconds`` have passed; it ends when the last dispatched step is
complete.  Once it has closed and the program's state is freed, the
plain reference replays the checked steps and the readings are
compared with it.
"""
from __future__ import annotations

import contextlib
import json
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic as traffic_mod

CHECK_STEPS = 3
# The runner's host spans, which name the idle gaps of a traced window.
HOST_SPANS = ("loader_wait", "device_put", "dispatch", "wait_step")


def program_config(model: dict):
    from repro.configs.base import EncoderConfig, ModelConfig

    fields = {k: v for k, v in model.items() if k != "encoders"}
    return ModelConfig(**fields, encoders=tuple(
        EncoderConfig(**e) for e in model["encoders"]))


def to_example(s):
    from repro.data.synthetic import Example

    return Example(s.task, s.text, s.vision, s.audio, s.order)


def to_size(ex) -> traffic_mod.ExampleSize:
    return traffic_mod.ExampleSize(ex.task, ex.text_len, ex.vision_meta,
                                   ex.audio_meta, tuple(ex.order))


class Recorder:
    """Wraps the orchestrator instance's ``plan_and_pack``: the batches it
    packed, in order, each with the capacity overflows (resamples) that
    came before it."""

    def __init__(self, orch):
        self.packed: list[tuple[list, int]] = []
        self._overflows = 0
        inner = orch.plan_and_pack

        def plan_and_pack(examples, *args, **kwargs):
            try:
                out = inner(examples, *args, **kwargs)
            except ValueError:
                self._overflows += 1
                raise
            flat = [to_size(ex) for insts in examples for ex in insts]
            self.packed.append((flat, self._overflows))
            self._overflows = 0
            return out

        orch.plan_and_pack = plan_and_pack


@dataclasses.dataclass
class Program:
    cfg: object
    mesh: object
    loader: object
    recorder: Recorder
    step: object
    params: object
    opt_state: object
    batch_sharding: object


def build(config: dict, mix: dict, seed: int, chips: int, *,
          make_train_step=None, reference=None) -> Program:
    """The program under test, made from the configuration, the mix and
    the seed.  ``make_train_step`` replaces the program's step factory
    (the fault tests plant faults there)."""
    from repro.core.orchestrator import MLLMGlobalOrchestrator
    from repro.data.pipeline import PrefetchingLoader
    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import opt_state_specs, param_specs, to_shardings
    from repro.training.optimizer import AdamWConfig, adamw_init
    from repro.training.train_step import make_train_step as program_step

    cfg = program_config(config["model"])
    d, per = mix["instances"], mix["examples_per_instance"]
    orch = MLLMGlobalOrchestrator(cfg, d, vocab=cfg.vocab_size)
    recorder = Recorder(orch)
    probe = [[to_example(s) for s in insts] for insts in traffic_mod.probe_sizes(mix)]
    caps = orch.default_capacities(probe, margin=mix["capacity_margin"])
    recorder.packed.clear()
    sampler = traffic_mod.BatchSampler(mix, seed, to_example)
    loader = PrefetchingLoader(orch, caps, examples_per_instance=per, seed=seed,
                               sampler=sampler, plan_ahead=True)

    model = config["model"]
    dtype = jnp.dtype(model["dtype"])
    params = reference.init_params(model, seed, dtype)
    opt_state = jax.jit(adamw_init)(params)
    mesh = None
    if chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((chips, 1), ("data", "model"))
        p_specs = param_specs(cfg, params, mesh)
        params, opt_state = jax.device_put(
            (params, opt_state), to_shardings((p_specs, opt_state_specs(p_specs)), mesh))
        batch_sharding = NamedSharding(mesh, P(("data",)))
    else:
        batch_sharding = jax.devices()[0]
        params, opt_state = jax.device_put((params, opt_state), batch_sharding)
    factory = make_train_step or program_step
    step_fn = factory(cfg, AdamWConfig(**config["optimizer"]), mesh=mesh,
                      dp_axes=("data",))
    step = jax.jit(step_fn, donate_argnums=(0, 1))
    return Program(cfg, mesh, loader, recorder, step, params, opt_state,
                   batch_sharding)


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(lambda a: jnp.linalg.norm(a.astype(jnp.float32).ravel()),
                                  tree)


def _named(tree) -> dict[str, float]:
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def run(cell, seed: int, seconds: float, trace_dir, t0: float, reference,
        counter) -> dict:
    """One run of a training cell.  Returns the record the metric
    readers and the correctness check read; ``counter`` is the
    configuration's FLOPs counter (``flops/<name>.py``)."""
    config, mix, model = cell.config, cell.mix, cell.config["model"]
    prog = build(config, mix, seed, cell.chips, reference=reference)
    with (jax.set_mesh(prog.mesh) if prog.mesh is not None
          else contextlib.nullcontext()):
        try:
            readings = _check_steps(prog, model, seed, reference,
                                    config["optimizer"]["b1"])
            rec = _window(prog, model, seconds, trace_dir, t0, counter)
        finally:
            prog.loader.close()
    rec.update(readings)
    resamples = sum(s["resamples"] for s in rec["steps"])
    rec.update(attempted=len(rec["steps"]) + resamples, failed=resamples,
               correct_outputs=True)
    rec["earlier"] = {
        "window steps": len(rec["steps"]),
        "mean solve_ms / exposed_ms": (
            float(np.mean([s["solve_ms"] for s in rec["steps"]] or [0])),
            float(np.mean([s["exposed_ms"] for s in rec["steps"]] or [0]))),
        "losses of the checked steps": readings["losses"]}
    rec["memory_peak_bytes"] = max(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for dev in jax.local_devices())
    checked = [flat for flat, _ in prog.recorder.packed[:CHECK_STEPS]]
    del prog
    for a in jax.live_arrays():
        a.delete()
    rec["checks"] = compare(rec, checked, model, config["optimizer"], seed,
                            reference, cell.limits)
    return rec


def _check_steps(prog: Program, model: dict, seed: int, reference, b1: float) -> dict:
    """The first steps, one at a time: each step's loss, the first
    gradient per leaf as AdamW holds it after step 1 (mu / (1 - b1)),
    and each leaf's change after the last checked step."""
    losses = []
    first_grad = None
    for k in range(CHECK_STEPS):
        batch_np, _, _ = next(prog.loader)
        batch = jax.device_put(batch_np, prog.batch_sharding)
        prog.params, prog.opt_state, m = prog.step(prog.params, prog.opt_state, batch)
        losses.append(float(m["loss"]))
        if k == 0:
            first_grad = {n: v / (1 - b1) for n, v in
                          _named(_norms(prog.opt_state["mu"])).items()}
    init = reference.init_params(model, seed, prog.params["embed"].dtype)
    delta = _named(_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), prog.params, init)))
    del init
    return {"losses": losses, "grad_norms": first_grad, "delta_norms": delta}


def _window(prog: Program, model: dict, seconds: float, trace_dir, t0: float,
            counter) -> dict:
    from repro.utils import CompileWatch

    steps = []
    pending = None  # (metrics, record) of the step in flight
    profiler = (jax.profiler.trace(str(trace_dir)) if trace_dir is not None
                else contextlib.nullcontext())
    with CompileWatch() as watch, profiler:
        t_start = time.perf_counter()
        setup_s = t_start - t0
        while True:
            now = time.perf_counter()
            if now - t_start >= seconds:
                break
            with jax.profiler.TraceAnnotation("loader_wait"):
                tw = time.perf_counter()
                batch_np, report, _ = next(prog.loader)
                wait_ms = (time.perf_counter() - tw) * 1e3
            with jax.profiler.TraceAnnotation("device_put"):
                batch = jax.device_put(batch_np, prog.batch_sharding)
            with jax.profiler.TraceAnnotation("dispatch"):
                prog.params, prog.opt_state, m = prog.step(
                    prog.params, prog.opt_state, batch)
            seg = batch_np["llm_seg"]
            rec = {"loader_wait_ms": wait_ms, "llm_real": int((seg > 0).sum()),
                   "llm_slots": int(seg.size), "solve_ms": report.solve_ms,
                   "exposed_ms": report.exposed_ms}
            if pending is not None:
                _settle(pending, steps)
            pending = (m, rec)
        if pending is not None:
            _settle(pending, steps)
        t_end = time.perf_counter()
    window = prog.recorder.packed[CHECK_STEPS:CHECK_STEPS + len(steps)]
    for s, (flat, overflows) in zip(steps, window):
        s["tokens"] = sum(counter.llm_tokens(ex, model) for ex in flat)
        s["flops"] = counter.train_flops(flat, model)
        s["examples"] = flat
        s["resamples"] = overflows
    return {"kind": "train", "steps": steps, "window_s": t_end - t_start,
            "setup_s": setup_s, "compiles_in_window": watch.lowered}


def _settle(pending, steps):
    m, rec = pending
    with jax.profiler.TraceAnnotation("wait_step"):
        jax.block_until_ready(m)
    rec["loss"] = float(m["loss"])
    steps.append(rec)


# ----------------------------------------------------------------------
# The comparison that decides ``correct``.
# ----------------------------------------------------------------------
def reference_readings(checked, model, opt, seed, reference, quant=None) -> dict:
    """The reference's readings over the checked steps: loss per step,
    the first clipped gradient per leaf, the change per leaf after the
    last step, and the first raw gradient per leaf (which leaves the
    change is judged on)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(reference.init_params(model, seed))
        state = {"t": 0, "mu": None, "nu": None}
        losses, first_grad, raw = [], None, None
        for k, flat in enumerate(checked):
            loss, grads, _ = reference.batch_grads(params, flat, model, quant)
            losses.append(loss)
            params, state, scale = reference.adam_step(params, grads, state, opt,
                                                       jnp.dtype(model["dtype"]))
            if k == 0:
                raw = reference.leaf_norms(grads)
                first_grad = {n: v * scale for n, v in raw.items()}
            del grads
        delta = reference.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, params, _f32(reference.init_params(model, seed))))
    return {"losses": losses, "grad_norms": first_grad, "delta_norms": delta,
            "raw_grad_norms": raw}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the worst relative loss gap over the
    checked steps; per leaf, the gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's, worst leaf, for the first gradient and for the
    change.  Leaves whose first reference gradient is under a
    thousandth of the median leaf's are left out of the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))

    def worst(got: dict, want: dict, names) -> tuple[float, str]:
        med = float(np.median([want[n] for n in names]))
        return max((abs(got[n] - want[n]) / max(want[n], med), n) for n in names)

    names = sorted(ref["grad_norms"])
    g, g_leaf = worst(prog["grad_norms"], ref["grad_norms"], names)
    med_raw = float(np.median(list(ref["raw_grad_norms"].values())))
    moved = [n for n in names if ref["raw_grad_norms"][n] >= 1e-3 * med_raw]
    c, c_leaf = worst(prog["delta_norms"], ref["delta_norms"], moved)
    return {"loss": loss, "grad": g, "grad_leaf": g_leaf, "change": c,
            "change_leaf": c_leaf, "left_out": sorted(set(names) - set(moved))}


def compare(readings, checked, model, opt, seed, reference, limits) -> list:
    """The checks: each number with its limit where the cell's limits
    file gives one; a number without a limit is printed, not judged."""
    ref = reference_readings(checked, model, opt, seed, reference)
    got = gaps(readings, ref)
    return [{"name": k, "value": got[k], **({"limit": limits[k]} if k in limits else {})}
            for k in ("loss", "grad", "change")] + [
        {"name": "compiles_in_window", "value": readings["compiles_in_window"],
         "limit": 0},
        {"name": "worst_grad_leaf", "note": got["grad_leaf"]},
        {"name": "worst_change_leaf", "note": got["change_leaf"]},
        {"name": "left_out", "note": got["left_out"]}]


def calibrate(cell, seeds, control_seeds, reference, make_train_step=None) -> list[dict]:
    """The readings the limits are set from: for each seed, the sound
    program's gaps to the reference over the checked steps and, for the
    control seeds, the fp8 control's.  With ``make_train_step`` (a fault
    of ``faults.py``) the program read is the faulty one."""
    config, mix, model = cell.config, cell.mix, cell.config["model"]
    opt = config["optimizer"]
    out = []
    for seed in seeds:
        prog = build(config, mix, seed, cell.chips, reference=reference,
                     make_train_step=make_train_step)
        with (jax.set_mesh(prog.mesh) if prog.mesh is not None
              else contextlib.nullcontext()):
            try:
                readings = _check_steps(prog, model, seed, reference, opt["b1"])
            finally:
                prog.loader.close()
        checked = [flat for flat, _ in prog.recorder.packed[:CHECK_STEPS]]
        del prog
        for a in jax.live_arrays():
            a.delete()
        want = reference_readings(checked, model, opt, seed, reference)
        row = {"seed": seed, "program": gaps(readings, want)}
        if seed in control_seeds:
            row["control"] = gaps(reference_readings(checked, model, opt, seed,
                                                     reference, "fp8"), want)
        out.append(row)
        print(json.dumps(row, default=float), flush=True)
    return out
