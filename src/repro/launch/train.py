"""Production training launcher.

Builds the mesh, sharded train state and post-balanced data pipeline for
any registered architecture and runs the training loop.  On the CPU
container this runs reduced configs (``--smoke``); on a real TPU slice
the same entrypoint runs the full configs under the production mesh.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_8b --smoke \
        --steps 20 --d 4

Pipeline mode: ``--pp N`` (N > 1) partitions the LLM backbone into N
stages and plans a 1F1B microbatch schedule with encoder bubble-fill
per step (docs/pipeline.md); the ledger gains per-stage bubble series
and the waterfall switches to its ``pipeline_bubble_s{k}`` components.

Profiling: ``--trace-out DIR`` runs the training loop under
``jax.profiler.trace(DIR)``.  The capture holds the device operations
and the program's host spans (``loader.*``, ``dispatch.*``, ``ckpt.*``;
:mod:`repro.obs.spans`) on one clock, and every device operation of the
step keeps its phase (``encoder.<name>``, ``exchange.<name>``, ``llm``,
``lm_head``, ``optimizer``) in its ``op_name``.  Open it in
TensorBoard's profile plugin or in Perfetto.

Observability: ``--metrics-dir DIR`` turns on the unified metrics plane
(:mod:`repro.obs`): an OpenMetrics textfile (``metrics.prom``,
atomically rewritten every ``--metrics-every`` steps), a crash-safe
JSONL flight recorder (``flight.jsonl``) carrying run metadata and
structured alert events (cost-model drift, checkpoint corruption
fallbacks, MoE drop spikes, stale-plan re-plans).  On top of the
recording plane sits the attribution plane: a
per-step MFU-gap waterfall (:class:`repro.obs.GapWaterfall`, recorded
as ``waterfall`` flight events), online anomaly detection over every
ledger/waterfall series (:class:`repro.obs.AnomalyMonitor`), and an
end-of-run ranked root-cause report (``triage.json`` +
``python -m repro.obs.triage <metrics-dir>``).

``--serve-metrics PORT`` serves the registry live at
``http://127.0.0.1:PORT/metrics`` (OpenMetrics) with the current triage
report at ``/triage`` (JSON); ``--serve-metrics-linger SEC`` keeps the
server up after the loop finishes so scrapers (the nightly CI curl)
can take a final sample.  The bound address is written to
``<metrics-dir>/server.json``.

Fault injection handles (each implies the plane it exercises):
``--inject-drift N`` triples the observed step time from step N on
(fires the CUSUM cost-model-drift alert); ``--inject-straggler N``
inflates shard 0's LLM-phase cost 1.6x from step N on (fires the
``imbalance_llm`` waterfall component and the ``straggler_llm`` triage
root cause); ``--inject-drop-spike N`` reports a 20% MoE drop fraction
from step N on (fires the drop-spike alert and the ``moe_drop``
component).

Fault tolerance: ``--ckpt-dir DIR --ckpt-every N`` snapshots the full
:class:`~repro.checkpoint.TrainState` (params, optimizer state, data
cursor, calibrator state) atomically every N steps with keep-last-K
retention; ``--resume`` restores the newest complete checkpoint (corrupt
ones are flagged and skipped) and continues bit-deterministically.
Resuming with a *different* ``--d`` than the checkpoint's is the elastic
path: the global batch is re-split across the new DP degree and the
Batch Post-Balancing Dispatcher re-solves assignments for the new shard
count -- no divisibility requirement between old and new world sizes.

Programmatic use: :func:`train` runs the loop for a given config and
parsed arguments (``parse_args``) and returns one record per step
(loss, grad norm, device-complete step time, programs compiled in the
step); ``chip_smoke.py`` drives the chip bring-up through it.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (
    CheckpointManager,
    DataCursor,
    TrainState,
    elastic_cursor,
    reshard_pytree,
    restore_train_state,
    save_train_state,
)
from repro.configs import get_config
from repro.core.orchestrator import MLLMGlobalOrchestrator
from repro.data.pipeline import PrefetchingLoader
from repro.data.synthetic import Example
from repro.launch.mesh import make_mesh
from repro.obs import (AlertBridge, AnomalyMonitor, FlightRecorder,
                       GapWaterfall, MetricsRegistry, MetricsServer,
                       StepLedger, render_text,
                       set_registry, triage, write_openmetrics)
from repro.sharding.specs import opt_state_specs, param_specs, to_shardings
from repro.telemetry import AdaptiveOrchestration
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step
from repro.utils import CompileWatch, setup_compile_cache


def _sampler_for(cfg):
    names = [e.name for e in cfg.encoders]

    def sampler(rng, per):
        out = []
        for _ in range(per):
            text = int(rng.integers(16, 128))
            vis = int(rng.integers(1, 4)) * 32 if "vision" in names else 0
            aud = int(rng.integers(16, 64)) if "audio" in names else 0
            if cfg.family == "audio":
                order = ("audio", "text")
            elif vis and aud:
                order = ("vision", "audio", "text")
            elif vis:
                order = ("vision", "text")
            elif aud:
                order = ("audio", "text")
            else:
                order = ("text",)
            out.append(Example("mix", text, vis, aud, order))
        return out

    return sampler


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--d", type=int, default=4, help="DP instances")
    ap.add_argument("--per", type=int, default=4, help="examples/instance")
    ap.add_argument("--pp", type=int, default=None, metavar="STAGES",
                    help="pipeline-parallel stages; >1 plans a 1F1B "
                         "microbatch schedule with encoder bubble fill "
                         "per step (docs/pipeline.md; default: the "
                         "config's pp_stages)")
    ap.add_argument("--microbatches", type=int, default=None, metavar="M",
                    help="microbatches per pipeline iteration (default: "
                         "the config's pp_microbatches, or 2*pp)")
    ap.add_argument("--no-bubble-fill", action="store_true",
                    help="pp > 1 only: schedule encoder microbatches as "
                         "pipeline prologue/epilogue instead of filling "
                         "the 1F1B bubbles (the ablation baseline of "
                         "benchmarks/pipeline_bubbles.py)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0, help="data stream seed")
    ap.add_argument("--mesh", choices=["none", "host"], default="none",
                    help="'host': shard over all local devices on a "
                         "(data, model) mesh")
    ap.add_argument("--adaptive", action="store_true",
                    help="online cost-model calibration: measured step "
                         "times refit the balancing coefficients "
                         "(repro.telemetry)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="profile the training loop into DIR "
                         "(jax.profiler.trace): device operations with "
                         "their step phases and the host spans on one "
                         "clock; open in TensorBoard or Perfetto")
    ap.add_argument("--metrics-dir", default=None,
                    help="enable the obs plane: write metrics.prom, "
                         "flight.jsonl and triage.json here")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="flush the exporters every N steps")
    ap.add_argument("--inject-drift", type=int, default=None, metavar="STEP",
                    help="fault injection: report 3x step times from STEP "
                         "on (fires the CUSUM drift alert; implies "
                         "--adaptive)")
    ap.add_argument("--inject-straggler", type=int, default=None,
                    metavar="STEP",
                    help="fault injection: inflate shard 0's LLM-phase "
                         "cost 1.6x from STEP on (fires the imbalance "
                         "waterfall component / straggler triage cause)")
    ap.add_argument("--inject-drop-spike", type=int, default=None,
                    metavar="STEP",
                    help="fault injection: report moe_dropped_frac=0.2 "
                         "from STEP on (fires the drop-spike alert and "
                         "the moe_drop waterfall component)")
    ap.add_argument("--serve-metrics", type=int, default=None, metavar="PORT",
                    help="serve live /metrics (OpenMetrics) and /triage "
                         "(JSON) on 127.0.0.1:PORT (0 picks a free port; "
                         "requires --metrics-dir; address lands in "
                         "<metrics-dir>/server.json)")
    ap.add_argument("--serve-metrics-linger", type=float, default=0.0,
                    metavar="SEC",
                    help="keep the metrics server up SEC seconds after "
                         "the loop ends (lets scrapers take a final "
                         "sample)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint root (enables checkpointing)")
    ap.add_argument("--ckpt-every", type=int, default=5,
                    help="save a checkpoint every N steps")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="retention: keep the newest K checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest complete checkpoint in "
                         "--ckpt-dir (elastic when --d differs)")
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    train(cfg, args)


def train(cfg, args: argparse.Namespace) -> list[dict]:
    """The training loop: ``cfg`` -> orchestrator -> PrefetchingLoader
    -> ``make_train_step`` -> ``jax.jit``, with ``args`` as
    :func:`parse_args` returns them (``args.arch`` and ``args.smoke``
    are not read: ``cfg`` is the model).  Returns one record per step:
    ``{"step", "loss", "grad_norm", "step_ms", "compiles"}``, where
    ``step_ms`` is the device-complete step time (read with
    ``block_until_ready`` once the next step is dispatched) and
    ``compiles`` counts the programs JAX lowered for the step."""
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"family={cfg.family}")

    if args.inject_drift is not None and not args.adaptive:
        print("--inject-drift implies --adaptive; enabling calibration")
        args.adaptive = True
    if args.serve_metrics is not None and not args.metrics_dir:
        raise SystemExit("--serve-metrics requires --metrics-dir")

    registry = ledger = recorder = alerts = None
    waterfall = monitor = ledger_monitor = server = None
    if args.metrics_dir:
        from repro.launch.roofline import device_hw

        os.makedirs(args.metrics_dir, exist_ok=True)
        registry = MetricsRegistry()
        set_registry(registry)  # kernel hooks publish here too
        # Hardware MFU is a device metric: only a TPU has a peak (an
        # unknown TPU kind is an error); elsewhere it is not computed.
        hw = device_hw() if jax.default_backend() == "tpu" else None
        recorder = FlightRecorder(
            os.path.join(args.metrics_dir, "flight.jsonl"),
            meta={"arch": cfg.name, "d": args.d, "per": args.per,
                  "steps": args.steps, "adaptive": args.adaptive,
                  "device": jax.devices()[0].device_kind,
                  "smoke": args.smoke})
        alerts = AlertBridge(recorder, registry)
        waterfall = GapWaterfall(registry=registry)
        # Two monitors because the ledger and the waterfall both track
        # an ``imbalance_<phase>`` series (ratio vs fraction-of-step):
        # one shared cursor map would silently skip one of the pair.
        monitor = AnomalyMonitor(alerts=alerts, registry=registry)
        ledger_monitor = AnomalyMonitor(alerts=alerts, registry=registry,
                                        include=("mfu_", "goodput_"))

        def triage_now() -> dict:
            return triage(
                [w.to_dict() for w in waterfall.history],
                anomalies=[a.to_dict() for a in (monitor.anomalies
                                                 + ledger_monitor.anomalies)],
                alerts=list(alerts.alerts),
                meta={"arch": cfg.name, "d": args.d})

        if args.serve_metrics is not None:
            server = MetricsServer(lambda: registry,
                                   triage_provider=triage_now,
                                   port=args.serve_metrics).start()
            with open(os.path.join(args.metrics_dir, "server.json"),
                      "w") as f:
                json.dump({"url": server.url, "port": server.port}, f)
            print(f"serving live metrics at {server.url}/metrics "
                  f"(triage at {server.url}/triage)")

    mesh = None
    dp_axes = ("data",)
    if args.mesh == "host":
        n = len(jax.devices())
        mesh = make_mesh((n, 1), ("data", "model"))

    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, keep_last=args.keep_last,
                                    metrics=registry)

    # The CLI loop feeds ONE straggler-attributed wall-clock scalar per
    # step, and shared-CPU wall times are far noisier than the per-shard
    # samples the calibrator defaults assume -- a 0.25 rel-SE
    # coefficient fit is unreachable here, which would leave the CUSUM
    # detector disarmed forever.  A coarse fit is still a usable drift
    # reference (the detector standardizes residuals against its own
    # warmup window), so loosen the confidence gate for this regime.
    adaptive = (AdaptiveOrchestration(cfg, rel_tol=1.0, min_samples=8)
                if args.adaptive else None)
    cursor = DataCursor(seed=args.seed, batch_index=0,
                        examples_per_instance=args.per, d=args.d)
    start_step = 0
    params = opt_state = None
    resumed_on_mesh = False
    if args.resume:
        if manager is None:
            raise SystemExit("--resume requires --ckpt-dir")
        found = restore_train_state(manager)
        if found is None:
            print("no restorable checkpoint found; starting fresh")
        else:
            state, manifest = found
            params, opt_state = state.params, state.opt_state
            start_step = state.step
            if args.seed != state.cursor.seed:
                print(f"warning: --seed {args.seed} ignored on resume; "
                      f"continuing the checkpoint's stream "
                      f"(seed {state.cursor.seed})")
            if (args.d == state.cursor.d
                    and args.per != state.cursor.examples_per_instance):
                print(f"warning: --per {args.per} ignored on resume; "
                      f"keeping the checkpoint's "
                      f"{state.cursor.examples_per_instance}/instance")
            cursor = state.cursor
            if args.d != cursor.d:
                old_d = cursor.d
                cursor = elastic_cursor(cursor, args.d)
                print(f"elastic resume: DP {old_d} -> {cursor.d} "
                      f"(per-instance {cursor.examples_per_instance}); "
                      f"post-balancing will re-solve for the new shard "
                      f"count")
            if mesh is not None:
                # Reshard the tree AS SAVED so leaf paths line up with
                # the manifest's spec rows ('params/...', 'opt_state/...').
                # This is the only device placement on the resume path
                # (the fresh-start device_put below is skipped).
                resharded = reshard_pytree(
                    {"params": params, "opt_state": opt_state},
                    manifest, mesh)
                params = resharded["params"]
                opt_state = resharded["opt_state"]
                resumed_on_mesh = True
            if adaptive is not None and state.calibrator is not None:
                adaptive.load_state_dict(state.calibrator)
            print(f"resumed from step {start_step} "
                  f"(cursor batch {cursor.batch_index})")

    if registry is not None:
        ledger = StepLedger(cfg, d=cursor.d, registry=registry,
                            peak_flops=hw.peak_flops if hw else None,
                            chips=len(jax.devices()))
        if manager is not None:
            # A fallback restore leaves flagged *.corrupt litter behind;
            # surface each one as a structured alert.
            for p in sorted(glob.glob(
                    os.path.join(manager.root, "*.corrupt*"))):
                alerts.on_checkpoint_fallback(p, start_step)

    orch = MLLMGlobalOrchestrator(
        cfg, cursor.d, vocab=cfg.vocab_size, adaptive=adaptive,
        metrics=registry, pp=args.pp, microbatches=args.microbatches,
        bubble_fill=False if args.no_bubble_fill else None)
    if orch.pp > 1:
        print(f"pipeline mode: pp={orch.pp} "
              f"microbatches={orch.microbatches or 2 * orch.pp} "
              f"bubble_fill={orch.bubble_fill} (docs/pipeline.md)")
    sampler = _sampler_for(cfg)
    probe = [sampler(np.random.default_rng(s), cursor.examples_per_instance)
             for s in range(cursor.d)]
    caps = orch.default_capacities(probe, margin=3.0)
    loader = PrefetchingLoader(
        orch, caps, examples_per_instance=cursor.examples_per_instance,
        seed=cursor.seed, sampler=sampler, start_index=cursor.batch_index)

    if params is None:
        params, opt_state = init_train_state(cfg, jax.random.PRNGKey(0))
    step_fn = make_train_step(cfg, AdamWConfig(lr=args.lr), mesh=mesh,
                              dp_axes=dp_axes)
    p_specs = None
    if mesh is not None:
        p_specs = param_specs(cfg, params, mesh)
        if not resumed_on_mesh:  # resume already placed via the manifest
            params, opt_state = jax.device_put(
                (params, opt_state),
                to_shardings((p_specs, opt_state_specs(p_specs)), mesh))
        # Each DP shard's rows of the batch go to its own device.
        batch_sharding = NamedSharding(mesh, P(dp_axes))
    else:
        # Commit the state where the step's outputs will live, so the
        # second step sees the same argument placement as the first
        # and reuses its executable.
        batch_sharding = jax.devices()[0]
        params, opt_state = jax.device_put((params, opt_state),
                                           batch_sharding)
    step = jax.jit(step_fn, donate_argnums=(0, 1))

    def save_ckpt(next_step: int) -> None:
        specs = None
        if p_specs is not None:
            specs = {"params": p_specs, "opt_state": opt_state_specs(p_specs)}
        state = TrainState(
            params=jax.device_get(params),
            opt_state=jax.device_get(opt_state),
            step=next_step,
            cursor=DataCursor(seed=cursor.seed, batch_index=loader.cursor,
                              examples_per_instance=cursor.examples_per_instance,
                              d=cursor.d),
            calibrator=adaptive.state_dict() if adaptive else None,
        )
        path = save_train_state(manager, state, specs=specs,
                                meta={"arch": cfg.name})
        print(f"checkpoint: step {next_step} -> {path}", flush=True)

    t0 = time.time()
    pending_ckpt_ms = 0.0  # save wall charged to the NEXT step's waterfall
    last_done = None  # host clock when the previous step was seen complete
    records: list[dict] = []

    def settle(it, m, report, batch_np, t_dispatch, compiles) -> None:
        """Wait for step ``it`` and book it.  Called once the NEXT step
        is dispatched (or before a checkpoint), so the device never
        idles on the host's bookkeeping.  ``step_ms`` runs from the
        later of the step's dispatch and the previous step's completion
        to its own completion: device-complete, not host-dispatch,
        time."""
        nonlocal last_done, pending_ckpt_ms
        jax.block_until_ready(m)
        now = time.perf_counter()
        step_ms = (now - max(t_dispatch, last_done or t_dispatch)) * 1e3
        last_done = now
        records.append({"step": it, "loss": float(m["loss"]),
                        "grad_norm": float(m["grad_norm"]),
                        "step_ms": step_ms, "compiles": compiles})
        if args.inject_drift is not None and it >= args.inject_drift:
            # Fault injection: pretend the step slowed 3x so the
            # CUSUM detector (and the alert path behind it) fire
            # without needing a real hardware regression.
            step_ms *= 3.0
        if adaptive is not None and it > start_step:
            # Skip the process's first step (dominated by XLA
            # compilation -- also the first step AFTER a resume,
            # which recompiles in the fresh process).  The
            # whole-step time is attributed to the LLM backbone
            # phase -- on a CPU smoke run the encoders are
            # noise; a per-phase profiler would feed each phase.
            drift = orch.observe_phase_times({"llm": step_ms},
                                             report=report, step=it)
            if alerts is not None:
                alerts.on_drift(drift, step=it)
        if ledger is not None:
            host_m = {k: float(v) for k, v in m.items()
                      if np.ndim(v) == 0}
            if (args.inject_drop_spike is not None
                    and it >= args.inject_drop_spike):
                # Fault injection: a capacity-overflow drop storm.
                host_m["moe_dropped_frac"] = 0.2
            events = ledger.record_step(it, report=report,
                                        step_ms=step_ms, metrics=host_m)
            alerts.on_ledger_events(events)
            if report.pipeline is not None:
                # Per-stage bubble series + fill/uplift gauges; the
                # waterfall below picks the plan off the report and
                # switches to its pipeline_bubble_s{k} algebra.
                ledger.record_pipeline(it, report.pipeline)
            # The smoke path runs dense reference attention, so the
            # tile fraction the Pallas kernels would have skipped IS
            # dead compute actually paid this step -- but only for
            # the attention share of the step's FLOPs, so weight it
            # down before charging it against total useful compute.
            dead = ledger.series.get("kernel_flash_skip_frac")
            attn_share = 0.2
            if it > start_step:
                # Skip the compile-dominated first step: its wall
                # time would poison the waterfall's cost->ms EWMA
                # (same reason the calibrator skips it above).
                wf = waterfall.observe(
                    it, report=report, step_ms=step_ms, metrics=host_m,
                    ckpt_ms=pending_ckpt_ms,
                    dead_tile_frac=(dead[-1][1] * attn_share
                                    if dead else 0.0))
                recorder.record("waterfall", **wf.to_dict())
            pending_ckpt_ms = 0.0
            monitor.poll(waterfall.series)
            ledger_monitor.poll(ledger.series)
            if (it - start_step) % max(args.metrics_every, 1) == 0:
                ledger.record_kernel_stats(it, batch_np)
                write_openmetrics(
                    os.path.join(args.metrics_dir, "metrics.prom"),
                    registry)
                recorder.record("flush", step=it,
                                **{k: v for k, v in ledger.summary().items()
                                   if isinstance(v, (int, float))})
                recorder.flush()
        if it % 5 == 0 or it == args.steps - 1:
            denom = max(it + 1 - start_step, 1)
            print(f"step {it:4d} loss={records[-1]['loss']:.4f} "
                  f"gnorm={records[-1]['grad_norm']:.2f} "
                  f"util={report.phase_utilization['llm']:.2f} "
                  f"{(time.time()-t0)/denom:.2f}s/step", flush=True)

    done = start_step
    unsettled = None  # settle() arguments of the step in flight
    # The step traces under the mesh, so Pallas kernels run per DP shard
    # (kernels.ops.per_dp_shard).  jax.set_mesh applies the mesh when it
    # is built and restores the previous one on exit: one for the loop.
    profile = (jax.profiler.trace(args.trace_out) if args.trace_out
               else contextlib.nullcontext())
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()), profile:
        try:
            for it in range(start_step, args.steps):
                batch_np, report, _ = next(loader)
                if (args.inject_straggler is not None
                        and it >= args.inject_straggler):
                    # Fault injection: one shard's LLM phase runs 1.6x
                    # hot, exactly the residual-imbalance signature the
                    # waterfall attributes to imbalance_llm (triage:
                    # straggler_llm).
                    costs = np.asarray(report.phase_costs["llm"],
                                       dtype=np.float64).copy()
                    costs[0] *= 1.6
                    report.phase_costs["llm"] = costs
                batch = jax.device_put(batch_np, batch_sharding)
                with CompileWatch() as watch:
                    t_dispatch = time.perf_counter()
                    params, opt_state, m = step(params, opt_state, batch)
                if unsettled is not None:
                    settle(*unsettled)
                unsettled = (it, m, report, batch_np, t_dispatch,
                             watch.lowered)
                done = it + 1
                if manager is not None and args.ckpt_every > 0 \
                        and done % args.ckpt_every == 0 and done < args.steps:
                    settle(*unsettled)
                    unsettled = None
                    save_ckpt(done)
                    pending_ckpt_ms = manager.last_op_ms
            if unsettled is not None:
                settle(*unsettled)
        finally:
            loader.close()
    if manager is not None and done > start_step:
        save_ckpt(done)
    if adaptive is not None:
        print("telemetry calibration summary:")
        print(json.dumps(adaptive.summary(), indent=1, default=str))
        print(f"stale plan-ahead re-plans: {orch.replans}")
    if args.trace_out:
        print(f"wrote a profile of the loop to {args.trace_out} "
              f"(open in TensorBoard or ui.perfetto.dev)")
    if ledger is not None:
        write_openmetrics(os.path.join(args.metrics_dir, "metrics.prom"),
                          registry)
        triage_report = triage_now()
        with open(os.path.join(args.metrics_dir, "triage.json"), "w") as f:
            json.dump(triage_report, f, indent=1, default=str)
        print(render_text(triage_report))
        summary = ledger.summary()
        summary.update({f"waterfall_{k}": v
                        for k, v in waterfall.summary().items()})
        recorder.record("summary", **{k: v for k, v in summary.items()
                                      if isinstance(v, (int, float))})
        recorder.close()
        print("observability summary:")
        print(json.dumps(summary, indent=1, default=str))
        print(f"wrote {args.metrics_dir}/metrics.prom, flight.jsonl "
              f"({recorder.events_written} events, "
              f"{len(alerts.alerts)} alerts), triage.json")
    if server is not None:
        if args.serve_metrics_linger > 0:
            print(f"metrics server lingering {args.serve_metrics_linger:g}s "
                  f"at {server.url}", flush=True)
            time.sleep(args.serve_metrics_linger)
        server.stop()
    print("training loop complete")
    return records


if __name__ == "__main__":
    main()
