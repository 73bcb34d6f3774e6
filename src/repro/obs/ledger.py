"""Per-step MFU / goodput / imbalance ledger -- the canonical formulas.

The paper's headline claim is an MFU number, so utilization must be a
first-class, always-on series rather than a per-benchmark proxy.  This
module is the ONE home of every utilization formula in the repo:

  * :func:`simulated_mfu` -- the paper's proxy: one iteration's mean
    useful time over straggler time, summed over synchronous phases
    (``sum_p mean(f_p) / sum_p max(f_p)``).  ``benchmarks/common.py``'s
    ``simulated_iteration_utilization`` is now a thin wrapper over this.
  * :func:`phase_imbalance` -- per-phase straggler ratio
    (``max/mean - 1``): the per-modality imbalance series that Modality
    Composition Incoherence shows up as.
  * :func:`hw_mfu` -- hardware MFU: model FLOPs over
    ``wall * peak * chips`` (what the paper reports as 41.6%).
  * :func:`useful_flops_ratio` -- MODEL_FLOPs / (HLO_FLOPs * chips):
    the compiled-efficiency term ``launch/roofline.py`` reports.
  * :func:`projected_mfu` -- roofline-projected MFU from the serial sum
    of the compute/memory/collective terms (``launch/perf.py``).

:class:`StepLedger` turns the orchestrator's :class:`OrchestratorReport`
(phase cost vectors, solve/exposed times) plus the train step's metrics
dict into labeled registry series -- gauges for the canonical ratios,
histograms for step/phase walls -- and keeps an in-memory
``(step, value)`` series per metric for the anomaly monitor.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from repro.obs.registry import MetricsRegistry, get_registry

__all__ = [
    "StepLedger",
    "goodput_fraction",
    "hw_mfu",
    "phase_imbalance",
    "projected_mfu",
    "simulated_mfu",
    "straggler_overhead",
    "useful_flops_ratio",
]


# ----------------------------------------------------------------------
# Canonical formulas (module functions so every consumer shares them).
# ----------------------------------------------------------------------
def simulated_mfu(phase_costs: Mapping[str, Sequence[float]]) -> float:
    """Paper's MFU proxy over one iteration's phase cost vectors.

    Each phase synchronizes across DP shards, so phase time = the
    straggler's cost; useful time is the mean.  Returns
    ``sum_p mean(c_p) / sum_p max(c_p)`` (1.0 when there is no work).
    """
    total_max = total_mean = 0.0
    for c in phase_costs.values():
        arr = np.asarray(c, dtype=np.float64)
        if arr.size == 0:
            continue
        total_max += float(arr.max())
        total_mean += float(arr.mean())
    return total_mean / total_max if total_max > 0 else 1.0


def straggler_overhead(phase_costs: Mapping[str, Sequence[float]]) -> float:
    """Fraction of the iteration spent waiting on stragglers."""
    return 1.0 - simulated_mfu(phase_costs)


def phase_imbalance(costs: Sequence[float]) -> float:
    """One phase's straggler ratio ``max/mean - 1`` (0 = balanced)."""
    arr = np.asarray(costs, dtype=np.float64)
    if arr.size == 0 or arr.mean() <= 0:
        return 0.0
    return float(arr.max() / arr.mean()) - 1.0


def hw_mfu(model_flops: float, wall_s: float, *, peak_flops: float,
           chips: int = 1) -> float:
    """Hardware MFU: useful model FLOPs / (wall * aggregate peak)."""
    denom = wall_s * peak_flops * max(chips, 1)
    return model_flops / denom if denom > 0 else 0.0


def useful_flops_ratio(model_flops_global: float, hlo_flops_per_chip: float,
                       chips: int) -> float:
    """MODEL_FLOPs / (HLO_FLOPs * chips): compiled-FLOP efficiency
    (rematerialization, padding and masking waste show up here)."""
    denom = hlo_flops_per_chip * max(chips, 1)
    return model_flops_global / denom if denom > 0 else 0.0


def projected_mfu(useful_ratio: float, compute_s: float, memory_s: float,
                  collective_s: float) -> float:
    """Roofline-projected MFU: compiled-FLOP efficiency discounted by
    the serial roofline sum (compute fraction of the projected step)."""
    total = compute_s + memory_s + collective_s
    return useful_ratio * compute_s / total if total > 0 else 0.0


def goodput_fraction(step_ms: float, exposed_ms: float, mfu: float) -> float:
    """Goodput = balanced-useful fraction of the measured step: the
    simulated MFU discounted by host latency the step actually waited
    on (exposed dispatcher solves, re-plans)."""
    if step_ms <= 0:
        return mfu
    return max(0.0, 1.0 - min(exposed_ms, step_ms) / step_ms) * mfu


# ----------------------------------------------------------------------
class StepLedger:
    """Per-step accounting: OrchestratorReport + metrics -> registry.

    One instance per training run.  ``record_step`` is the only hot-path
    call; everything it publishes is O(#phases) gauge/histogram updates.
    Alert *detection* lives here (drop spikes, replans); alert *routing*
    is the caller's job via the returned event list (the train loop
    forwards them to the flight recorder).
    """

    # moe_dropped_frac above this is an alert (drop-free dispatch should
    # keep it at exactly 0; the capacity-buffer legacy path stays low).
    MOE_DROP_ALERT = 0.05

    def __init__(self, cfg=None, *, d: int = 1,
                 registry: MetricsRegistry | None = None,
                 peak_flops: float | None = None, chips: int | None = None,
                 counter_track_prefixes: Sequence[str] = ("kernel_", "alerts_"),
                 ) -> None:
        self.cfg = cfg
        self.d = d
        self.registry = registry if registry is not None else get_registry()
        self.peak_flops = peak_flops
        self.chips = chips if chips is not None else d
        self.counter_track_prefixes = tuple(counter_track_prefixes)
        # FLOPs per token ~ 6 * active params (fwd + bwd); decode/prefill
        # callers can override per call.
        self._flops_per_token = None
        if cfg is not None:
            try:
                self._flops_per_token = 6.0 * float(cfg.active_param_count())
            except Exception:
                self._flops_per_token = 6.0 * float(cfg.param_count())
        r = self.registry
        self._g_mfu = r.gauge("train_mfu_simulated",
                              "paper MFU proxy: sum mean(f)/sum max(f)")
        self._g_goodput = r.gauge("train_goodput_frac",
                                  "simulated MFU minus exposed host latency")
        self._g_straggler = r.gauge("train_straggler_overhead_frac",
                                    "1 - simulated MFU")
        self._g_hw_mfu = r.gauge("train_mfu_hw",
                                 "model FLOPs / (wall * peak * chips)")
        self._g_imb = r.gauge("train_phase_imbalance",
                              "per-phase max/mean - 1", labels=("phase",))
        self._h_step = r.histogram("train_step_ms", "train step wall time",
                                   labels=())
        self._h_solve = r.histogram("orch_phase_solve_ms",
                                    "dispatcher solve time per phase",
                                    labels=("phase",))
        self._h_exposed = r.histogram("orch_exposed_ms",
                                      "host plan latency the step waited on")
        self._c_tokens = r.counter("train_tokens", "tokens trained on")
        self._c_steps = r.counter("train_steps", "train steps")
        self._c_replans = r.counter("orch_replans",
                                    "stale plan-ahead plans re-planned")
        self._g_metric = r.gauge("train_metric", "last train-step metrics",
                                 labels=("name",))
        # Pipeline mode (docs/pipeline.md): per-stage unfilled bubble
        # fraction + schedule-level fill/uplift gauges, published by
        # ``record_pipeline`` when the orchestrator runs with pp > 1.
        self._g_pipe_bubble = r.gauge(
            "pipeline_bubble_frac",
            "unfilled 1F1B bubble fraction of stage device time",
            labels=("stage",))
        self._g_pipe_fill = r.gauge(
            "pipeline_fill_fraction",
            "encoder compute placed / theoretical 1F1B bubble time")
        self._g_pipe_uplift = r.gauge(
            "pipeline_mfu_uplift",
            "projected MFU delta of bubble fill vs no-fill 1F1B")
        # (step, value) series for the anomaly monitor.
        self.series: dict[str, list[tuple[int, float]]] = {}
        self.steps_recorded = 0

    # ------------------------------------------------------------------
    def _track(self, name: str, step: int, value: float) -> None:
        self.series.setdefault(name, []).append((step, float(value)))

    def record_step(self, step: int, *, report=None, step_ms: float | None = None,
                    metrics: Mapping[str, float] | None = None,
                    tokens: int | None = None) -> list[dict]:
        """Account one training step; returns alert events (possibly
        empty) for the caller to route to the flight recorder.

        ``report`` is an ``OrchestratorReport`` (phase costs, solve and
        exposed times); ``step_ms`` the measured device-complete wall
        time; ``metrics`` the train step's metrics dict (host scalars).
        """
        events: list[dict] = []
        self._c_steps.inc()
        self.steps_recorded += 1
        if step_ms is not None:
            self._h_step.observe(step_ms)

        mfu = None
        if report is not None:
            mfu = simulated_mfu(report.phase_costs)
            self._g_mfu.set(mfu)
            self._g_straggler.set(1.0 - mfu)
            self._track("mfu_simulated", step, mfu)
            for phase, costs in report.phase_costs.items():
                imb = phase_imbalance(costs)
                self._g_imb.set(imb, phase=phase)
                self._track(f"imbalance_{phase}", step, imb)
            for phase, ms in report.phase_solve_ms.items():
                self._h_solve.observe(ms, phase=phase)
            self._h_exposed.observe(report.exposed_ms)
            if step_ms:
                if report.exposed_ms > step_ms:
                    # goodput_fraction clamps exposed_ms to the step,
                    # but waiting longer on the plan than the whole
                    # step took means the two clocks disagree --
                    # surface it instead of only clamping silently.
                    events.append({"alert": "measurement_inconsistent",
                                   "step": step,
                                   "exposed_ms": float(report.exposed_ms),
                                   "step_ms": float(step_ms)})
                gp = goodput_fraction(step_ms, report.exposed_ms, mfu)
                self._g_goodput.set(gp)
                self._track("goodput_frac", step, gp)
            if report.replanned:
                self._c_replans.inc()
                events.append({"alert": "stale_plan_replanned", "step": step,
                               "coeff_version": report.coeff_version})

        if tokens is None and metrics is not None and "tokens" in metrics:
            tokens = int(metrics["tokens"])
        if tokens:
            self._c_tokens.inc(float(tokens))
            if (self._flops_per_token and step_ms and self.peak_flops):
                hm = hw_mfu(self._flops_per_token * tokens, step_ms * 1e-3,
                            peak_flops=self.peak_flops, chips=self.chips)
                self._g_hw_mfu.set(hm)
                self._track("mfu_hw", step, hm)

        if metrics is not None:
            for name, v in metrics.items():
                try:
                    fv = float(v)
                except (TypeError, ValueError):
                    continue
                self._g_metric.set(fv, name=name)
            drop = metrics.get("moe_dropped_frac")
            if drop is not None and float(drop) > self.MOE_DROP_ALERT:
                events.append({"alert": "moe_drop_spike", "step": step,
                               "moe_dropped_frac": float(drop),
                               "threshold": self.MOE_DROP_ALERT})

        # Counter tracks (kernel hit/skip counters, alert totals): poll
        # the registry so host-side kernel hooks show up on the step axis.
        for name, value in self.registry.snapshot_counters().items():
            if name.startswith(self.counter_track_prefixes):
                self._track(name, step, value)
        return events

    # ------------------------------------------------------------------
    def record_pipeline(self, step: int, plan) -> None:
        """Account one step's pipeline schedule (a ``PipelinePlan``).

        Publishes per-stage unfilled-bubble fractions (device-time
        share of each stage lane), the run's bubble-fill fraction and
        the projected MFU uplift, and keeps the per-stage series for
        the anomaly monitor."""
        if plan is None:
            return
        denom = float(plan.rank_total.max()) * plan.d
        stage_idle = plan.stage_idle.sum(axis=0)  # (pp,) over ranks
        for s in range(plan.pp):
            frac = stage_idle[s] / denom if denom > 0 else 0.0
            self._g_pipe_bubble.set(frac, stage=str(s))
            self._track(f"pipeline_bubble_s{s}", step, frac)
        self._g_pipe_fill.set(plan.fill_fraction)
        self._g_pipe_uplift.set(plan.mfu_uplift)
        self._track("pipeline_fill_fraction", step, plan.fill_fraction)
        self._track("pipeline_mfu_uplift", step, plan.mfu_uplift)

    # ------------------------------------------------------------------
    def record_kernel_stats(self, step: int, batch: Mapping[str, np.ndarray],
                            *, block_q: int | None = None,
                            block_kv: int | None = None) -> None:
        """Sample the flash tile-skip fraction from a host batch.

        Cheap interval math over seg/pos (the same accounting the kernel
        uses); call it every flush interval, not every step."""
        seg = pos = None
        for sk, pk in (("llm_seg", "llm_pos"), ("seg", "pos")):
            if sk in batch:
                seg, pos = np.asarray(batch[sk]), np.asarray(batch[pk])
                break
        if seg is None or self.cfg is None:
            return
        from repro.kernels.flash_attention import tile_skip_fraction
        bq = block_q or min(self.cfg.block_q, seg.shape[-1])
        bk = block_kv or min(self.cfg.block_kv, seg.shape[-1])
        if seg.shape[-1] % bq or seg.shape[-1] % bk:
            return
        frac = tile_skip_fraction(seg, seg, pos, pos, block_q=bq, block_kv=bk,
                                  causal=True, window=self.cfg.sliding_window)
        self._track("kernel_flash_skip_frac", step, frac)

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        """End-of-run canonical metrics (also what train.py prints)."""
        out = {
            "steps": self.steps_recorded,
            "tokens": self._c_tokens.labels().value,
            "step_ms_p50": self._h_step.labels().quantile(0.5),
            "step_ms_p95": self._h_step.labels().quantile(0.95),
            "step_ms_p99": self._h_step.labels().quantile(0.99),
            "mfu_simulated": self._g_mfu.labels().value,
            "goodput_frac": self._g_goodput.labels().value,
            "straggler_overhead_frac": self._g_straggler.labels().value,
        }
        if self.peak_flops:
            out["mfu_hw"] = self._g_hw_mfu.labels().value
        for labels, child in self._g_imb.children():
            out[f"imbalance_{labels['phase']}"] = child.value
        return out
