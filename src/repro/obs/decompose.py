"""Per-step MFU-gap waterfall: additive, closure-checked attribution.

The ledger (:mod:`repro.obs.ledger`) records *that* a step lost MFU;
this module explains *where* it went.  Each training step's gap --
``1 - goodput`` against a perfectly balanced, zero-overhead step -- is
split into additive components, each a fraction of the measured step
wall time:

  * ``imbalance_<phase>`` -- residual post-balance straggler wait per
    synchronous phase: ``(max_p - mean_p)`` of the phase's per-shard
    cost vector, converted to wall time.  These are exactly the terms
    of ``1 - simulated_mfu`` re-expressed on the measured clock, so the
    per-(phase, modality) split is additive by construction.
  * ``exposed_dispatch`` -- dispatcher solve / re-plan host latency the
    step actually waited on (``OrchestratorReport.exposed_ms``).
  * ``checkpoint_stall`` -- save/restore wall time charged to the step
    that paid it (:class:`repro.checkpoint.CheckpointManager` op log).
  * ``kernel_dead_tiles`` -- compute spent on dead (padding) tiles the
    block-skipping kernels would have skipped (PR 6 tile counters).
  * ``moe_drop`` -- useful work lost to dropped MoE tokens.
  * ``preempt_recompute`` -- serving-side recompute of preempted
    context (teacher-forced re-prefill is real compute, zero goodput).
  * ``unattributed`` -- the signed residual: measured step time the
    model above does NOT explain.  This is the closure check -- a
    healthy run keeps it near zero; a cost-model drift (step time moves
    without the cost vectors moving) shows up *here*, which is exactly
    how the triage layer roots drift.

Closure is exact by algebra: with ``T`` the measured step time, the
named components plus ``unattributed`` telescope to the gap
``1 - useful_net/T``.  The *checked* property (gated in
``benchmarks/triage_accuracy.py``) is that on a healthy step the named
components alone sum to the measured gap within tolerance, i.e.
``|unattributed|`` stays small relative to the gap.

Cost vectors arrive in abstract cost units; the waterfall calibrates a
cost-to-ms scale online (EWMA over *previous* steps of
``(step_ms - host_ms) / sum_p max_p``), so the current step's closure
is a genuine out-of-sample check, not a tautology.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import numpy as np

from repro.obs.registry import MetricsRegistry, get_registry

__all__ = ["GapWaterfall", "WaterfallStep", "COMPONENT_ORDER"]

# Canonical component ordering (imbalance phases expand in report order;
# pipeline_bubble stages expand when the run is pipelined, pp > 1).
COMPONENT_ORDER = (
    "imbalance_*",
    "pipeline_bubble_s*",
    "exposed_dispatch",
    "checkpoint_stall",
    "kernel_dead_tiles",
    "moe_drop",
    "preempt_recompute",
    "unattributed",
)


@dataclasses.dataclass
class WaterfallStep:
    """One step's attributed MFU gap (all values are fractions of the
    measured step wall time)."""

    step: int
    step_ms: float
    gap: float  # 1 - goodput: everything that was not balanced useful work
    goodput: float  # useful_net / step_ms
    components: dict[str, float]  # named components, insertion-ordered
    unattributed: float  # signed residual the model does not explain
    closure_err: float  # |unattributed| / max(gap, floor)
    scale_ms_per_cost: float  # cost-unit -> ms scale used this step

    def to_dict(self) -> dict:
        return {
            "step": self.step, "step_ms": self.step_ms, "gap": self.gap,
            "goodput": self.goodput, "components": dict(self.components),
            "unattributed": self.unattributed,
            "closure_err": self.closure_err,
            "scale_ms_per_cost": self.scale_ms_per_cost,
        }

    @staticmethod
    def from_dict(d: Mapping) -> "WaterfallStep":
        return WaterfallStep(
            step=int(d["step"]), step_ms=float(d["step_ms"]),
            gap=float(d["gap"]), goodput=float(d["goodput"]),
            components=dict(d["components"]),
            unattributed=float(d["unattributed"]),
            closure_err=float(d["closure_err"]),
            scale_ms_per_cost=float(d.get("scale_ms_per_cost", 0.0)))


class GapWaterfall:
    """Online per-step MFU-gap decomposition.

    ``observe`` is the only hot-path call; it publishes each component
    as a labeled gauge (``mfu_gap_component{component=...}``) through
    the registry, keeps ``(step, value)`` series for the anomaly
    monitor, and returns the :class:`WaterfallStep` for the
    flight recorder.
    """

    # Relative-closure denominator floor: a near-zero gap makes any
    # residual look huge; below this gap closure is not meaningful.
    GAP_FLOOR = 0.02

    def __init__(self, *, registry: MetricsRegistry | None = None,
                 scale_ema: float = 0.3, warmup: int = 3,
                 history_cap: int = 100_000) -> None:
        self.registry = registry if registry is not None else get_registry()
        self.scale_ema = float(scale_ema)
        self.warmup = int(warmup)
        self.history_cap = int(history_cap)
        self._scale: float | None = None  # EWMA cost-unit -> ms
        self.history: list[WaterfallStep] = []
        self.series: dict[str, list[tuple[int, float]]] = {}
        r = self.registry
        self._g_comp = r.gauge(
            "mfu_gap_component",
            "per-step MFU-gap waterfall component (fraction of step)",
            labels=("component",))
        # NB not "_total": that suffix is counter-reserved in OpenMetrics
        # and the strict parser rejects negative values under it (the
        # gap goes signed-negative when measurement noise beats the
        # scale calibration).
        self._g_gap = r.gauge("mfu_gap",
                              "per-step total MFU gap (1 - goodput)")
        self._g_goodput = r.gauge(
            "mfu_goodput_attributed",
            "balanced useful fraction after waterfall attribution")
        self._g_closure = r.gauge(
            "mfu_gap_closure_err",
            "|unattributed| / gap -- waterfall closure check")

    # ------------------------------------------------------------------
    def _track(self, name: str, step: int, value: float) -> None:
        self.series.setdefault(name, []).append((step, float(value)))

    def observe(self, step: int, *, report=None,
                phase_costs: Mapping[str, Sequence[float]] | None = None,
                step_ms: float, exposed_ms: float | None = None,
                metrics: Mapping[str, float] | None = None,
                ckpt_ms: float = 0.0, dead_tile_frac: float = 0.0,
                recompute_frac: float = 0.0,
                pipeline=None) -> WaterfallStep:
        """Attribute one step's gap.

        ``report`` is an ``OrchestratorReport`` (or anything with
        ``phase_costs`` / ``exposed_ms``); alternatively pass
        ``phase_costs`` and ``exposed_ms`` directly.  ``ckpt_ms`` is
        checkpoint save/restore wall charged to this step;
        ``dead_tile_frac`` / ``recompute_frac`` are waste fractions of
        the useful compute (kernel padding tiles, preemption
        recompute).  ``metrics`` supplies ``moe_dropped_frac``.

        ``pipeline`` switches to the pipeline-mode algebra: a
        ``PipelinePlan`` (or its ``waterfall_inputs()`` mapping), taken
        from ``report.pipeline`` automatically when present.  Devices
        then live on a (d, pp) grid: per-stage unfilled bubble time
        becomes a ``pipeline_bubble_s{k}`` component, the cross-rank
        pipeline-makespan spread becomes ``imbalance_llm``, and closure
        follows from the simulator identity ``useful + sum_k idle_k =
        pp * rank_total`` per rank.
        """
        if report is not None:
            phase_costs = report.phase_costs
            if exposed_ms is None:
                exposed_ms = report.exposed_ms
            if pipeline is None:
                pipeline = getattr(report, "pipeline", None)
        if pipeline is not None and hasattr(pipeline, "waterfall_inputs"):
            pipeline = pipeline.waterfall_inputs()
        phase_costs = phase_costs or {}
        exposed_ms = float(exposed_ms or 0.0)
        step_ms = float(step_ms)
        if step_ms <= 0:
            raise ValueError(f"step_ms must be positive, got {step_ms}")

        # Host-side time is measured directly in ms; the remainder of
        # the step is compute, which calibrates the cost->ms scale.
        host_ms = min(exposed_ms + ckpt_ms, step_ms)
        compute_ms = max(step_ms - host_ms, 0.0)

        comps: dict[str, float] = {}
        if pipeline is not None:
            # ---- pipeline mode: attribute on the (d, pp) device grid.
            pp = int(pipeline["stages"])
            stage_bubble = np.asarray(pipeline["stage_bubble"], np.float64)
            totals = np.asarray(pipeline["rank_totals"], np.float64)
            crit = float(pipeline["critical_cost"])
            sum_max = crit  # cost on the critical path -> compute_ms
            scale_now = compute_ms / crit if crit > 0 else 0.0
            scale = self._scale if self._scale is not None else scale_now
            for k in range(pp):
                comps[f"pipeline_bubble_s{k}"] = (
                    float(stage_bubble[k]) * scale / (pp * step_ms))
            mean_total = float(totals.mean()) if totals.size else crit
            comps["imbalance_llm"] = (crit - mean_total) * scale / step_ms
            useful_raw = (float(pipeline["useful_per_device"])
                          * scale / step_ms)
        else:
            maxes: dict[str, float] = {}
            means: dict[str, float] = {}
            for phase, costs in phase_costs.items():
                arr = np.asarray(costs, dtype=np.float64)
                if arr.size == 0:
                    continue
                maxes[phase] = float(arr.max())
                means[phase] = float(arr.mean())
            sum_max = sum(maxes.values())
            scale_now = compute_ms / sum_max if sum_max > 0 else 0.0
            # Attribute with the scale learned from PREVIOUS steps so the
            # closure residual is a real check (warmup uses the current
            # estimate: nothing to check against yet).
            scale = self._scale if self._scale is not None else scale_now
            for phase in maxes:
                comps[f"imbalance_{phase}"] = (
                    (maxes[phase] - means[phase]) * scale / step_ms)
            useful_raw = sum(means.values()) * scale / step_ms
        warming = len(self.history) < self.warmup

        comps["exposed_dispatch"] = min(exposed_ms, step_ms) / step_ms
        comps["checkpoint_stall"] = min(ckpt_ms, step_ms) / step_ms
        drop_frac = float((metrics or {}).get("moe_dropped_frac", 0.0) or 0.0)
        comps["kernel_dead_tiles"] = max(dead_tile_frac, 0.0) * useful_raw
        comps["moe_drop"] = max(drop_frac, 0.0) * useful_raw
        comps["preempt_recompute"] = max(recompute_frac, 0.0) * useful_raw

        modeled = (sum_max * scale + min(exposed_ms, step_ms)
                   + min(ckpt_ms, step_ms)) / step_ms
        unattributed = 1.0 - modeled
        waste = (comps["kernel_dead_tiles"] + comps["moe_drop"]
                 + comps["preempt_recompute"])
        goodput = useful_raw - waste
        gap = 1.0 - goodput
        closure_err = (0.0 if warming
                       else abs(unattributed) / max(gap, self.GAP_FLOOR))

        wf = WaterfallStep(step=step, step_ms=step_ms, gap=gap,
                           goodput=goodput, components=comps,
                           unattributed=unattributed,
                           closure_err=closure_err,
                           scale_ms_per_cost=scale)
        if len(self.history) < self.history_cap:
            self.history.append(wf)
        for name, v in comps.items():
            self._g_comp.set(v, component=name)
            self._track(name, step, v)
        self._g_comp.set(unattributed, component="unattributed")
        self._track("unattributed", step, unattributed)
        self._g_gap.set(gap)
        self._g_goodput.set(goodput)
        self._g_closure.set(closure_err)
        self._track("gap", step, gap)
        self._track("goodput", step, goodput)

        # Fold this step's scale into the EWMA for the NEXT step.
        if scale_now > 0:
            if self._scale is None:
                self._scale = scale_now
            else:
                a = self.scale_ema
                self._scale = (1.0 - a) * self._scale + a * scale_now
        return wf

    # ------------------------------------------------------------------
    def closure(self, *, skip_warmup: bool = True) -> dict:
        """Run-level closure summary over the recorded history."""
        hist = self.history[self.warmup:] if skip_warmup else self.history
        if not hist:
            return {"steps": 0, "max_closure_err": 0.0,
                    "mean_closure_err": 0.0}
        errs = [w.closure_err for w in hist]
        return {"steps": len(hist),
                "max_closure_err": float(max(errs)),
                "mean_closure_err": float(sum(errs) / len(errs))}

    def summary(self) -> dict:
        """Mean per-component attribution over the run (fractions)."""
        if not self.history:
            return {}
        names: list[str] = []
        for w in self.history:
            for n in w.components:
                if n not in names:
                    names.append(n)
        out = {f"component_{n}": float(np.mean(
            [w.components.get(n, 0.0) for w in self.history])) for n in names}
        out["gap"] = float(np.mean([w.gap for w in self.history]))
        out["unattributed"] = float(np.mean(
            [w.unattributed for w in self.history]))
        out.update(self.closure())
        return out
