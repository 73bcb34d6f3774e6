"""Adaptive cost models: measured coefficients behind the analytic prior.

``AdaptiveCostModel`` wraps one phase's :class:`~repro.core.cost_model.
CostModel`: it starts on the analytic ``transformer_cost_coeffs`` prior
(derived once through ``llm_cost_model`` / ``encoder_cost_model`` -- the
single injection point), accumulates measured (features, wall-time)
samples through a :class:`~repro.telemetry.calibrate.PhaseCalibrator`,
and swaps in the fitted coefficients once their confidence passes the
threshold.  Consumers poll :meth:`current` each time they need f(S);
:attr:`version` bumps only when the swap would *change the plan* (the
balancing objective is scale-invariant, so only a material shift of the
quadratic/linear ratio ``lam = beta/alpha`` forces a re-plan).

``AdaptiveOrchestration`` bundles one adaptive model per training phase
(LLM backbone + every encoder) plus a shared
:class:`~repro.telemetry.trace.TraceBuffer`, and is what
``MLLMGlobalOrchestrator(adaptive=...)`` consumes: dispatcher cost
models are refreshed from it before every solve, phase plans are
stamped with its version (stale plan-ahead plans are re-planned), and
measured per-phase step times flow back in through
``observe`` / ``observe_straggler``.

``AdaptiveServingCostModel`` is the serving twin: it duck-types
:class:`~repro.core.cost_model.ServingCostModel` (the scheduler and
``assign_replicas`` call it directly) while re-fitting the per-modality
weights and the decode/prefill cost ratio from ``EngineReport``-level
prefill/decode wall times.  The backbone alpha/beta stay on the
scheduler's unit scale (alpha ~ 1 per token) so ``token_budget``
semantics never change -- calibration only moves the *ratios* the
admission decisions depend on.

Calibration changes only the plan, never the math: every consumer uses
these models to choose rearrangements/admissions, and the rearranged
payloads are consequence-invariant by construction (paper S3.3).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import numpy as np

from repro.core.cost_model import (
    CostModel,
    ServingCostModel,
    encoder_cost_model,
    llm_cost_model,
)
from repro.telemetry.calibrate import (
    CoeffEstimate,
    PhaseCalibrator,
    ServingCalibrator,
)
from repro.telemetry.trace import PhaseSample, TraceBuffer

__all__ = [
    "AdaptiveCostModel",
    "AdaptiveOrchestration",
    "AdaptiveServingCostModel",
]


def _lam_differs(old: CostModel, new: CostModel, tol: float) -> bool:
    """Would swapping ``old`` for ``new`` change balancing decisions?

    The per-phase objective is invariant to scaling f, so only the
    quadratic/linear ratio matters."""
    lo, ln = old.lam, new.lam
    scale = max(abs(lo), abs(ln))
    if scale == 0:
        return False
    return abs(ln - lo) / scale > tol


class AdaptiveCostModel:
    """One phase's f(S): analytic prior -> calibrated coefficients."""

    def __init__(self, prior: CostModel, *, phase: str = "phase",
                 trace: TraceBuffer | None = None,
                 replan_tol: float = 0.05, **calibrator_kw) -> None:
        self.prior = prior
        self.phase = phase
        self.trace = trace
        self.replan_tol = replan_tol
        self.calibrator = PhaseCalibrator(prior, **calibrator_kw)
        self._current = prior
        self._version = 0

    # ------------------------------------------------------------------
    @property
    def version(self) -> int:
        """Bumped whenever :meth:`current`'s output changes materially
        (swap-in, drift re-fit, or a > ``replan_tol`` shift of lam)."""
        return self._version

    @property
    def calibrated(self) -> bool:
        return self.calibrator.calibrated

    @property
    def estimate(self) -> CoeffEstimate | None:
        return self.calibrator.estimate

    @property
    def drift_events(self) -> int:
        return self.calibrator.drift_events

    def current(self) -> CostModel:
        return self._current

    # ------------------------------------------------------------------
    def observe(self, features: np.ndarray, wall_ms, *, step: int = 0,
                shards: Sequence[int] | None = None) -> bool:
        """Feed measured (features, wall-time) rows; True on drift."""
        F = np.asarray(features, dtype=np.float64)
        if F.ndim == 1:
            F = F[None, :]
        w = np.atleast_1d(np.asarray(wall_ms, dtype=np.float64))
        if self.trace is not None:
            for i, (row, t) in enumerate(zip(F, w)):
                shard = shards[i] if shards is not None else i
                self.trace.add(PhaseSample(
                    phase=self.phase, shard=int(shard), step=step,
                    features=row, wall_ms=float(t)))
        drifted = self.calibrator.observe(F, w)
        cand = self.calibrator.cost_model()
        if drifted or _lam_differs(self._current, cand, self.replan_tol):
            self._current = cand
            self._version += 1
        return drifted

    def observe_straggler(self, features: np.ndarray, wall_ms: float, *,
                          step: int = 0) -> bool:
        """Attribute one synchronous-step wall time to the straggler.

        Under synchronous DP the measured step time is the *max* over
        shards, so the sample pairs the scalar time with the feature
        row the current model predicts most expensive."""
        F = np.asarray(features, dtype=np.float64)
        if F.ndim == 1:
            F = F[None, :]
        costs = self._current.cost_from_features(F)
        i = int(np.argmax(costs))
        return self.observe(F[i], float(wall_ms), step=step, shards=[i])

    # -- checkpointing --------------------------------------------------
    def state_dict(self) -> dict:
        """JSON-able dynamic state: calibrator window + the currently
        served coefficients and version (the prior is reconstructed from
        the config at restore time, not serialized)."""
        return {
            "calibrator": self.calibrator.state_dict(),
            "alpha": self._current.alpha,
            "beta": self._current.beta,
            "version": self._version,
        }

    def load_state_dict(self, state) -> None:
        self.calibrator.load_state_dict(state["calibrator"])
        self._current = self.prior.with_coeffs(state["alpha"], state["beta"])
        self._version = int(state["version"])

    def summary(self) -> dict:
        est = self.estimate
        return {
            "phase": self.phase,
            "prior_alpha": self.prior.alpha,
            "prior_beta": self.prior.beta,
            "alpha": self._current.alpha,
            "beta": self._current.beta,
            "calibrated": self.calibrated,
            "version": self._version,
            "drift_events": self.drift_events,
            "n_samples": self.calibrator.n_observed,
            "rel_se": est.max_rel_se() if est is not None else None,
        }


class AdaptiveOrchestration:
    """Per-phase adaptive cost models for the training orchestrator."""

    def __init__(self, cfg=None, *, priors: Mapping[str, CostModel] | None = None,
                 trace_capacity: int = 8192, replan_tol: float = 0.05,
                 **calibrator_kw) -> None:
        if cfg is None and priors is None:
            raise ValueError("need a ModelConfig or explicit per-phase priors")
        self.trace = TraceBuffer(trace_capacity)
        phase_priors: dict[str, CostModel] = {}
        if cfg is not None:
            phase_priors["llm"] = llm_cost_model(cfg)
            for e in cfg.encoders:
                phase_priors[e.name] = encoder_cost_model(e)
        if priors:
            phase_priors.update(priors)
        self.models = {
            name: AdaptiveCostModel(prior, phase=name, trace=self.trace,
                                    replan_tol=replan_tol, **calibrator_kw)
            for name, prior in phase_priors.items()
        }
        self._step = 0

    # ------------------------------------------------------------------
    @property
    def version(self) -> int:
        return sum(m.version for m in self.models.values())

    @property
    def drift_events(self) -> int:
        return sum(m.drift_events for m in self.models.values())

    @property
    def calibrated(self) -> bool:
        return all(m.calibrated for m in self.models.values())

    def cost_model(self, phase: str) -> CostModel:
        return self.models[phase].current()

    # ------------------------------------------------------------------
    def observe(self, features_by_phase: Mapping[str, np.ndarray],
                times_by_phase: Mapping[str, "float | np.ndarray"], *,
                step: int | None = None) -> dict[str, bool]:
        """Feed one step's measured phase times.

        ``times_by_phase[p]`` is either a per-shard vector matched to
        ``features_by_phase[p]`` rows, or a scalar synchronous step time
        (attributed to the straggler shard).  Phases without a time are
        skipped.  Returns the per-phase drift flags."""
        if step is None:
            step = self._step
        self._step = step + 1
        out: dict[str, bool] = {}
        for phase, t in times_by_phase.items():
            if phase not in self.models:
                continue
            F = np.asarray(features_by_phase[phase], dtype=np.float64)
            t_arr = np.atleast_1d(np.asarray(t, dtype=np.float64))
            m = self.models[phase]
            if t_arr.size == 1 and F.ndim == 2 and F.shape[0] > 1:
                out[phase] = m.observe_straggler(F, float(t_arr[0]), step=step)
            else:
                out[phase] = m.observe(F, t_arr, step=step)
        return out

    # -- checkpointing --------------------------------------------------
    def state_dict(self) -> dict:
        """JSON-able calibration state for all phases (the trace ring is
        diagnostic telemetry and deliberately NOT checkpointed)."""
        return {
            "step": self._step,
            "models": {n: m.state_dict() for n, m in self.models.items()},
        }

    def load_state_dict(self, state) -> None:
        self._step = int(state["step"])
        for name, sub in state["models"].items():
            if name in self.models:
                self.models[name].load_state_dict(sub)

    def summary(self) -> dict[str, dict]:
        return {name: m.summary() for name, m in self.models.items()}


class AdaptiveServingCostModel:
    """Serving admission costs with measured modality weights.

    Duck-types :class:`~repro.core.cost_model.ServingCostModel`
    (``model`` / ``modality_weights`` / ``decode_cost`` /
    ``weighted_length[s]`` / ``prefill_cost``), so it drops into
    :class:`~repro.serving.engine.scheduler.Scheduler` and
    ``assign_replicas`` unchanged.  The engine feeds it per-call
    prefill/decode wall times; once the fit is confident the calibrated
    weights replace the analytic ones.  The backbone alpha/beta are kept
    from the prior: the budget is denominated in "text-token units" and
    calibration must not silently rescale it."""

    def __init__(self, prior: ServingCostModel, *,
                 trace: TraceBuffer | None = None,
                 replan_tol: float = 0.05, **calibrator_kw) -> None:
        self.prior = prior
        self.trace = trace
        self.replan_tol = replan_tol
        self.calibrator = ServingCalibrator(
            tuple(prior.modality_weights), **calibrator_kw)
        self._current = prior
        self._version = 0
        self._n_prefill = 0
        self._n_decode = 0

    # -- ServingCostModel interface -------------------------------------
    @property
    def model(self) -> CostModel:
        return self._current.model

    @property
    def modality_weights(self) -> Mapping[str, float]:
        return self._current.modality_weights

    @property
    def decode_cost(self) -> float:
        return self._current.decode_cost

    def weighted_length(self, text_len, modality_tokens=None) -> float:
        return self._current.weighted_length(text_len, modality_tokens)

    def prefill_cost(self, text_len, modality_tokens=None) -> float:
        return self._current.prefill_cost(text_len, modality_tokens)

    def weighted_lengths(self, text_lens, modality_tokens) -> np.ndarray:
        return self._current.weighted_lengths(text_lens, modality_tokens)

    # -- calibration ----------------------------------------------------
    @property
    def version(self) -> int:
        return self._version

    @property
    def calibrated(self) -> bool:
        return self.calibrator.calibrated

    @property
    def drift_events(self) -> int:
        return self.calibrator.drift_events

    def current(self) -> ServingCostModel:
        return self._current

    def observe_prefill(self, token_counts: Mapping[str, int],
                        wall_ms: float, *, step: int = 0) -> bool:
        if self.trace is not None:
            n = float(sum(token_counts.values()))
            self.trace.add(PhaseSample(
                phase="serve_prefill", shard=0, step=step,
                features=np.array([n, 0.0, 0.0, 0.0]),
                wall_ms=float(wall_ms)))
        drifted = self.calibrator.observe_prefill(token_counts, wall_ms)
        self._refresh()
        return drifted

    def observe_decode(self, batch: int, wall_ms: float, *,
                       step: int = 0) -> None:
        if self.trace is not None:
            self.trace.add(PhaseSample(
                phase="serve_decode", shard=0, step=step,
                features=np.array([float(batch), 0.0, 0.0, 0.0]),
                wall_ms=float(wall_ms)))
        self.calibrator.observe_decode(batch, wall_ms)
        self._refresh()

    def _refresh(self) -> None:
        weights = self.calibrator.weights()
        if weights is None:
            return
        merged = dict(self.prior.modality_weights)
        merged.update(weights)
        dec = self.calibrator.decode_cost()
        cand = dataclasses.replace(
            self.prior, modality_weights=merged,
            decode_cost=self.prior.decode_cost if dec is None else dec)
        if self._weights_differ(self._current, cand):
            self._current = cand
            self._version += 1

    def _weights_differ(self, old: ServingCostModel,
                        new: ServingCostModel) -> bool:
        for m in new.modality_weights:
            ow = old.modality_weights.get(m, 1.0)
            nw = new.modality_weights[m]
            if abs(nw - ow) / max(abs(ow), abs(nw), 1e-12) > self.replan_tol:
                return True
        od, nd = old.decode_cost, new.decode_cost
        return abs(nd - od) / max(abs(od), abs(nd), 1e-12) > self.replan_tol

    # -- checkpointing --------------------------------------------------
    def state_dict(self) -> dict:
        """JSON-able dynamic state (serving replica handoff /
        checkpoint): calibrator window + currently served weights."""
        return {
            "calibrator": self.calibrator.state_dict(),
            "modality_weights": dict(self._current.modality_weights),
            "decode_cost": self._current.decode_cost,
            "version": self._version,
        }

    def load_state_dict(self, state) -> None:
        self.calibrator.load_state_dict(state["calibrator"])
        self._current = dataclasses.replace(
            self.prior,
            modality_weights=dict(state["modality_weights"]),
            decode_cost=float(state["decode_cost"]))
        self._version = int(state["version"])

    def summary(self) -> dict:
        return {
            "calibrated": self.calibrated,
            "version": self._version,
            "drift_events": self.drift_events,
            "prior_weights": dict(self.prior.modality_weights),
            "weights": dict(self._current.modality_weights),
            "prior_decode_cost": self.prior.decode_cost,
            "decode_cost": self._current.decode_cost,
        }
