"""Low-overhead per-phase, per-DP-shard trace capture.

Every balancing decision in this repo prices work through ``f(S)``
(:mod:`repro.core.cost_model`); this module records what the hardware
*actually* did so :mod:`repro.telemetry.calibrate` can close the loop.

A :class:`PhaseSample` pairs one mini-batch's feature vector

    [L, L^2/b, sum(l^2), b*max(l)^2]

(the shared basis of every f(S) variant -- see
``cost_model.FEATURE_NAMES``) with the measured wall time of executing
that batch on its shard.  Samples land in a fixed-capacity
:class:`TraceBuffer` ring (O(1) append, no allocation churn on the hot
path, oldest samples evicted), which hands the calibrator its (X, y)
regression window (:meth:`TraceBuffer.design_matrix`).  It is a sample
store, not a timeline: host spans and device phases are read from the
profiler's trace (:mod:`repro.obs.spans`).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Iterable

import numpy as np

from repro.core.cost_model import FEATURE_NAMES, N_FEATURES, length_features

__all__ = ["PhaseSample", "TraceBuffer"]


@dataclasses.dataclass(frozen=True)
class PhaseSample:
    """One (phase, shard) observation: features + measured wall time."""

    phase: str
    shard: int
    step: int
    features: np.ndarray  # (N_FEATURES,) float64
    wall_ms: float

    def __post_init__(self) -> None:
        f = np.asarray(self.features, dtype=np.float64).reshape(-1)
        if f.size != N_FEATURES:
            raise ValueError(
                f"features must have {N_FEATURES} entries {FEATURE_NAMES}, "
                f"got shape {f.shape}")
        object.__setattr__(self, "features", f)

    @classmethod
    def from_lengths(cls, phase: str, lengths, wall_ms: float, *,
                     shard: int = 0, step: int = 0, padding: bool = False,
                     ) -> "PhaseSample":
        return cls(phase=phase, shard=shard, step=step,
                   features=length_features(lengths, padding),
                   wall_ms=float(wall_ms))


class TraceBuffer:
    """Fixed-capacity ring buffer of :class:`PhaseSample`.

    Thread-safe: samples may be added from any thread while another
    reads a snapshot, so the ring pointer update and snapshot reads are
    taken under a lock."""

    def __init__(self, capacity: int = 4096) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._buf: list[PhaseSample | None] = [None] * capacity
        self._next = 0  # next write slot
        self._count = 0  # total samples ever added
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        return min(self._count, self.capacity)

    @property
    def total_added(self) -> int:
        return self._count

    @property
    def dropped(self) -> int:
        """Samples evicted by the ring (capacity overflow)."""
        return max(0, self._count - self.capacity)

    def add(self, sample: PhaseSample) -> None:
        with self._lock:
            self._buf[self._next] = sample
            self._next = (self._next + 1) % self.capacity
            self._count += 1

    def extend(self, samples: Iterable[PhaseSample]) -> None:
        for s in samples:
            self.add(s)

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._next = 0
            self._count = 0

    def samples(self, phase: str | None = None) -> list[PhaseSample]:
        """Oldest-first view, optionally filtered."""
        with self._lock:
            if self._count < self.capacity:
                ordered = self._buf[: self._count]
            else:
                ordered = self._buf[self._next:] + self._buf[: self._next]
        return [s for s in ordered
                if s is not None
                and (phase is None or s.phase == phase)]

    def phases(self) -> list[str]:
        seen: dict[str, None] = {}
        for s in self.samples():
            seen.setdefault(s.phase, None)
        return list(seen)

    # ------------------------------------------------------------------
    def design_matrix(self, phase: str) -> tuple[np.ndarray, np.ndarray]:
        """(X, y) for the calibrator: X (n, 4) features, y (n,) wall ms."""
        sel = self.samples(phase)
        if not sel:
            return np.zeros((0, N_FEATURES)), np.zeros(0)
        X = np.stack([s.features for s in sel])
        y = np.array([s.wall_ms for s in sel], dtype=np.float64)
        return X, y
