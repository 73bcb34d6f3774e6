"""Prefetching data pipeline with overlapped dispatcher computation.

Paper S6, 'Computation overhead overlapping': the Post-Balancing /
Node-wise / Composition *computation* needs only sequence lengths, which
are known as soon as the mini-batches are sampled -- so it runs inside
the prefetch worker, in parallel with the device's forward pass.  Only
the all-to-all *communication* stays on the critical path (inside the
jitted step).

``PrefetchingLoader`` runs sampling + ``plan_and_pack`` on a background
thread with a bounded queue.  With ``plan_ahead=True`` it goes one step
further: step k+1's phase plans (``orchestrator.plan_phases``) are
launched *before* step k is packed, so the dispatcher solve overlaps
both the worker's own packing and the consumer's forward pass -- the
per-step ``report.exposed_ms`` then measures how much dispatcher time
was actually left on the critical path (~0 when fully hidden).
``overlap_stats()`` aggregates it for the Table-2 analog.

Determinism contract (checkpoint resume): batch i's sampling RNG is
derived from ``(seed, i, attempt)`` -- never from wall time, thread
interleaving, or how many batches a previous consumer took.  A loader
constructed with ``start_index=i`` therefore replays the exact stream
an uninterrupted loader would have produced from batch i on, which is
what makes ``repro.checkpoint``'s save->resume loss trajectory bitwise
reproducible.  The retry path (capacity overflow on a pathological
draw) bumps ``attempt`` deterministically instead of consuming from a
shared stream.  ``cursor`` is the index of the next batch the consumer
will receive -- the value a checkpoint's ``DataCursor`` records.

Each resample is counted by the stream whose capacity overflowed
(``CapacityOverflow.stream``): per batch on its ``report.resamples``,
and on the orchestrator's metrics registry, where it has one, as
``loader_resamples{stream}``.  The worker's and the consumer's work are
``loader.*`` spans (:mod:`repro.obs.spans`), each tagged with the batch
index as ``step``.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator

import numpy as np

from repro.core.orchestrator import Capacities, MLLMGlobalOrchestrator
from repro.data.synthetic import Example, TaskMix, sample_examples
from repro.obs.spans import span

__all__ = ["PrefetchingLoader"]


class PrefetchingLoader:
    def __init__(
        self,
        orchestrator: MLLMGlobalOrchestrator,
        caps: Capacities,
        *,
        examples_per_instance: int,
        seed: int = 0,
        mix: TaskMix | None = None,
        modalities: tuple[str, ...] = ("vision", "audio"),
        sampler: Callable[[np.random.Generator, int], list[Example]] | None = None,
        depth: int = 2,
        plan_ahead: bool = True,
        start_index: int = 0,
    ) -> None:
        self.orch = orchestrator
        self.caps = caps
        self.per = examples_per_instance
        self.seed = seed
        self.start_index = start_index
        self.mix = mix
        self.modalities = modalities
        self.sampler = sampler
        self.plan_ahead = plan_ahead
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.solve_ms_total = 0.0
        self.exposed_ms_total = 0.0
        self.batches_produced = 0
        self.batches_consumed = 0
        reg = orchestrator.metrics
        self._c_resamples = None if reg is None else reg.counter(
            "loader_resamples", "draws resampled on a capacity overflow, by stream",
            labels=("stream",))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    @property
    def cursor(self) -> int:
        """Index of the next batch the consumer will receive -- what a
        checkpoint's ``DataCursor.batch_index`` records."""
        return self.start_index + self.batches_consumed

    def _batch_rng(self, index: int, attempt: int) -> np.random.Generator:
        """Batch ``index``'s deterministic RNG; ``attempt`` bumps on the
        (rare) capacity-overflow resample so retries stay replayable."""
        return np.random.default_rng((int(self.seed), int(index), int(attempt)))

    def _sample(self, index: int, attempt: int = 0) -> list[list[Example]]:
        # Each DP instance samples independently (batching randomness,
        # paper S2.3) -- post-balancing happens AFTER this step.  All d
        # instances draw sequentially from ONE per-index stream, so the
        # flattened example list depends only on (seed, index, attempt,
        # d*per): an elastic resume that re-splits the same global batch
        # across a different d sees the identical example multiset.
        rng = self._batch_rng(index, attempt)
        out = []
        for _ in range(self.orch.d):
            if self.sampler is not None:
                out.append(self.sampler(rng, self.per))
            else:
                out.append(sample_examples(rng, self.per, self.mix,
                                           self.modalities))
        return out

    def _worker(self) -> None:
        index = self.start_index
        attempts = 0
        overflows: dict[str, int] = {}  # this batch's resamples by stream
        pending = None  # (index, examples, PlanAheadHandle) for index+1
        while not self._stop.is_set():
            t0 = time.perf_counter()
            if pending is not None and pending[0] == index:
                _, examples, handle = pending
                pending = None
            else:
                with span("loader.sample", step=index):
                    examples = self._sample(index, attempts)
                handle = (self.orch.plan_ahead(examples, self.caps, step=index)
                          if self.plan_ahead else None)
            if self.plan_ahead and (pending is None or pending[0] != index + 1):
                # Launch step k+1's plans before packing step k: the
                # solve overlaps our packing of step k AND the consumer's
                # forward pass, so by the time the worker loops around
                # the plans are ready (exposed ~ 0).  On a retry of step
                # k the still-valid pending plan for k+1 is kept as is.
                with span("loader.sample", step=index + 1):
                    nxt = self._sample(index + 1)
                pending = (index + 1, nxt,
                           self.orch.plan_ahead(nxt, self.caps, step=index + 1))
            try:
                rng = self._batch_rng(index, attempts)
                plans = exposed_ms = None
                if handle is not None:
                    with span("loader.plan_wait", step=index):
                        plans, exposed_ms = handle.result()
                with span("loader.pack", step=index):
                    batch, report = self.orch.plan_and_pack(
                        examples, self.caps, rng, plans,
                        exposed_ms=exposed_ms, step=index)
            except ValueError as err:
                # Capacity overflow on a pathological draw: retry the
                # SAME index with a bumped attempt counter (replayable).
                stream = getattr(err, "stream", "other")
                overflows[stream] = overflows.get(stream, 0) + 1
                if self._c_resamples is not None:
                    self._c_resamples.inc(stream=stream)
                attempts += 1
                continue
            report.resamples, overflows = overflows, {}
            dt = (time.perf_counter() - t0) * 1e3
            self.solve_ms_total += report.solve_ms
            self.exposed_ms_total += report.exposed_ms
            self.batches_produced += 1
            item = (batch, report, dt)
            try:
                self.q.put_nowait(item)
            except queue.Full:
                with span("loader.queue_full", step=index):
                    while not self._stop.is_set():
                        try:
                            self.q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
            index += 1
            attempts = 0

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        with span("loader.next", step=self.cursor):
            item = self.q.get()
        self.batches_consumed += 1
        return item

    def overlap_stats(self) -> dict[str, float]:
        n = max(self.batches_produced, 1)
        return {
            "batches": self.batches_produced,
            "mean_solve_ms": self.solve_ms_total / n,
            "mean_exposed_ms": self.exposed_ms_total / n,
        }

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)
