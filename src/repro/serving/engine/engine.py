"""Continuous-batching engine loop: schedule -> prefill -> decode.

``Engine.step()`` asks the :class:`~repro.serving.engine.scheduler.
Scheduler` for a :class:`StepPlan` under the token budget, runs the
admitted prompts through ONE jitted chunked-prefill call
(``serving.serve_step.make_prefill_step``), runs the running sequences
through ONE jitted paged decode call (``make_serve_step(paged=True)``),
and streams sampled tokens into each request.  Sequences join and leave
the decode batch every step (iteration-level scheduling), so a finished
request's slot is recycled immediately instead of idling until the
slowest member of a fixed batch completes.

Exactness: prefill is a scan of the very same paged decode step, and
paged reads gather bit-identical dense views (see
:mod:`repro.models.decode`), so with greedy sampling every request's
output stream is identical to running it alone through the dense-cache
``serve_step`` path -- preemption included (recompute teacher-forces
the tokens generated so far).

``EngineReport`` mirrors ``OrchestratorReport``: throughput, TTFT, ITL,
pool occupancy, budget utilization, and a padded-compute ``token_slots``
account (the deterministic cost the serving benchmark compares against
the fixed-batch baseline).

``MultiReplicaEngine`` runs N engines behind one queue, post-balancing
each arrival burst across replicas with the training dispatcher
(:func:`~repro.serving.engine.scheduler.assign_replicas`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EngineConfig, ModelConfig
from repro.core.cost_model import ServingCostModel
from repro.obs.registry import QuantileSketch
from repro.obs.spans import span
from repro.serving.engine.kv_pool import PagedKVPool
from repro.serving.engine.request import Request, RequestState, SequenceState
from repro.serving.engine.scheduler import (
    Scheduler,
    StepPlan,
    assign_replicas,
    serving_cost_model,
)
from repro.serving.serve_step import make_prefill_step, make_serve_step
from repro.utils import round_up

__all__ = ["Engine", "MultiReplicaEngine", "EngineReport", "StepTiming"]


@dataclasses.dataclass
class StepTiming:
    """One engine step's wall-time breakdown (host clock).

    ``prefill_ms`` / ``decode_ms`` cover the jitted calls (all prefill
    sub-batches of the step, resp. the one decode batch);
    ``schedule_ms`` is the scheduler's host time.  The serving
    calibrator regresses these against the step's token composition."""

    step: int
    schedule_ms: float
    prefill_ms: float
    decode_ms: float
    n_prefill_seqs: int
    prefill_tokens: int  # tokens prefilled this step (recompute included)
    n_decode_seqs: int
    # Attribution inputs for the MFU-gap waterfall (repro.obs.decompose):
    # preemptions charged to this step's schedule and the recomputed
    # (post-preemption re-prefill) share of prefill_tokens.
    n_preempted: int = 0
    recompute_tokens: int = 0

    def to_state_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_state_dict(d: dict) -> "StepTiming":
        return StepTiming(**d)


@dataclasses.dataclass
class EngineReport:
    """Per-run serving metrics (the ``OrchestratorReport`` analog)."""

    n_requests: int
    n_finished: int
    n_steps: int
    n_preemptions: int
    prompt_tokens: int  # first-time prefill tokens (== sum of prompt lens)
    recompute_tokens: int  # re-prefilled context after preemption (overhead)
    generated_tokens: int
    wall_s: float
    throughput_tok_s: float  # generated tokens / wall second
    token_slots: int  # padded (sequence, position) compute slots spent
    slot_efficiency: float  # useful tokens / token_slots
    ttft_steps_mean: float  # arrival -> first token, in engine steps
    ttft_steps_p95: float
    ttft_s_mean: float
    itl_steps_mean: float  # steps per generated token after the first
    occupancy_mean: float  # KV-pool block occupancy, sampled per step
    occupancy_max: float
    budget_util_mean: float  # budget_used / token_budget per step
    # Sketch-backed tail latencies (Greenwald-Khanna, repro.obs.registry):
    # means alone hide preemption-induced tails -- a preempted request
    # re-prefills its whole context, which shows up only at p95/p99.
    ttft_steps_p50: float = 0.0
    ttft_steps_p99: float = 0.0
    itl_steps_p50: float = 0.0
    itl_steps_p95: float = 0.0
    itl_steps_p99: float = 0.0
    # Phase-level wall-time breakdown (sums over steps; the per-step
    # rows live in ``Engine.step_timings``).  prefill_ms_mean /
    # decode_ms_mean average over the steps that RAN that phase.
    schedule_s_total: float = 0.0
    prefill_s_total: float = 0.0
    decode_s_total: float = 0.0
    prefill_steps: int = 0  # steps with at least one prefill sub-batch
    decode_steps: int = 0  # steps with a decode batch
    prefill_ms_mean: float = 0.0
    decode_ms_mean: float = 0.0

    def summary(self) -> str:
        return (
            f"requests {self.n_finished}/{self.n_requests} finished in "
            f"{self.n_steps} steps ({self.n_preemptions} preemptions)\n"
            f"tokens   {self.prompt_tokens} prompt + {self.generated_tokens} "
            f"generated (+{self.recompute_tokens} recomputed); "
            f"{self.throughput_tok_s:.1f} tok/s wall, "
            f"{self.token_slots} compute slots "
            f"({self.slot_efficiency:.1%} useful)\n"
            f"latency  TTFT {self.ttft_steps_mean:.1f} steps mean / "
            f"{self.ttft_steps_p50:.1f}/{self.ttft_steps_p95:.1f}/"
            f"{self.ttft_steps_p99:.1f} p50/p95/p99 "
            f"({self.ttft_s_mean * 1e3:.1f} ms); "
            f"ITL {self.itl_steps_mean:.2f} steps mean / "
            f"{self.itl_steps_p50:.2f}/{self.itl_steps_p95:.2f}/"
            f"{self.itl_steps_p99:.2f} p50/p95/p99\n"
            f"pool     occupancy {self.occupancy_mean:.1%} mean / "
            f"{self.occupancy_max:.1%} max; budget {self.budget_util_mean:.1%}\n"
            f"phases   prefill {self.prefill_s_total * 1e3:.1f} ms over "
            f"{self.prefill_steps} steps ({self.prefill_ms_mean:.2f} ms/step); "
            f"decode {self.decode_s_total * 1e3:.1f} ms over "
            f"{self.decode_steps} steps ({self.decode_ms_mean:.2f} ms/step)"
        )


def _sketch_quantiles(xs: Sequence[float], qs: Sequence[float]) -> list[float]:
    """Percentiles via the streaming sketch (the same estimator the live
    registry histograms use, so report numbers match scraped metrics).
    Monotone in q by construction."""
    if not len(xs):
        return [0.0] * len(qs)
    sk = QuantileSketch()
    sk.extend(float(x) for x in xs)
    return [sk.quantile(q) for q in qs]


def build_report(requests: Sequence[Request], *, n_steps: int, wall_s: float,
                 token_slots: int, prompt_tokens: int, recompute_tokens: int,
                 generated_tokens: int,
                 occupancy_samples: Sequence[float],
                 budget_fracs: Sequence[float],
                 step_timings: Sequence[StepTiming] = ()) -> EngineReport:
    finished = [r for r in requests if r.state is RequestState.FINISHED]
    ttft_steps = [r.first_token_step - r.arrival_step for r in finished
                  if r.first_token_step is not None]
    ttft_s = [r.first_token_time - r.arrival_time for r in finished
              if r.first_token_time is not None]
    itl = [(r.finish_step - r.first_token_step) / (len(r.output_tokens) - 1)
           for r in finished
           if len(r.output_tokens) > 1 and r.finish_step is not None]
    # Recomputed context is real compute but NOT useful output -- it is
    # preemption overhead and must not inflate slot_efficiency.
    useful = prompt_tokens + generated_tokens
    pf = [t for t in step_timings if t.n_prefill_seqs]
    dc = [t for t in step_timings if t.n_decode_seqs]
    ttft_p50, ttft_p95, ttft_p99 = _sketch_quantiles(
        ttft_steps, (0.5, 0.95, 0.99))
    itl_p50, itl_p95, itl_p99 = _sketch_quantiles(itl, (0.5, 0.95, 0.99))
    return EngineReport(
        n_requests=len(requests),
        n_finished=len(finished),
        n_steps=n_steps,
        n_preemptions=sum(r.n_preemptions for r in requests),
        prompt_tokens=prompt_tokens,
        recompute_tokens=recompute_tokens,
        generated_tokens=generated_tokens,
        wall_s=wall_s,
        throughput_tok_s=generated_tokens / wall_s if wall_s > 0 else 0.0,
        token_slots=token_slots,
        slot_efficiency=useful / token_slots if token_slots else 0.0,
        ttft_steps_mean=float(np.mean(ttft_steps)) if ttft_steps else 0.0,
        ttft_steps_p95=ttft_p95,
        ttft_s_mean=float(np.mean(ttft_s)) if ttft_s else 0.0,
        itl_steps_mean=float(np.mean(itl)) if itl else 0.0,
        ttft_steps_p50=ttft_p50,
        ttft_steps_p99=ttft_p99,
        itl_steps_p50=itl_p50,
        itl_steps_p95=itl_p95,
        itl_steps_p99=itl_p99,
        occupancy_mean=float(np.mean(occupancy_samples)) if len(occupancy_samples) else 0.0,
        occupancy_max=float(np.max(occupancy_samples)) if len(occupancy_samples) else 0.0,
        budget_util_mean=float(np.mean(budget_fracs)) if len(budget_fracs) else 0.0,
        schedule_s_total=sum(t.schedule_ms for t in step_timings) * 1e-3,
        prefill_s_total=sum(t.prefill_ms for t in step_timings) * 1e-3,
        decode_s_total=sum(t.decode_ms for t in step_timings) * 1e-3,
        prefill_steps=len(pf),
        decode_steps=len(dc),
        prefill_ms_mean=float(np.mean([t.prefill_ms for t in pf])) if pf else 0.0,
        decode_ms_mean=float(np.mean([t.decode_ms for t in dc])) if dc else 0.0,
    )


class Engine:
    """One continuous-batching replica over one paged KV pool."""

    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig, params, *,
                 sample_fn: Callable | None = None,
                 attention_backend: str | None = None,
                 rng_key=None,
                 cost_model: ServingCostModel | None = None,
                 replica_id: int = 0,
                 jit_steps: tuple | None = None,
                 metrics=None):
        if cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"engine serves dense/moe/vlm families, not {cfg.family!r}")
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.params = params
        self.replica_id = replica_id
        # Logical per-sequence cache length: the SWA ring needs only the
        # window (but never less -- a smaller ring would silently
        # truncate attention vs the dense path); everything else must
        # hold prompt + generation.
        if cfg.sliding_window and engine_cfg.max_model_len < cfg.sliding_window:
            raise ValueError(
                f"max_model_len={engine_cfg.max_model_len} is smaller than "
                f"sliding_window={cfg.sliding_window}; the ring must cover "
                f"the full window")
        self.seq_slots = cfg.sliding_window or engine_cfg.max_model_len
        if self.seq_slots % engine_cfg.block_size:
            raise ValueError(
                f"per-sequence cache length {self.seq_slots} (sliding window "
                f"or max_model_len) must be a multiple of "
                f"block_size={engine_cfg.block_size}")
        self.table_width = self.seq_slots // engine_cfg.block_size
        self.pool = PagedKVPool(cfg, num_blocks=engine_cfg.num_blocks,
                                block_size=engine_cfg.block_size)
        self.scheduler = Scheduler(cost_model or serving_cost_model(cfg),
                                   engine_cfg)
        # ``jit_steps`` lets MultiReplicaEngine share one (prefill,
        # decode) pair of jitted callables -- and their XLA compile
        # caches -- across replicas instead of compiling per replica.
        self._prefill, self._decode = jit_steps or (
            jax.jit(make_prefill_step(
                cfg, attention_backend=attention_backend, sample_fn=sample_fn)),
            jax.jit(make_serve_step(
                cfg, attention_backend=attention_backend, sample_fn=sample_fn,
                paged=True)),
        )
        self._key = rng_key  # None = deterministic (greedy) path
        self._rng_calls = 0  # folded into the key once per jitted call
        # Shapes this replica has already run through the jitted steps:
        # the FIRST call per shape includes XLA compilation (seconds vs
        # milliseconds steady-state) and must not be fed to the serving
        # calibrator as a timing sample.
        self._warm_prefill_shapes: set[tuple[int, int]] = set()
        self._warm_decode_shapes: set[int] = set()

        self.waiting: list[SequenceState] = []
        self.running: list[SequenceState] = []
        self.requests: list[Request] = []
        self.plans: list[StepPlan] = []
        self.step_timings: list[StepTiming] = []
        self.n_steps = 0
        self.token_slots = 0
        self.prompt_tokens = 0
        self.recompute_tokens = 0
        self.generated_tokens = 0
        self.occupancy_samples: list[float] = []
        self.budget_fracs: list[float] = []
        self._wall_s = 0.0
        # Observability: an optional MetricsRegistry (repro.obs.registry)
        # receives the SLO series live -- TTFT / per-request ITL / pool
        # occupancy as replica-labeled histograms whose sketch gives the
        # same p50/p95/p99 the end-of-run EngineReport computes.
        self.metrics = metrics
        if metrics is not None:
            step_buckets = (1, 2, 4, 8, 16, 32, 64, 128, 256)
            self._h_ttft = metrics.histogram(
                "serving_ttft_steps", "arrival to first token, engine steps",
                labels=("replica",), buckets=step_buckets)
            self._h_itl = metrics.histogram(
                "serving_itl_steps", "per-request mean inter-token steps",
                labels=("replica",), buckets=step_buckets)
            self._h_occ = metrics.histogram(
                "serving_occupancy_frac", "KV-pool block occupancy per step",
                labels=("replica",),
                buckets=tuple(i / 10 for i in range(1, 11)))
            self._c_preempt = metrics.counter(
                "serving_preemptions", "sequences preempted by the scheduler",
                labels=("replica",))
            self._n_preempt_seen = 0
        else:
            self._h_ttft = self._h_itl = self._h_occ = self._c_preempt = None

    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def submit(self, request: Request) -> None:
        """Queue a request (WAITING).  Prompt + generation must fit the
        logical cache unless the model's sliding window bounds reads."""
        total = request.prompt_len + request.max_new_tokens
        if self.cfg.sliding_window is None and total > self.seq_slots:
            raise ValueError(
                f"request {request.req_id}: prompt+max_new={total} exceeds "
                f"max_model_len={self.seq_slots}")
        # Reject up front what no amount of preemption could ever place
        # (a too-big head would livelock the strict-FIFO queue).
        need = self.pool.blocks_for_slots(min(total, self.seq_slots))
        if need > self.pool.usable_blocks:
            raise ValueError(
                f"request {request.req_id}: needs {need} KV blocks, pool has "
                f"{self.pool.usable_blocks} total")
        request.replica = self.replica_id
        request.arrival_time = time.perf_counter()  # wall clock domain
        self.requests.append(request)
        self.waiting.append(SequenceState(request))

    # ------------------------------------------------------------------
    def step(self) -> StepPlan:
        """One engine iteration: schedule -> batched prefill -> batched
        decode -> lifecycle bookkeeping.  Returns the step's plan."""
        t0 = time.perf_counter()
        step = self.n_steps
        pre_recompute = self.recompute_tokens
        pre_preempt = sum(r.n_preemptions for r in self.requests)
        plan = self.scheduler.schedule(step, self.waiting, self.running,
                                       self.pool, seq_slots=self.seq_slots)
        t1 = time.perf_counter()
        prefill_tokens = 0
        if plan.prefill:
            with span("engine.prefill", step=step):
                prefill_tokens = self._run_prefill(plan.prefill, step)
        t2 = time.perf_counter()
        if plan.decode:
            with span("engine.decode", step=step):
                self._run_decode(plan.decode, step)
        t3 = time.perf_counter()
        n_preempted = sum(r.n_preemptions for r in self.requests) - pre_preempt
        self.step_timings.append(StepTiming(
            step=step,
            schedule_ms=(t1 - t0) * 1e3,
            prefill_ms=(t2 - t1) * 1e3,
            decode_ms=(t3 - t2) * 1e3,
            n_prefill_seqs=len(plan.prefill),
            prefill_tokens=prefill_tokens,
            n_decode_seqs=len(plan.decode),
            n_preempted=n_preempted,
            recompute_tokens=self.recompute_tokens - pre_recompute))
        self.n_steps += 1
        self.plans.append(plan)
        self.occupancy_samples.append(self.pool.occupancy)
        self.budget_fracs.append(plan.budget_used / plan.budget)
        self._wall_s += time.perf_counter() - t0
        if self._h_occ is not None:
            self._h_occ.observe(self.pool.occupancy, replica=self.replica_id)
            if n_preempted > 0:
                self._c_preempt.inc(n_preempted, replica=self.replica_id)
        return plan

    def _prefill_groups(self, seqs: list[SequenceState],
                        prompts: list[np.ndarray]) -> list[list[int]]:
        """Split one step's admitted prefills into low-padding
        sub-batches: sort by prompt length (descending) and cut a new
        group whenever padding the next prompt up to the group's padded
        max would cost more than ``prefill_waste`` extra slots per
        useful token (padded > useful * (1 + prefill_waste)) --
        Algorithm 2's bounded padded batches applied to the prefill
        batch dimension."""
        ecfg = self.engine_cfg
        order = sorted(range(len(seqs)), key=lambda i: -prompts[i].size)
        groups: list[list[int]] = []
        cur: list[int] = []
        tp = useful = 0
        for i in order:
            n = int(prompts[i].size)
            if not cur:
                cur, tp, useful = [i], round_up(n, ecfg.prefill_pad), n
                continue
            if (len(cur) + 1) * tp > (useful + n) * (1.0 + ecfg.prefill_waste):
                groups.append(cur)
                cur, tp, useful = [i], round_up(n, ecfg.prefill_pad), n
            else:
                cur.append(i)
                useful += n
        if cur:
            groups.append(cur)
        return groups

    def _next_key(self):
        """Fresh key per jitted call (deterministic across identical
        runs; never reused between prefill groups, decode calls, or
        replicas)."""
        if self._key is None:
            return None
        self._rng_calls += 1
        return jax.random.fold_in(
            jax.random.fold_in(self._key, self.replica_id), self._rng_calls)

    def _run_prefill(self, seqs: list[SequenceState], step: int) -> int:
        ecfg = self.engine_cfg
        observe = getattr(self.scheduler.cost_model, "observe_prefill", None)
        total_tokens = 0
        prompts = [s.request.full_prompt() for s in seqs]
        for group in self._prefill_groups(seqs, prompts):
            B = len(group)
            lens = np.array([prompts[i].size for i in group], np.int32)
            Tp = round_up(int(lens.max()), ecfg.prefill_pad)
            batch = np.zeros((B, Tp), np.int32)
            for row, i in enumerate(group):
                batch[row, : prompts[i].size] = prompts[i]
            bt = self.pool.table_array([seqs[i].seq_id for i in group],
                                       self.table_width)
            tg = time.perf_counter()
            first, _, cache = self._prefill(
                self.params, jnp.asarray(batch), jnp.asarray(lens),
                self.pool.cache, jnp.asarray(bt), self._next_key())
            self.pool.cache = cache
            first = np.asarray(first)
            now = time.perf_counter()
            total_tokens += int(lens.sum())
            warm = (B, Tp) in self._warm_prefill_shapes
            self._warm_prefill_shapes.add((B, Tp))
            if observe is not None and warm:
                # Feed the serving calibrator this sub-batch's token
                # composition (generated-so-far recompute tokens count
                # as text, matching Scheduler.request_cost).  Cold
                # shapes are skipped: their wall time is XLA compile.
                counts: dict[str, int] = {"text": 0}
                for i in group:
                    req = seqs[i].request
                    for m, n in req.modality_tokens.items():
                        counts[m] = counts.get(m, 0) + int(n)
                    counts["text"] += int(prompts[i].size
                                          - sum(req.modality_tokens.values()))
                observe(counts, (now - tg) * 1e3, step=step)
            for row, i in enumerate(group):
                # A recompute (post-preemption) re-prefills its whole
                # context; only a first admission counts as useful
                # prompt work.
                if seqs[i].request.first_token_step is None:
                    self.prompt_tokens += int(lens[row])
                else:
                    self.recompute_tokens += int(lens[row])
                seqs[i].t = int(lens[row])
                self._deliver(seqs[i], int(first[row, 0]), step, now)
            self.token_slots += B * Tp
        return total_tokens

    def _run_decode(self, seqs: list[SequenceState], step: int) -> None:
        ecfg = self.engine_cfg
        B = round_up(len(seqs), ecfg.decode_pad)
        tokens = np.zeros((B, 1), np.int32)
        t_vec = np.full(B, -1, np.int32)
        for i, seq in enumerate(seqs):
            tokens[i, 0] = seq.last_token
            t_vec[i] = seq.t
        bt = self.pool.table_array([s.seq_id for s in seqs], self.table_width)
        if B > len(seqs):
            bt = np.concatenate(
                [bt, np.zeros((B - len(seqs), self.table_width), np.int32)])
        tg = time.perf_counter()
        nxt, _, cache = self._decode(
            self.params, jnp.asarray(tokens), self.pool.cache,
            jnp.asarray(bt), jnp.asarray(t_vec), self._next_key())
        self.pool.cache = cache
        nxt = np.asarray(nxt)
        now = time.perf_counter()
        warm = B in self._warm_decode_shapes
        self._warm_decode_shapes.add(B)
        observe = getattr(self.scheduler.cost_model, "observe_decode", None)
        if observe is not None and warm:  # cold shape = XLA compile time
            # Regress on the PADDED row count: that is what the jitted
            # call computed, so the fitted per-row cost is fill-level
            # unbiased (an active seq occupies ~1 padded row).
            observe(B, (now - tg) * 1e3, step=step)
        for i, seq in enumerate(seqs):
            seq.t += 1
            self._deliver(seq, int(nxt[i, 0]), step, now)
        self.token_slots += B

    def _deliver(self, seq: SequenceState, token: int, step: int, now: float) -> None:
        seq.last_token = token
        req = seq.request
        first = req.first_token_step is None
        req.record_token(token, step, now)
        self.generated_tokens += 1
        if first and self._h_ttft is not None:
            self._h_ttft.observe(step - req.arrival_step,
                                 replica=self.replica_id)
        if req.done:
            req.finish(step, now)
            if (self._h_itl is not None and len(req.output_tokens) > 1
                    and req.finish_step is not None):
                itl = ((req.finish_step - req.first_token_step)
                       / (len(req.output_tokens) - 1))
                self._h_itl.observe(itl, replica=self.replica_id)
            self.pool.free(seq.seq_id)
            self.running.remove(seq)

    # ------------------------------------------------------------------
    # Snapshot / restore: scheduler + request lifecycle state.  KV pages
    # are deliberately NOT serialized -- a restored in-flight sequence
    # re-enters through the SAME preemption-recompute path the scheduler
    # uses under pool pressure (full_prompt() teacher-forces the tokens
    # generated so far), so with greedy sampling the continued output
    # stream is bitwise the stream an uninterrupted engine produces.
    def snapshot(self) -> dict:
        """JSON-able engine state: every request's lifecycle, the
        scheduler queues (by req_id), counters, and per-step timings."""
        return {
            "replica_id": self.replica_id,
            "n_steps": self.n_steps,
            "token_slots": self.token_slots,
            "prompt_tokens": self.prompt_tokens,
            "recompute_tokens": self.recompute_tokens,
            "generated_tokens": self.generated_tokens,
            "occupancy_samples": [float(x) for x in self.occupancy_samples],
            "budget_fracs": [float(x) for x in self.budget_fracs],
            "wall_s": self._wall_s,
            "rng_calls": self._rng_calls,
            "requests": [r.to_state_dict() for r in self.requests],
            "waiting": [s.seq_id for s in self.waiting],
            "running": [s.seq_id for s in self.running],
            "step_timings": [t.to_state_dict() for t in self.step_timings],
            "cost_model": (self.scheduler.cost_model.state_dict()
                           if hasattr(self.scheduler.cost_model,
                                      "state_dict") else None),
        }

    def restore(self, snap: dict) -> None:
        """Rebuild a drained replica's state from :meth:`snapshot`.

        Must be called on a fresh (empty) engine.  Former RUNNING
        sequences are re-queued WAITING through the recompute path;
        their KV pages are regenerated on re-admission."""
        if self.requests or self.waiting or self.running:
            raise ValueError("restore() needs a fresh engine "
                             "(this one already has requests)")
        if int(snap["replica_id"]) != self.replica_id:
            raise ValueError(
                f"snapshot is replica {snap['replica_id']}, this engine "
                f"is replica {self.replica_id} (use export_unfinished/"
                f"admit_serialized to MOVE work between replicas)")
        self.n_steps = int(snap["n_steps"])
        self.token_slots = int(snap["token_slots"])
        self.prompt_tokens = int(snap["prompt_tokens"])
        self.recompute_tokens = int(snap["recompute_tokens"])
        self.generated_tokens = int(snap["generated_tokens"])
        self.occupancy_samples = list(snap["occupancy_samples"])
        self.budget_fracs = list(snap["budget_fracs"])
        self._wall_s = float(snap["wall_s"])
        self._rng_calls = int(snap["rng_calls"])
        self.step_timings = [StepTiming.from_state_dict(t)
                             for t in snap["step_timings"]]
        cm_state = snap.get("cost_model")
        if cm_state is not None and hasattr(self.scheduler.cost_model,
                                           "load_state_dict"):
            self.scheduler.cost_model.load_state_dict(cm_state)
        was_running = set(snap["running"])
        for d in snap["requests"]:
            self._admit_restored(Request.from_state_dict(d),
                                 recompute=d["req_id"] in was_running)

    def _admit_restored(self, req: Request, *, recompute: bool) -> None:
        """One shared admission path for snapshot restore AND replica
        handoff: an in-flight request goes through the state machine's
        preemption transition (DECODE -> WAITING recompute), exactly as
        the scheduler evicts under pool pressure."""
        req.replica = self.replica_id
        self.requests.append(req)
        if req.state is RequestState.FINISHED:
            return
        if req.state is RequestState.DECODE and recompute:
            req.preempt()
        elif req.state is not RequestState.WAITING:
            # PREFILL never survives a step boundary; normalize anything
            # unexpected to WAITING without touching preemption counts.
            req.state = RequestState.WAITING
        seq = SequenceState(req)
        seq.reset()
        self.waiting.append(seq)

    def export_unfinished(self) -> list[dict]:
        """Drain this replica: serialize and REMOVE every unfinished
        request (blocks freed), leaving finished history in place for
        reporting.  Feed the result to another replica's
        :meth:`admit_serialized` -- together they are the handoff path
        ``MultiReplicaEngine.handoff`` uses."""
        out = []
        for seq in list(self.running):
            self.pool.free(seq.seq_id)
            self.running.remove(seq)
            if seq.request.state is RequestState.DECODE:
                seq.request.preempt()  # shared recompute transition
            out.append(seq.request.to_state_dict())
            self.requests.remove(seq.request)
        for seq in list(self.waiting):
            self.waiting.remove(seq)
            out.append(seq.request.to_state_dict())
            self.requests.remove(seq.request)
        return out

    def admit_serialized(self, reqs: Sequence[dict]) -> None:
        """Admit serialized requests (from :meth:`export_unfinished` or
        an external queue) through the shared restore path."""
        for d in reqs:
            self._admit_restored(Request.from_state_dict(d),
                                 recompute=False)

    # ------------------------------------------------------------------
    def run(self, requests: Sequence[Request] = (), *,
            max_steps: int = 100_000) -> EngineReport:
        """Drive to completion: submit each request when the step clock
        reaches its ``arrival_step``, then step until idle."""
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.req_id))
        while pending or self.has_work:
            while pending and pending[0].arrival_step <= self.n_steps:
                self.submit(pending.pop(0))
            self.step()
            if self.n_steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps "
                    f"({len(self.waiting)} waiting, {len(self.running)} running)")
        return self.report()

    def report(self) -> EngineReport:
        return build_report(
            self.requests, n_steps=self.n_steps, wall_s=self._wall_s,
            token_slots=self.token_slots, prompt_tokens=self.prompt_tokens,
            recompute_tokens=self.recompute_tokens,
            generated_tokens=self.generated_tokens,
            occupancy_samples=self.occupancy_samples,
            budget_fracs=self.budget_fracs,
            step_timings=self.step_timings)


class MultiReplicaEngine:
    """N engine replicas behind one post-balanced admission queue.

    Each arrival burst (requests sharing an ``arrival_step``) is
    assigned across replicas by :func:`assign_replicas` -- the paper's
    post-balancing applied to the waiting queue, minimizing the
    straggler replica's weighted admission load."""

    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig, params,
                 **engine_kw):
        self.engine_cfg = engine_cfg
        self.cost_model = engine_kw.pop("cost_model", None) or serving_cost_model(cfg)
        shared = jax.jit(make_prefill_step(
            cfg, attention_backend=engine_kw.get("attention_backend"),
            sample_fn=engine_kw.get("sample_fn"))), jax.jit(make_serve_step(
            cfg, attention_backend=engine_kw.get("attention_backend"),
            sample_fn=engine_kw.get("sample_fn"), paged=True))
        self.engines = [
            Engine(cfg, engine_cfg, params, cost_model=self.cost_model,
                   replica_id=i, jit_steps=shared, **engine_kw)
            for i in range(engine_cfg.replicas)
        ]
        self.assignment_loads: list[np.ndarray] = []

    @property
    def has_work(self) -> bool:
        return any(e.has_work for e in self.engines)

    def submit_batch(self, requests: Sequence[Request]) -> np.ndarray:
        """Post-balance one burst across replicas; returns the
        per-replica weighted-length loads of this assignment."""
        groups, loads = assign_replicas(
            requests, len(self.engines), self.cost_model,
            backend=self.engine_cfg.balancing_backend)
        for engine, group in zip(self.engines, groups):
            for r in group:
                engine.submit(r)
        self.assignment_loads.append(loads)
        return loads

    def step(self) -> None:
        # Idle replicas step too: local step clocks stay in lockstep
        # with the global arrival clock (TTFT-in-steps consistency).
        for e in self.engines:
            e.step()

    # ------------------------------------------------------------------
    def handoff(self, src: int, dst: int) -> int:
        """Drain replica ``src`` and move its unfinished requests to
        ``dst`` -- the replica-failure / rolling-restart path.

        Routed entirely through ``Engine.export_unfinished`` /
        ``Engine.admit_serialized``, i.e. the same snapshot/restore and
        preemption-recompute code paths the scheduler and the unit tests
        exercise: in-flight DECODE sequences take the state machine's
        preempt transition and re-prefill their full context at ``dst``
        (KV pages are never copied between pools).  Returns how many
        requests moved."""
        if src == dst:
            raise ValueError("handoff needs distinct src/dst replicas")
        moved = self.engines[src].export_unfinished()
        self.engines[dst].admit_serialized(moved)
        return len(moved)

    def snapshot(self) -> list[dict]:
        """Per-replica ``Engine.snapshot`` list (whole-cluster state)."""
        return [e.snapshot() for e in self.engines]

    def restore(self, snaps: Sequence[dict]) -> None:
        """Restore a whole-cluster snapshot onto fresh replicas."""
        if len(snaps) != len(self.engines):
            raise ValueError(
                f"snapshot has {len(snaps)} replicas, engine has "
                f"{len(self.engines)}")
        for e, snap in zip(self.engines, snaps):
            e.restore(snap)

    def run(self, requests: Sequence[Request] = (), *,
            max_steps: int = 100_000) -> EngineReport:
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.req_id))
        clock = 0
        while pending or self.has_work:
            burst = []
            while pending and pending[0].arrival_step <= clock:
                burst.append(pending.pop(0))
            if burst:
                self.submit_batch(burst)
            self.step()
            clock += 1
            if clock >= max_steps:
                raise RuntimeError(f"replicas did not drain in {max_steps} steps")
        return self.report()

    def report(self) -> EngineReport:
        requests = [r for e in self.engines for r in e.requests]
        occ = [s for e in self.engines for s in e.occupancy_samples]
        frac = [f for e in self.engines for f in e.budget_fracs]
        return build_report(
            requests,
            n_steps=max((e.n_steps for e in self.engines), default=0),
            wall_s=sum(e._wall_s for e in self.engines),
            token_slots=sum(e.token_slots for e in self.engines),
            prompt_tokens=sum(e.prompt_tokens for e in self.engines),
            recompute_tokens=sum(e.recompute_tokens for e in self.engines),
            generated_tokens=sum(e.generated_tokens for e in self.engines),
            occupancy_samples=occ, budget_fracs=frac,
            step_timings=[t for e in self.engines for t in e.step_timings])
