"""Process-wide metrics registry: named Counters / Gauges / Histograms.

Dependency-free (pure stdlib) instrumentation substrate for the whole
repo: the orchestrator, the training loop, the serving engine, and the
kernel call sites all record into ONE :class:`MetricsRegistry` (the
process default from :func:`get_registry`, or an explicit instance for
tests), and the exporters in :mod:`repro.obs.export` read it back
out.

Design points:

  * **Labels** are first-class: a metric family created with
    ``labels=("phase", "shard")`` holds one child per label-value tuple
    (``fam.labels(phase="llm", shard=0).inc()``), so per-phase /
    per-shard / per-modality series never need name mangling.
  * **Histograms** keep both fixed buckets (OpenMetrics ``_bucket``
    export) and a streaming :class:`QuantileSketch`, so p50/p95/p99 are
    available online without retaining the raw stream -- that is what
    turns the serving engine's TTFT/ITL means into real tail metrics.
  * Everything on the hot path is O(1) amortized and allocation-light;
    the <2% overhead budget is gated in
    ``benchmarks/observability_overhead.py``.

Thread safety: one lock per metric family (the serving engine and the
plan-ahead worker record concurrently with the consumer thread).
"""
from __future__ import annotations

import math
import threading
from typing import Iterable, Sequence

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricFamily",
    "MetricsRegistry",
    "QuantileSketch",
    "get_registry",
    "set_registry",
]


# ----------------------------------------------------------------------
# Streaming quantile sketch (Greenwald-Khanna).
# ----------------------------------------------------------------------
class QuantileSketch:
    """Greenwald-Khanna epsilon-approximate streaming quantiles.

    Maintains tuples ``(v, g, delta)`` such that for any query rank
    ``r`` the returned value's true rank is within ``eps * n`` of ``r``
    -- the classic GK invariant ``g + delta <= floor(2 * eps * n)``.
    Memory is O((1/eps) * log(eps * n)); inserts amortize to O(log)
    via a buffered batch insert.

    The rank-error bound is what the property tests in
    ``tests/test_obs.py`` verify against ``np.quantile`` on adversarial
    (sorted / reversed / constant / heavy-tailed) streams.
    """

    __slots__ = ("eps", "_tuples", "_n", "_buf", "_buf_cap")

    def __init__(self, eps: float = 0.005, buffer: int = 64) -> None:
        if not 0.0 < eps < 0.5:
            raise ValueError(f"eps must be in (0, 0.5), got {eps}")
        self.eps = float(eps)
        self._tuples: list[list[float]] = []  # [v, g, delta], sorted by v
        self._n = 0
        self._buf: list[float] = []
        self._buf_cap = max(1, int(buffer))

    # ------------------------------------------------------------------
    @property
    def n(self) -> int:
        return self._n + len(self._buf)

    def add(self, value: float) -> None:
        self._buf.append(float(value))
        if len(self._buf) >= self._buf_cap:
            self._drain()

    def extend(self, values: Iterable[float]) -> None:
        for v in values:
            self.add(v)

    def _drain(self) -> None:
        if not self._buf:
            return
        for v in sorted(self._buf):
            self._insert(v)
        self._buf.clear()
        self._compress()

    def _insert(self, v: float) -> None:
        t = self._tuples
        self._n += 1
        if not t or v < t[0][0]:
            t.insert(0, [v, 1.0, 0.0])
            return
        if v >= t[-1][0]:
            t.append([v, 1.0, 0.0])
            return
        # binary search for the first tuple with value > v
        lo, hi = 0, len(t)
        while lo < hi:
            mid = (lo + hi) // 2
            if t[mid][0] <= v:
                lo = mid + 1
            else:
                hi = mid
        cap = math.floor(2.0 * self.eps * self._n)
        t.insert(lo, [v, 1.0, max(0.0, cap - 1.0)])

    def _compress(self) -> None:
        t = self._tuples
        if len(t) < 3:
            return
        cap = math.floor(2.0 * self.eps * self._n)
        i = len(t) - 2
        while i >= 1:
            if t[i][1] + t[i + 1][1] + t[i + 1][2] <= cap:
                t[i + 1][1] += t[i][1]
                del t[i]
            i -= 1

    def quantile(self, q: float) -> float:
        """Value whose rank is within ``eps * n`` of ``ceil(q * n)``."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        self._drain()
        if self._n == 0:
            return float("nan")
        t = self._tuples
        target = max(1, math.ceil(q * self._n))  # 1-based target rank
        margin = self.eps * self._n
        rmin = 0.0
        prev_v = t[0][0]
        for v, g, delta in t:
            rmin += g
            if rmin + delta > target + margin:
                return prev_v
            prev_v = v
        return t[-1][0]

    def quantiles(self, qs: Sequence[float]) -> list[float]:
        return [self.quantile(q) for q in qs]

    # -- serialization (flight recorder / snapshots) --------------------
    def state_dict(self) -> dict:
        self._drain()
        return {"eps": self.eps, "n": self._n,
                "tuples": [list(t) for t in self._tuples]}

    @classmethod
    def from_state_dict(cls, state: dict) -> "QuantileSketch":
        sk = cls(eps=state["eps"])
        sk._n = int(state["n"])
        sk._tuples = [list(t) for t in state["tuples"]]
        return sk


# ----------------------------------------------------------------------
# Metric kinds.
# ----------------------------------------------------------------------
class Counter:
    """Monotone counter (export name gets a ``_total`` suffix)."""

    __slots__ = ("_value", "_lock")

    def __init__(self) -> None:
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Point-in-time value (set / add)."""

    __slots__ = ("_value", "_lock")

    def __init__(self) -> None:
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def add(self, amount: float) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                   500.0, 1000.0, float("inf"))


class Histogram:
    """Fixed buckets (OpenMetrics export) + a quantile sketch (tails).

    ``observe`` is the only hot-path call: one bucket bisect + one
    amortized sketch insert.  ``quantile(q)`` answers p50/p95/p99 with
    the GK rank-error guarantee; bucket counts are cumulative
    (``le``-style) at export time.
    """

    __slots__ = ("buckets", "_counts", "_sum", "_count", "_sketch", "_lock")

    def __init__(self, buckets: Sequence[float] = DEFAULT_BUCKETS,
                 eps: float = 0.005) -> None:
        bs = tuple(float(b) for b in buckets)
        if list(bs) != sorted(bs):
            raise ValueError("buckets must be sorted ascending")
        if not bs or bs[-1] != float("inf"):
            bs = bs + (float("inf"),)
        self.buckets = bs
        self._counts = [0] * len(bs)
        self._sum = 0.0
        self._count = 0
        self._sketch = QuantileSketch(eps=eps)
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            # linear scan is faster than bisect for the short tails that
            # dominate in practice; buckets are small tuples.
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    break
            self._sum += v
            self._count += 1
            self._sketch.add(v)

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def bucket_counts(self) -> list[tuple[float, int]]:
        """Cumulative (le, count) pairs, OpenMetrics style."""
        out, cum = [], 0
        with self._lock:
            for b, c in zip(self.buckets, self._counts):
                cum += c
                out.append((b, cum))
        return out

    def quantile(self, q: float) -> float:
        with self._lock:
            return self._sketch.quantile(q)

    def quantiles(self, qs: Sequence[float] = (0.5, 0.95, 0.99)) -> list[float]:
        with self._lock:
            return self._sketch.quantiles(qs)

    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class MetricFamily:
    """One named metric + its labeled children.

    A family with no label names has exactly one (unlabeled) child; a
    labeled family materializes children on first use.  Convenience
    pass-throughs (``inc`` / ``set`` / ``observe`` with label kwargs)
    keep call sites one-liners.
    """

    def __init__(self, name: str, kind: str, help: str = "",
                 labelnames: Sequence[str] = (), **metric_kw) -> None:
        if kind not in _KINDS:
            raise ValueError(f"unknown metric kind {kind!r}")
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self._metric_kw = metric_kw
        self._children: dict[tuple, object] = {}
        self._lock = threading.Lock()

    def labels(self, **labels) -> "Counter | Gauge | Histogram":
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(labels)} != declared "
                f"{sorted(self.labelnames)}")
        key = tuple(str(labels[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = _KINDS[self.kind](**self._metric_kw)
                self._children[key] = child
        return child

    # -- one-liner pass-throughs ----------------------------------------
    def inc(self, amount: float = 1.0, **labels) -> None:
        self.labels(**labels).inc(amount)

    def set(self, value: float, **labels) -> None:
        self.labels(**labels).set(value)

    def observe(self, value: float, **labels) -> None:
        self.labels(**labels).observe(value)

    def children(self) -> list[tuple[dict[str, str], object]]:
        """(labels dict, metric) pairs, insertion-ordered."""
        with self._lock:
            return [(dict(zip(self.labelnames, key)), child)
                    for key, child in self._children.items()]


class MetricsRegistry:
    """Named metric families; the exporters' single read surface."""

    def __init__(self) -> None:
        self._families: dict[str, MetricFamily] = {}
        self._lock = threading.Lock()

    def _family(self, name: str, kind: str, help: str,
                labelnames: Sequence[str], **kw) -> MetricFamily:
        # "_total" is an exposition-reserved suffix: the renderer appends
        # it to counters, and the strict OpenMetrics parser treats any
        # series carrying it as a counter.  Baking it into a family name
        # either double-suffixes (counters) or miscategorizes (gauges).
        if name.endswith("_total"):
            raise ValueError(
                f"metric name {name!r} must not end with '_total' "
                "(reserved exposition suffix; the renderer adds it to "
                "counters)")
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = MetricFamily(name, kind, help, labelnames, **kw)
                self._families[name] = fam
            elif fam.kind != kind or fam.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} re-registered as {kind}{tuple(labelnames)} "
                    f"(was {fam.kind}{fam.labelnames})")
        return fam

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> MetricFamily:
        return self._family(name, "counter", help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> MetricFamily:
        return self._family(name, "gauge", help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS,
                  eps: float = 0.005) -> MetricFamily:
        return self._family(name, "histogram", help, labels,
                            buckets=buckets, eps=eps)

    def families(self) -> list[MetricFamily]:
        with self._lock:
            return list(self._families.values())

    def get(self, name: str) -> MetricFamily | None:
        with self._lock:
            return self._families.get(name)

    def snapshot_counters(self, prefix: str = "") -> dict[str, float]:
        """Flat {name{labels}: value} view of every counter -- the
        ledger polls this to lay counter tracks on the step axis."""
        out: dict[str, float] = {}
        for fam in self.families():
            if fam.kind != "counter" or not fam.name.startswith(prefix):
                continue
            for labels, child in fam.children():
                key = fam.name
                if labels:
                    key += "{" + ",".join(f"{k}={v}" for k, v in
                                          sorted(labels.items())) + "}"
                out[key] = child.value
        return out


# ----------------------------------------------------------------------
# Process-wide default.
# ----------------------------------------------------------------------
_default_registry = MetricsRegistry()
_default_lock = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry (kernel hooks record here)."""
    return _default_registry


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Swap the process default (tests / multi-run isolation); returns
    the previous one."""
    global _default_registry
    with _default_lock:
        prev, _default_registry = _default_registry, registry
    return prev
