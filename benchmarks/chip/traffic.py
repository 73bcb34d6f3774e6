"""The one traffic generator.  A mix is a JSON file under ``traffic/``
that this module reads; nothing about a mix lives in code.

A training mix (``"kind": "examples"``) is a weighted list of tasks,
each with a length law per modality, the DP layout (``instances`` x
``examples_per_instance`` per step) and the capacity margin.  Batch
``b`` holds ``instances * examples_per_instance`` examples whose *sizes*
come from ``(size_seed, b)`` alone, so every run seed trains on the
same sequence of sizes; the run seed deals them to DP instances and
slots (another order, other contents).  Steps run back to back: a
training window has no arrivals.

A length spec is one of:

* ``{"lognormal": [median, sigma], "clip": [lo, hi]}``
* ``{"choice": [a, b, ...]}``
* ``{"integers": [lo, hi], "times": k}``  (``lo <= n < hi``, times k)
* ``{"of": "<modality>", "normal": [mean, sd], "min": m}``  (a multiple
  of another modality's length, at least m)
"""
from __future__ import annotations

import dataclasses
import json
import threading
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
MODALITIES = ("vision", "audio", "text")


def load(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng_for(*key: int) -> np.random.Generator:
    """A generator keyed by non-negative integers (run seeds may exceed
    32 bits; they are reduced mod 2**63, never hashed)."""
    return np.random.default_rng([int(k) % (1 << 63) for k in key])


def draw_length(rng: np.random.Generator, spec: dict, done: dict) -> int:
    if "lognormal" in spec:
        median, sigma = spec["lognormal"]
        lo, hi = spec["clip"]
        return int(np.clip(rng.lognormal(np.log(median), sigma), lo, hi))
    if "choice" in spec:
        return int(rng.choice(spec["choice"]))
    if "integers" in spec:
        lo, hi = spec["integers"]
        return int(rng.integers(lo, hi)) * int(spec.get("times", 1))
    if "of" in spec:
        mean, sd = spec["normal"]
        return max(int(spec["min"]), int(done[spec["of"]] * rng.normal(mean, sd)))
    raise ValueError(f"unknown length spec {spec}")


# ----------------------------------------------------------------------
# Training examples.
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExampleSize:
    task: str
    text: int
    vision: int  # encoder-input tokens (0 = absent)
    audio: int
    order: tuple[str, ...]


def draw_example(rng: np.random.Generator, mix: dict) -> ExampleSize:
    tasks = mix["tasks"]
    p = np.array([t["weight"] for t in tasks], np.float64)
    task = tasks[int(rng.choice(len(tasks), p=p / p.sum()))]
    done = {m: 0 for m in MODALITIES}
    for m in MODALITIES:
        if m in task:
            done[m] = draw_length(rng, task[m], done)
    return ExampleSize(task["name"], done["text"], done["vision"],
                       done["audio"], tuple(task["order"]))


def batch_sizes(mix: dict, b: int) -> list[ExampleSize]:
    """Batch ``b``'s example sizes: a function of the mix alone."""
    rng = rng_for(mix["size_seed"], b)
    n = mix["instances"] * mix["examples_per_instance"]
    return [draw_example(rng, mix) for _ in range(n)]


def probe_sizes(mix: dict) -> list[list[ExampleSize]]:
    """The batch capacities are sized from (seed-independent)."""
    rng = rng_for(mix["size_seed"], 1 << 40)
    per = mix["examples_per_instance"]
    return [[draw_example(rng, mix) for _ in range(per)]
            for _ in range(mix["instances"])]


def deal(mix: dict, b: int, seed: int) -> list[list[ExampleSize]]:
    """Batch ``b`` dealt to instances: the run seed permutes it."""
    sizes = batch_sizes(mix, b)
    perm = rng_for(seed, b).permutation(len(sizes))
    per = mix["examples_per_instance"]
    return [[sizes[k] for k in perm[i * per:(i + 1) * per]]
            for i in range(mix["instances"])]


class BatchSampler:
    """``sampler(rng, per)`` for the program's ``PrefetchingLoader``.

    The loader asks for one instance at a time, ``instances`` calls per
    batch, from its one worker thread; its own ``rng`` is not used: call
    ``c`` serves instance ``c % instances`` of batch ``c // instances``
    (a resample after a capacity overflow gets the next batch, new
    sizes).  ``make`` turns an :class:`ExampleSize` into whatever the
    program's sampler returns."""

    def __init__(self, mix: dict, seed: int, make):
        self.mix, self.seed, self.make = mix, seed, make
        self.calls = 0
        self._batch: tuple[int, list] | None = None
        self._lock = threading.Lock()

    def __call__(self, rng, per: int):
        del rng
        if per != self.mix["examples_per_instance"]:
            raise ValueError(f"loader asked {per} examples per instance, "
                             f"the mix has {self.mix['examples_per_instance']}")
        with self._lock:
            b, i = divmod(self.calls, self.mix["instances"])
            self.calls += 1
            if self._batch is None or self._batch[0] != b:
                self._batch = (b, deal(self.mix, b, self.seed))
            return [self.make(s) for s in self._batch[1][i]]
