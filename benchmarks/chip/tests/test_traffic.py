"""The traffic generator: the seed alone fixes the stream, every seed
gets the same sizes in another order, and the drawn lengths follow the
mix's parameters."""
import numpy as np

import traffic

BIG_SEED = 2**31 + 12345


def test_seed_fixes_the_example_stream():
    mix = traffic.load("omni")
    a = [traffic.deal(mix, b, BIG_SEED) for b in range(5)]
    b = [traffic.deal(mix, b, BIG_SEED) for b in range(5)]
    c = [traffic.deal(mix, b, BIG_SEED + 1) for b in range(5)]
    assert a == b
    assert a != c


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = traffic.load("omni")
    for b in range(5):
        one = [ex for inst in traffic.deal(mix, b, 1) for ex in inst]
        two = [ex for inst in traffic.deal(mix, b, 2) for ex in inst]
        key = lambda e: (e.task, e.text, e.vision, e.audio)  # noqa: E731
        assert sorted(one, key=key) == sorted(two, key=key)


def test_batch_sampler_serves_instances_in_order():
    mix = traffic.load("omni")
    s = traffic.BatchSampler(mix, 7, lambda e: e)
    per = mix["examples_per_instance"]
    got = [s(None, per) for _ in range(2 * mix["instances"])]
    assert got[:mix["instances"]] == traffic.deal(mix, 0, 7)
    assert got[mix["instances"]:] == traffic.deal(mix, 1, 7)


def test_example_lengths_follow_the_mix():
    mix = traffic.load("omni")
    rng = np.random.default_rng(0)
    exs = [traffic.draw_example(rng, mix) for _ in range(20000)]
    share = {t["name"]: t["weight"] for t in mix["tasks"]}
    for task, w in share.items():
        got = sum(e.task == task for e in exs) / len(exs)
        assert abs(got - w) < 0.015, (task, got, w)
    sqa = np.array([e.audio for e in exs if e.task == "sqa"])
    assert abs(np.median(sqa) / 700 - 1) < 0.06
    asr = [e for e in exs if e.task == "asr"]
    ratio = np.median([e.text / e.audio for e in asr if e.text > 8])
    assert abs(ratio - 0.25) < 0.01
    vqa = {e.vision for e in exs if e.task == "vqa"}
    assert vqa == {576 * k for k in range(1, 6)}
