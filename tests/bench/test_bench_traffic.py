"""The traffic generator is a function of the mix and the seed: the same
seed gives the same requests, every seed the same sizes from the same
clients."""
import json
import os

import numpy as np

from bench.traffic import generator

from conftest import ROOT, TINY_MIXES


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def _waves(plan, n=4):
    return [r for w in range(n) for r in plan.wave(w)]


def _same_waves(a, b):
    return all(
        x.idx == y.idx and x.max_tokens == y.max_tokens
        and x.client == y.client and np.array_equal(x.prompt, y.prompt)
        for x, y in zip(_waves(a), _waves(b)))


def test_closed_loop_is_deterministic_in_the_seed():
    mix = _mix("long_docs")
    a = generator.generate(mix, 3_000_000_019, 92544)
    b = generator.generate(mix, 3_000_000_019, 92544)
    c = generator.generate(mix, 17, 92544)
    assert _same_waves(a, b)
    assert not _same_waves(a, c)
    sizes = lambda p: [  # noqa: E731
        (r.client, len(r.prompt), r.max_tokens) for r in _waves(p)]
    assert sizes(a) == sizes(c)
    # a client's sizes change from wave to wave
    assert len(set(sizes(a)[::len(a.wave(0))])) > 1


def test_seeds_past_32_bits_are_distinct():
    mix = TINY_MIXES["closed"]
    a = generator.generate(mix, 5, 256)
    b = generator.generate(mix, 5 + 2 ** 32, 256)
    assert not _same_waves(a, b)


def test_every_closed_loop_wave_has_the_same_sizes():
    mix = _mix("long_docs")
    plan = generator.generate(mix, 11, 92544)
    waves = [[(len(r.prompt), r.max_tokens) for r in plan.wave(w)]
             for w in (0, 1, 2, 57, 1000)]
    first = sorted(waves[0])
    assert [n for n, _ in first] == [2048] * 2 + [4096] * 2 + [6144]
    assert all(sorted(w) == first for w in waves)
    assert all(128 <= o <= 384 for w in waves for _, o in w)
    # the clients never run out of requests
    assert [r.client for r in plan.wave(10 ** 6)] == list(range(5))


def test_apportion_and_quantiles():
    assert list(generator.apportion([0.4, 0.3, 0.2, 0.1], 10)) == [4, 3, 2, 1]
    assert generator.apportion([1, 1, 1], 8).sum() == 8
    u = generator.output_quantiles({"dist": "uniform", "min": 128,
                                    "max": 384}, 8)
    assert u.min() >= 128 and u.max() <= 384 and len(set(u)) == 8
