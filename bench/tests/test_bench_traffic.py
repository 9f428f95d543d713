"""The traffic generator: deterministic in the seed, the same work for
every seed, and lengths and arrivals that follow each mix's parameters."""
import json
import os
from statistics import NormalDist

import numpy as np
import pytest

from bench import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: an open-loop mix (long prompts, short answers, Poisson arrivals, the
#: parameters of the knee sweep in PERF.md): no cell runs one yet, but the
#: generator serves both loops
OPEN = {"loop": "open", "rate_per_s": 2.8,
        "prompt_tokens": {"dist": "lognormal", "median": 2048, "sigma": 0.4,
                          "min": 1024, "max": 4096},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 32}}
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))) \
    + ["prefill-open"]


def load(name):
    if name == "prefill-open":
        return OPEN
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def requests(mix, seed, seconds=30.0):
    n = 4 * int(mix.get("clients", 1))
    return traffic.generate(mix, seed, 50000, seconds, n_closed=n)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = load(name)
    a, b = requests(mix, 2 ** 33 + 17), requests(mix, 2 ** 33 + 17)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.max_new_tokens, x.due, x.client) == \
            (y.max_new_tokens, y.due, y.client)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    mix = load(name)
    a, b = requests(mix, 11), requests(mix, 2 ** 31 + 12)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    if mix["loop"] == "open":
        assert len(a) == len(b)
        assert a[-1].due == pytest.approx(b[-1].due)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("key", ["prompt_tokens", "output_tokens"])
def test_lengths_follow_the_mix(name, key):
    mix = load(name)
    dist = mix[key]
    n = 480
    v = traffic.quantiles(dist, n)
    assert v.min() >= dist["min"] and v.max() <= dist["max"]
    if dist["dist"] == "lognormal":
        assert np.median(v) == pytest.approx(dist["median"], rel=0.01)
        # the log-spread between quartiles, where no clipping reaches
        q1, q3 = np.percentile(np.log(v), [25, 75])
        want = 2 * NormalDist().inv_cdf(0.75) * dist["sigma"]
        assert q3 - q1 == pytest.approx(want, rel=0.05)
    else:
        assert v.mean() == pytest.approx((dist["min"] + dist["max"]) / 2,
                                         rel=0.01)
        assert set(np.unique(v)) == set(range(dist["min"], dist["max"] + 1))


def test_open_loop_arrivals_are_poisson_at_the_rate():
    mix = load("prefill-open")
    rate, seconds = float(mix["rate_per_s"]), 600.0
    items = traffic.generate(mix, 5, 50000, seconds)
    dues = np.array([r.due for r in items])
    gaps = np.diff(np.concatenate([[0.0], dues]))
    assert len(items) == round(rate * seconds)
    assert dues[-1] < seconds and np.all(gaps > 0)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.02)
    # exponential: the coefficient of variation is 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.1)


def test_closed_loop_blocks_hold_each_length_once():
    mix = load("decode-backlog")
    c = int(mix["clients"])
    items = requests(mix, 3)
    want = sorted(traffic.quantiles(mix["prompt_tokens"], c))
    for start in range(0, len(items), c):
        block = items[start:start + c]
        assert sorted(len(r.prompt) for r in block) == want
        assert [r.client for r in block] == list(range(c))
        assert all(r.due is None for r in block)
