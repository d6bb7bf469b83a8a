"""The traffic generator: deterministic per seed, the stated distributions,
the same work for every seed."""
import math
from statistics import NormalDist

import numpy as np
import pytest

from harness import traffic

CHAT = {"kind": "open_loop", "rate_per_s": 0.5,
        "interarrival": {"dist": "gamma", "cv": 2.0},
        "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                          "min": 32, "max": 1536},
        "output_tokens": {"dist": "uniform", "min": 16, "max": 64},
        "block": 16}


def _sizes(items):
    return [(len(i.prompt), i.max_new_tokens) for i in items]


def test_same_seed_same_requests():
    a = traffic.requests(CHAT, 2 ** 40 + 7, 48, 1000)
    b = traffic.requests(CHAT, 2 ** 40 + 7, 48, 1000)
    assert _sizes(a) == _sizes(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.due for x in a] == [y.due for y in b]


def test_seeds_offer_the_same_work_with_other_tokens():
    a = traffic.requests(CHAT, 1, 48, 1000)
    b = traffic.requests(CHAT, 2 ** 33 + 1, 48, 1000)
    assert _sizes(a) == _sizes(b)
    assert [x.due for x in a] == [y.due for y in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_each_block_holds_the_quantiles():
    a = traffic.requests(CHAT, 1, 48, 1000)
    q = sorted(traffic.quantiles(CHAT["prompt_tokens"], 16).tolist())
    for k in range(0, 48, 16):
        assert sorted(len(i.prompt) for i in a[k:k + 16]) == q


def test_open_loop_phases():
    mix = dict(CHAT, preroll_s=6.0, drain_limit_s=10.0)
    items = traffic.open_loop(mix, 9, 20.0, 1000)
    due = np.array([i.due for i in items])
    assert len(items) == 3 + 10 + 5
    assert np.all(np.diff(due) > 0)
    window = [i for i in items if 6.0 <= i.due < 26.0]
    assert len(window) == 10 and window[0].due == 6.0
    q = sorted(traffic.quantiles(CHAT["prompt_tokens"], 10).tolist())
    assert sorted(len(i.prompt) for i in window) == q


@pytest.mark.parametrize("n", [16, 400])
def test_lognormal_quantiles(n):
    d = {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 32,
         "max": 1536}
    v = traffic.quantiles(d, n)
    assert v.min() >= 32 and v.max() <= 1536
    assert np.median(v) == pytest.approx(384, rel=0.03)
    # the share below e^(mu + sigma) is the normal's 84%
    hi = 384 * math.exp(0.8)
    assert np.mean(v <= hi) == pytest.approx(NormalDist().cdf(1.0), abs=2 / n)


def test_uniform_covers_its_integers_evenly():
    v = traffic.quantiles({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert sorted(v.tolist()) == list(range(16, 65))


def test_gamma_gaps_have_the_rate_and_the_spread():
    g = traffic.quantiles({"dist": "gamma", "mean": 2.0, "cv": 2.0}, 4000)
    assert g.mean() == pytest.approx(2.0, rel=1e-9)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.05)
    assert np.all(g > 0)


def test_open_loop_offers_its_rate():
    items = traffic.requests(CHAT, 3, 160, 1000)
    gaps = np.diff([i.due for i in items] + [items[-1].due])
    # every full block's gaps sum to block / rate
    assert sum(np.diff([i.due for i in items[:17]])) == pytest.approx(
        16 / CHAT["rate_per_s"])
    assert gaps[:-1].min() > 0


def test_prompts_are_tokens_of_the_vocabulary():
    items = traffic.requests(CHAT, 5, 32, 777)
    for it in items:
        assert it.prompt.dtype == np.int32
        assert 0 <= it.prompt.min() and it.prompt.max() < 777
