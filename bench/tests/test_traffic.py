"""The seeded traffic generator: one seed, one schedule; Poisson arrivals;
lengths distributed as the mix file says."""

import json
import math
import statistics

import numpy as np
import pytest

from bench import traffic
from bench.harness import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
SEED = 2**31 + 11


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _items(mix, seed, seconds=50.0, vocab=1000):
    return traffic.Traffic(mix, seed, vocab, seconds).items


@pytest.mark.parametrize("name", MIXES)
def test_one_seed_one_schedule(name):
    a, b = _items(_mix(name), SEED), _items(_mix(name), SEED)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.offset_s == y.offset_s
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_prompts_deal_out_the_set_and_answers_are_each_seeds_own(name):
    mix = _mix(name)
    pool = traffic.lognormal_quantiles(mix["prompt_tokens"],
                                       mix["distinct_prompt_lengths"])
    a, b = _items(mix, 1), _items(mix, 2)
    la, lb = [len(x.prompt) for x in a], [len(x.prompt) for x in b]
    assert set(la) <= set(pool.tolist()) and la != lb
    # every length of the set once before any comes again
    k = len(pool)
    assert sorted(la[:k]) == sorted(lb[:k]) == sorted(pool.tolist())
    oa, ob = [x.max_new_tokens for x in a], [x.max_new_tokens for x in b]
    assert oa != ob
    # the work a window holds barely moves
    assert sum(oa) == pytest.approx(sum(ob), rel=0.05)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_match_the_mix_file(name):
    mix = _mix(name)
    items = [x for s in range(8) for x in _items(mix, 100 + s)]
    for key, got in (("prompt_tokens", [len(x.prompt) for x in items]),
                     ("output_tokens", [x.max_new_tokens for x in items])):
        spec = mix[key]
        stats = traffic.length_stats(got)
        assert spec["min"] <= stats["min"] and stats["max"] <= spec["max"]
        assert abs(stats["median"] - spec["median"]) <= 0.05 * spec["median"]
        # the log of the unclipped middle spreads as sigma says
        q1, _, q3 = statistics.quantiles(np.log(got), n=4)
        assert (q3 - q1) / (2 * 0.6745) == pytest.approx(spec["sigma"],
                                                         rel=0.1)


@pytest.mark.parametrize("name", MIXES)
def test_arrivals_are_poisson(name):
    mix = _mix(name)
    rate, seconds = mix["rate_per_s"], 50.0
    n = round(rate * seconds)
    counts, gaps = [], []
    for s in range(40):
        t = [x.offset_s for x in _items(mix, 1000 + s, seconds)]
        assert len(t) == n and t == sorted(t)
        assert 0.0 <= t[0] and t[-1] < seconds
        gaps.extend(np.diff(t))
        # arrivals in each 10 s stretch of the window
        counts.extend(np.histogram(t, bins=5, range=(0, seconds))[0])
    # exponential gaps: the median is ln 2 of the mean
    assert statistics.fmean(gaps) == pytest.approx(1 / rate, rel=0.05)
    assert statistics.median(gaps) == pytest.approx(math.log(2) / rate,
                                                    rel=0.1)
    # counts per stretch spread as a Poisson count does (binomially, with
    # the window's total fixed), not evenly
    mean = n / 5
    assert statistics.pvariance(counts) == pytest.approx(
        mean * (1 - 1 / 5), rel=0.3)


def test_token_ids_lie_in_the_vocabulary():
    items = _items(_mix("docqa-poisson"), 4, seconds=4.0, vocab=37)
    for x in items:
        assert x.prompt.dtype == np.int32
        assert x.prompt.min() >= 0 and x.prompt.max() < 37


def test_prompt_lengths_are_the_schedules_own():
    t = traffic.Traffic(_mix("docqa-poisson"), 7, 1000, 50.0)
    assert t.prompt_lengths == sorted({len(x.prompt) for x in t.items})
    assert t.max_tokens == max(len(x.prompt) + x.max_new_tokens
                               for x in t.items)
