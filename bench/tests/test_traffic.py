"""The seeded traffic generator: one seed, one schedule; Poisson arrivals
and a backlog; lengths distributed as the mix file says."""

import hashlib
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


POISSON = [m for m in MIXES if _mix(m)["arrival"] == "poisson"]
BACKLOG = [m for m in MIXES if _mix(m)["arrival"] == "backlog"]


def _items(mix, seed, seconds=50.0, vocab=1000, backlog_items=256):
    """A Poisson schedule's items, or the first ``backlog_items`` a
    backlog's queue takes."""
    t = traffic.Traffic(mix, seed, vocab, seconds)
    for _ in range(backlog_items if t.depth else 0):
        t.draw()
    return t.items


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


@pytest.mark.parametrize("name", POISSON)
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


def test_poisson_items_are_those_recorded_before_backlogs_existed():
    """The Poisson branch draws the same items from the same stream as
    the generator did before it learnt backlogs (``data/``: index, due
    time, prompt length, budget and a hash of the token ids)."""
    from bench.tests.conftest import TINY_MIX
    recorded = json.loads((BENCH / "tests" / "data" /
                           "poisson_items.json").read_text())
    for key, want in recorded.items():
        name, seed, seconds, vocab = key.split("/")
        mix = TINY_MIX if name == "tiny" else _mix(name)
        got = [[x.index, repr(x.offset_s), len(x.prompt), x.max_new_tokens,
                hashlib.sha256(x.prompt.tobytes()).hexdigest()[:16]]
               for x in _items(mix, int(seed), float(seconds), int(vocab))]
        assert got == want, key


@pytest.mark.parametrize("name", BACKLOG)
def test_backlog_draws_on_demand_all_due_at_once(name):
    """A backlog draws an item when its queue takes one, never runs dry,
    and its warm-up covers every length any item sends."""
    mix = _mix(name)
    t = traffic.Traffic(mix, SEED, 1000, 50.0)
    assert t.depth == mix["depth"] > 0 and t.items == []
    drawn = [t.draw() for _ in range(1000)]
    assert drawn == t.items and [x.index for x in drawn] == list(range(1000))
    assert all(x.offset_s == 0.0 for x in drawn)
    pool = traffic.lognormal_quantiles(mix["prompt_tokens"],
                                       mix["distinct_prompt_lengths"])
    assert t.prompt_lengths == sorted(set(pool.tolist()))
    assert {len(x.prompt) for x in drawn} == set(t.prompt_lengths)
    assert max(len(x.prompt) + x.max_new_tokens for x in drawn) \
        <= t.max_tokens


@pytest.mark.parametrize("name", BACKLOG)
def test_backlog_answers_are_a_stratified_set_every_depth_items(name):
    """A window takes a backlog's items in order, so every ``depth`` of
    them carry one draw from each of ``depth`` equal-probability slices
    of the answer lengths: seeds differ in order, not in the work."""
    mix = _mix(name)
    spec, d = mix["output_tokens"], mix["depth"]
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = statistics.NormalDist()
    lo = [spec["min"]] + [
        math.floor(math.exp(mu + sigma * nd.inv_cdf(i / d)))
        for i in range(1, d)]
    hi = [math.ceil(math.exp(mu + sigma * nd.inv_cdf(i / d)))
          for i in range(1, d)] + [spec["max"]]
    for seed in (SEED, SEED + 1):
        out = [x.max_new_tokens for x in _items(mix, seed, 50.0)]
        for b in range(0, len(out) - d + 1, d):
            block = sorted(out[b:b + d])
            assert all(max(lo[i], spec["min"]) <= v <= min(hi[i], spec["max"])
                       for i, v in enumerate(block)), (seed, b)
