"""Seeded traffic from a mix file (``bench/traffic/<mix>.json``).

Poisson arrivals: a window of ``seconds`` gets ``n = round(rate_per_s *
seconds)`` requests.  Their due times are ``n`` independent uniform draws
over the window, sorted: a Poisson process at ``rate_per_s`` conditioned
on its count, so arrivals clump as Poisson arrivals do and only the total
is fixed.

A backlog: a queue that never empties.  The harness keeps ``depth``
requests submitted and not yet placed in a slot; each time one is placed,
it draws the next item (``Traffic.draw``), due when it joins the queue.
Items are drawn from the seeded stream in order, so the ``k``-th item of
a seed is the same however many a run takes.

Prompt lengths come from a fixed set of ``distinct_prompt_lengths``
lengths, the mid-quantiles of the mix's clipped lognormal; each seed
deals them out in its own order, every length once before any comes
again.  The set is fixed because the program compiles its page writes
anew for every prompt length it has not seen (about eleven programs, some
seconds of compilation each on a TPU v5e): a run warms the whole set up
before its window, and the count shows in ``setup_s`` and in the
warm-up's programs per length.  Answer lengths are independent
stratified draws from their lognormal (the ``i``-th of ``n`` draws takes a
uniform point of the ``i``-th of ``n`` equal-probability slices), in a
seeded order: each seed brings answers of its own, while the work a
window holds barely moves from seed to seed.  In a backlog, whose window
takes the items in order, every run of ``depth`` consecutive items is
such a stratified set.

Mix file keys:

- ``arrival``: ``"poisson"`` (an open loop at ``rate_per_s``) or
  ``"backlog"`` (``depth`` requests always waiting);
- ``prompt_tokens`` and ``output_tokens``: ``{"median", "sigma", "min",
  "max"}`` of a lognormal clipped to ``[min, max]``;
- ``distinct_prompt_lengths``: how many prompt lengths the mix sends.

The program only ever sees token ids and a token budget.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Item:
    index: int
    offset_s: float          # due time after the window opens
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the clipped lognormal ``spec``, rounded
    to whole tokens, ascending."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.clip(np.rint(np.exp(mu + sigma * z)), spec["min"], spec["max"])
    return v.astype(np.int64)


def stratified_lognormal(spec: Dict, rng: np.random.Generator,
                         n: int) -> np.ndarray:
    """``n`` whole-token draws from the clipped lognormal ``spec``, one
    from each of ``n`` equal-probability slices, in a random order."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    u = (np.arange(n) + rng.random(n)) / n
    z = np.array([_NORMAL.inv_cdf(float(min(max(x, 1e-12), 1 - 1e-12)))
                  for x in u])
    v = np.clip(np.rint(np.exp(mu + sigma * z)), spec["min"], spec["max"])
    return rng.permutation(v.astype(np.int64))


class Traffic:
    """The requests of one mix under one seed for a window of
    ``seconds``: a Poisson schedule's all drawn up front, a backlog's one
    by one as the queue takes them (``draw``)."""

    def __init__(self, mix: Dict, seed: int, vocab: int, seconds: float):
        arrival = mix["arrival"]
        if arrival not in ("poisson", "backlog"):
            raise ValueError(f"unknown arrival {arrival!r}")
        self._rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self._vocab = vocab
        self._outputs_spec = mix["output_tokens"]
        self._pool = lognormal_quantiles(mix["prompt_tokens"],
                                         mix["distinct_prompt_lengths"])
        self.items: List[Item] = []
        if arrival == "backlog":
            self.depth = int(mix["depth"])
            self._prompts: List[int] = []
            self._outputs: List[int] = []
            return
        self.depth = 0
        rng = self._rng
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        due = np.sort(rng.random(n)) * seconds
        passes = -(-n // len(self._pool))
        prompts = np.concatenate([rng.permutation(self._pool)
                                  for _ in range(passes)])[:n]
        outputs = stratified_lognormal(self._outputs_spec, rng, n)
        self.items = [
            Item(i, float(due[i]),
                 rng.integers(0, vocab, size=int(prompts[i]),
                              dtype=np.int32),
                 int(outputs[i]))
            for i in range(n)]

    def draw(self) -> Item:
        """A backlog's next item, due at once.  Prompt lengths deal out
        the fixed set, every length once before any comes again; answer
        lengths come in stratified sets of ``depth``."""
        if not self._prompts:
            self._prompts = self._rng.permutation(self._pool).tolist()
        if not self._outputs:
            self._outputs = stratified_lognormal(
                self._outputs_spec, self._rng, self.depth).tolist()
        n = self._prompts.pop()
        item = Item(len(self.items), 0.0,
                    self._rng.integers(0, self._vocab, size=n,
                                       dtype=np.int32),
                    self._outputs.pop())
        self.items.append(item)
        return item

    @property
    def prompt_lengths(self) -> List[int]:
        """Every distinct prompt length this schedule sends, ascending."""
        if self.depth:
            return sorted(set(self._pool.tolist()))
        return sorted({len(it.prompt) for it in self.items})

    @property
    def max_tokens(self) -> int:
        """The most tokens, prompt and answer, one request can hold."""
        if self.depth:
            return int(self._pool.max()) + int(self._outputs_spec["max"])
        return max(len(it.prompt) + it.max_new_tokens for it in self.items)


def length_stats(values: List[int]) -> Dict[str, float]:
    """Median, mean and range of a list of lengths, for the run's log."""
    return {"n": len(values), "median": float(statistics.median(values)),
            "mean": float(statistics.fmean(values)), "min": min(values),
            "max": max(values)}
