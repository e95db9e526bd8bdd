"""Median time to first token, ms: every request due in the window, from
its due time (a request still without one counts what it waited)."""

from bench.harness import percentile


def read(run):
    v = percentile(run.ttfts_s(), 50)
    return None if v is None else 1e3 * v
