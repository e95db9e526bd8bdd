"""95th percentile of the gaps between consecutive tokens, ms, over every
gap of every request inside the window."""

from bench.harness import percentile


def read(run):
    v = percentile(run.itls_s(), 95)
    return None if v is None else 1e3 * v
