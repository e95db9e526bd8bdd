"""Median queue wait, ms: from a request's due time to its first placement
into a decode slot (the scheduler's ``prefill_start_time``), over every
request due in the window; one never placed counts what it waited."""

from bench.harness import percentile


def read(run):
    v = percentile(run.queue_waits_s(), 50)
    return None if v is None else 1e3 * v
