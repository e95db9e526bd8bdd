"""``prefill_stall_p90_ms`` (bench/spans.py) in qwen3-0.6b.docqa-poisson:
90th percentile over decode steps of the prefill time run since the
previous step, ms."""

from bench import spans


def read(run):
    return spans.prefill_stall_p90_ms(spans.spans_of(run))
