"""``decode_batch_mean`` (bench/spans.py) in qwen3-0.6b.docqa-poisson: mean
live slots per decode step, from the ``batch`` stat of ``engine.decode``."""

from bench import spans


def read(run):
    return spans.decode_batch_mean(spans.spans_of(run))
