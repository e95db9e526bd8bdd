"""``decode_batch_mean`` (bench/spans.py) in the chat-backlog cells: mean
live slots per decode step, from the ``batch`` stat of ``engine.decode``;
the backlog should keep it near the cell's slot count."""

from bench import spans


def read(run):
    return spans.decode_batch_mean(spans.spans_of(run))
