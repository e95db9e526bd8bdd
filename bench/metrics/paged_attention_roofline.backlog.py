"""``paged_attention_roofline`` (bench/metrics/__init__.py) in the
chat-backlog cells: chip 0's kernel against chip 0's share of the work
(2 of 8 KV heads at tp=4)."""

from bench.metrics import paged_attention_roofline as read  # noqa: F401
