"""``paged_attention_roofline`` (bench/metrics/__init__.py) in qwen3-0.6b.docqa-poisson."""

from bench.metrics import paged_attention_roofline as read  # noqa: F401
