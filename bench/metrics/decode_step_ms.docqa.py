"""``decode_step_ms`` (bench/metrics/__init__.py) in qwen3-0.6b.docqa-poisson."""

from bench.metrics import decode_step_ms as read  # noqa: F401
