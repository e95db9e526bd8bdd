"""``step_mfu`` (bench/metrics/__init__.py) in qwen3-0.6b.docqa-poisson."""

from bench.metrics import step_mfu as read  # noqa: F401
