"""``decode_step_ms`` (bench/metrics/__init__.py) in the chat-backlog cells."""

from bench.metrics import decode_step_ms as read  # noqa: F401
