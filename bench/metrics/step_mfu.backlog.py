"""``step_mfu`` (bench/metrics/__init__.py) in the chat-backlog cells."""

from bench.metrics import step_mfu as read  # noqa: F401
