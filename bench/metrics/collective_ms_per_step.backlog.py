"""``collective_ms_per_step`` (bench/metrics/__init__.py) in
qwen3-14b-tp4.chat-backlog: the two row-parallel psums a layer and the
vocabulary all-gather of a tp=4 decode step, on chip 0."""

from bench.metrics import collective_ms_per_step as read  # noqa: F401
