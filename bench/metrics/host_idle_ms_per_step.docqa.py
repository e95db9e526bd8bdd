"""``host_idle_ms_per_step`` (bench/spans.py) in qwen3-0.6b.docqa-poisson:
chip-0 idle time inside the program's own spans, ms per decode step."""

from bench import spans


def read(run):
    return spans.host_idle_ms_per_step(run.trace, spans.spans_of(run))
