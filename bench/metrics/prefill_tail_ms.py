"""Mean over the window's prefilled requests of ``kv.write_prefill`` +
``engine.sample_first`` (bench/spans.py), ms: dispatching the eager page
writes, then the first-token sample, which waits on the device behind
those writes."""

from bench import spans


def read(run):
    return spans.prefill_tail_ms(spans.spans_of(run))
