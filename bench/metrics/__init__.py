"""Per-metric readers, one file each (``<metric>.py`` defining
``read(run)`` over ``bench.harness.RunData``), found by name.  A quantity
that a later cell reports under a name of its own (``decode_step_ms.docqa``
beside a ``decode_step_ms.<cell>``) shares its arithmetic here."""

from bench import spans
from bench.workcounts import (decode_token_flops, least_time_s,
                              paged_attention_work, prefill_flops)


def decode_step_ms(run):
    """Fenced decode-step wall, ms, mean over the window's decode steps,
    from the scheduler's ``decode`` phase."""
    ph = run.phases.get("decode")
    if not ph or not ph["steps"]:
        return None
    return 1e3 * ph["wall_s"] / ph["steps"]


def step_mfu(run):
    """Model FLOPs of every token computed in the window over (window x
    chips x bf16 peak), %: each prefilled prompt's useful FLOPs and each
    decode token's matmuls, output head and attention over its live
    context."""
    if not run.peaks:
        return None
    flops = sum(prefill_flops(run.dims, r.prompt_len)
                for r in run.prefills_in_window())
    flops += sum(decode_token_flops(run.dims, c)
                 for c in run.decode_contexts())
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["bf16_flops_per_s"])


def paged_attention_roofline(run):
    """Roofline share of the paged decode attention, %: the least time the
    chip needs for the work the window's decode steps asked of it (every
    live K/V line read once, this chip's share of the KV heads, queries
    and outputs; or its FLOPs at peak, whichever is longer) over the
    device time of the ``paged_attention`` kernel's ops on chip 0, both
    over the traced stretch.  A tensor-parallel chip holds ``1 /
    serving.tp`` of the heads."""
    if run.trace is None or not run.peaks:
        return None
    measured = run.trace.kernel_time_s("paged_attention", chip=0)
    contexts = run.decode_contexts(since=run.t_traced)
    if measured <= 0 or not contexts:
        return None
    flops, nbytes = paged_attention_work(run.dims, contexts,
                                         tp=run.config["serving"]["tp"])
    return 100.0 * least_time_s(flops, nbytes, run.peaks) / measured


def collective_ms_per_step(run):
    """Device time of the collectives (all-reduce, all-gather, ...) in the
    decode step's program on chip 0, ms per decode step (``engine.decode``
    span) of the traced stretch; None where it ran none."""
    if run.trace is None:
        return None
    steps = sum(1 for sp in spans.spans_of(run) if spans.is_decode_step(sp))
    t = run.trace.collective_time_s(chip=0, module="decode")
    return 1e3 * t / steps if steps and t > 0 else None
