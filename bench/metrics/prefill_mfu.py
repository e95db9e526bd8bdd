"""Useful prefill FLOPs over (fenced prefill wall x chips x bf16 peak), %.
Useful: the real prompt (not its padded bucket), causal attention, the
output head at the last position only."""

from bench.workcounts import prefill_flops


def read(run):
    ph = run.phases.get("prefill")
    if not ph or not ph["wall_s"] or not run.peaks:
        return None
    flops = sum(prefill_flops(run.dims, r.prompt_len)
                for r in run.prefills_in_window())
    return 100.0 * flops / (ph["wall_s"] * run.chips
                            * run.peaks["bf16_flops_per_s"])
