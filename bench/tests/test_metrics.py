"""Every metric reader against a run worked by hand."""

import json

import numpy as np
import pytest

from bench import harness, spans, workcounts
from bench.peaks import peaks_for

CFG = json.loads((harness.BENCH / "configs" / "qwen3-0.6b.json").read_text())


class _Req:
    def __init__(self, placed, times, n):
        self.prefill_start_time = placed
        self.token_times = times
        self.generated = [1] * len(times)
        self.finish_reason = "length" if len(times) == n else ""


class _Trace:
    window_s = 10.0

    def chip_busy_s(self, chip):
        return 8.0

    def kernel_time_s(self, kernel, chip=0):
        return 2.0 if kernel == "paged_attention" else 0.0

    def collective_time_s(self, chip=0, module=None):
        return 0.3 if module == "decode" else 0.5


def _run():
    # window [100, 110); a request due at 101 placed at 101.5, tokens at
    # 102, 102.5, 103.5; one due at 104 never served; one due before the
    # window (not counted for TTFT) with tokens inside it
    recs = [
        harness.Record(0, 101.0, 101.0, np.zeros(10, np.int32), 3,
                       _Req(101.5, [102.0, 102.5, 103.5], 3)),
        harness.Record(1, 104.0, 104.0, np.zeros(20, np.int32), 4,
                       _Req(0.0, [], 4)),
        harness.Record(2, 99.0, 99.0, np.zeros(30, np.int32), 2,
                       _Req(99.5, [99.8, 100.5], 2)),
    ]
    return harness.RunData(
        cell="c", config=CFG, dims=workcounts.Dims.of(CFG), chips=1,
        num_slots=4, peaks=peaks_for("TPU v5 lite"), t_open=100.0,
        deadline=110.0, t_end=110.0, setup_s=5.0, records=recs,
        phases={"prefill": {"wall_s": 0.2, "steps": 1, "tokens": 10},
                "decode": {"wall_s": 1.5, "steps": 3, "tokens": 6}},
        trace=_Trace(), warmup_programs=45, warmup_lengths=4)


def read(name, **run):
    r = _run()
    for k, v in run.items():
        setattr(r, k, v)
    return harness.metric_reader(name)(r)


def test_latencies():
    # TTFT: 102 - 101 = 1 s; the unserved request waited 110 - 104 = 6 s
    assert read("ttft_p50_ms") == pytest.approx(3500.0)
    # queue waits 0.5 s and 6 s
    assert read("queue_wait_p50_ms") == pytest.approx(3250.0)
    # gaps 0.5, 1.0 (request 0); the other's only in-window token has none
    assert read("itl_p95_ms") == pytest.approx(1000 * (0.5 + 0.95 * 0.5))


def test_rates_and_step_times():
    assert read("decode_step_ms.docqa") == pytest.approx(500.0)
    assert read("prefill_ms") == pytest.approx(200.0)
    assert read("warmup_programs_per_length") == pytest.approx(11.25)


def test_utilizations():
    d = workcounts.Dims.of(CFG)
    peak = 197e12
    pre = workcounts.prefill_flops(d, 10)          # request 0's prefill
    dec = (workcounts.decode_token_flops(d, 11)
           + workcounts.decode_token_flops(d, 12)
           + workcounts.decode_token_flops(d, 31))  # request 2's token 1
    assert read("prefill_mfu") == pytest.approx(100 * pre / (0.2 * peak))
    assert read("step_mfu.docqa") == pytest.approx(100 * (pre + dec) / (10 * peak))
    flops, nbytes = workcounts.paged_attention_work(d, [11, 12, 31])
    want = 100 * max(flops / peak, nbytes / 819e9) / 2.0
    assert read("paged_attention_roofline.docqa") == pytest.approx(want)


def test_backlog_rates_and_step_times(monkeypatch):
    steps = [spans.HostSpan(0.0, 1.0, "engine.decode", {"batch": 2})] * 3
    monkeypatch.setattr(spans, "spans_of", lambda run: steps)
    # request 0's three tokens and request 2's one at 100.5, over 10 s
    assert read("output_tok_s") == pytest.approx(0.4)
    assert read("output_tok_s", records=[]) is None
    assert read("decode_step_ms.backlog") == pytest.approx(500.0)
    assert read("step_mfu.backlog") == pytest.approx(read("step_mfu.docqa"))
    # 0.3 s of collectives in the decode program over the trace's 3
    # decode steps
    assert read("collective_ms_per_step.backlog") == pytest.approx(100.0)
    assert read("collective_ms_per_step.backlog", trace=None) is None


def test_roofline_reads_the_traced_stretch_only():
    """With the trace begun at 102.2 s, only request 0's tokens at 102.5
    and 103.5 (11 and 12 keys) are the kernel's work."""
    d = workcounts.Dims.of(CFG)
    flops, nbytes = workcounts.paged_attention_work(d, [11, 12])
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 2.0
    assert read("paged_attention_roofline.docqa", t_traced=102.2) \
        == pytest.approx(want)


def test_roofline_counts_a_tensor_parallel_chips_share():
    cfg = json.loads((harness.BENCH / "configs" /
                      "qwen3-14b-tp4.json").read_text())
    d = workcounts.Dims.of(cfg)
    got = read("paged_attention_roofline.backlog", config=cfg, dims=d,
               chips=4)
    flops, nbytes = workcounts.paged_attention_work(d, [11, 12, 31], tp=4)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9)
                                / 2.0)
    whole, _ = workcounts.paged_attention_work(d, [11, 12, 31])
    assert flops == pytest.approx(whole / 4)


def test_every_metric_of_the_spec_has_a_reader():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] != "setup_s":
            v = harness.metric_reader(m["name"])(_run())
            assert v is None or np.isfinite(v)
