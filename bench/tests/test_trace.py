"""The trace reduction: interval arithmetic by hand, and the whole
reduction on a small trace recorded on a TPU v5e (``data/``)."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_clips_and_merges():
    iv = [(0, 10), (5, 15), (20, 30), (40, 50)]
    assert trace.union_ns(iv, 0, 100) == 15 + 10 + 10
    assert trace.union_ns(iv, 8, 25) == 7 + 5
    assert trace.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement_of_the_union():
    iv = [(10, 20), (15, 30), (50, 60)]
    gaps = trace.gaps_ns(iv, 0, 100)
    assert gaps == [(0, 10), (30, 50), (60, 100)]
    busy = trace.union_ns(iv, 0, 100)
    assert busy + sum(b - a for a, b in gaps) == 100


@pytest.fixture(scope="module")
def recorded():
    """0.9 s of the window of a qwen3-0.6b.docqa-poisson run with
    ``--trace 1`` on one TPU v5e: two decode steps and a prefill, cut out
    of the profiler's trace and kept as an XSpace text proto."""
    import gzip

    import jax
    with gzip.open(DATA / "v5e_docqa_trace.textproto.gz", "rt") as f:
        return trace.summarize(jax.profiler.ProfileData.from_text_proto(
            f.read()), chips=1)


def test_recorded_trace_busy_and_idle(recorded):
    assert recorded.window_s == pytest.approx(0.9)
    assert recorded.busy_s == pytest.approx(0.852819718, rel=1e-6)
    assert 0 < recorded.busy_s < recorded.window_s


def test_recorded_trace_kernel_and_containers(recorded):
    kernel = recorded.kernel_time_s("paged_attention")
    assert kernel == pytest.approx(0.705189512, rel=1e-6)
    assert recorded.collective_time_s(0) == 0.0       # one chip
    whiles = [o for o in recorded.ops[0] if o.kind == "while"]
    assert whiles, "the scan over layers runs as a while op"
    assert all(o.module for o in recorded.ops[0])


def test_recorded_trace_breakdown(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= trace.TOP
    assert 0 < len(b["idle_gaps"]) <= trace.TOP
    name, seconds = b["device_ops"][0]
    assert name == "jit__decode_sample:paged_attention.9"
    assert seconds == pytest.approx(0.705189512, rel=1e-6)
    # containers are left out, so the ops sum to no more than busy time
    assert sum(s for _, s in b["device_ops"]) <= recorded.busy_s
    assert all(g[0] in trace.HOST_SPANS + ("harness",)
               for g in b["idle_gaps"])
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_op_names_come_from_the_hlo_text():
    assert trace.op_name('%all-reduce.5 = bf16[64,5120] all-reduce('
                         'bf16[64,5120] %fusion.3), channel_id=1') \
        == "all-reduce.5"
    assert trace.COLLECTIVE.match("all-reduce.5")
    assert not trace.COLLECTIVE.match("fusion.3")
