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


PSUM = ("%psum.14 = bf16[64,1,5120]{2,0,1:T(8,128)(2,1)S(1)} all-reduce("
        "%fusion.162), channel_id=1, replica_groups={{0,1,2,3}}")
GATHER = ("%all-gather.6 = bf16[4,37984,64]{1,2,0:T(8,128)(2,1)S(1)} "
          "all-gather(%convolution_bitcast_fusion), dimensions={0}")
WHILE = ("%while.3 = (s32[]{:T(128)}, bf16[1,1024]{1,0:T(8,128)(2,1)}, "
         "/*index=5*/bf16[28]{0}) while(%tuple.1), condition=%cond")


def test_op_codes_come_from_the_hlo_text():
    """A psum is named by JAX (``%psum.14``) and is an all-reduce: the
    collectives are found by opcode, not by name."""
    assert trace.op_name(PSUM) == "psum.14"
    assert trace.op_code(PSUM) == "all-reduce"
    assert trace.op_code(GATHER) == "all-gather"
    assert trace.op_code(WHILE) == "while"
    assert trace.op_code("window") == ""


def test_collectives_of_a_tensor_parallel_trace():
    """Two chips, each with a fusion and a psum in the decode program and
    an all-gather in a prefill program: chip 0's collective time by
    program, and busy time averaged over both chips."""
    from bench import spans
    ms = 1e6
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"

    def chip(shift):
        return {"XLA Modules": [("jit__decode_sample(1)", 0.0, 50 * ms, {}),
                                ("jit__prefill_bucket(2)", 60 * ms, 30 * ms,
                                 {})],
                "XLA Ops": [(fusion, 1 * ms, 10 * ms, {}),
                            (PSUM, (11 + shift) * ms, 4 * ms, {}),
                            (GATHER, 61 * ms, 2 * ms, {})]}
    text = spans.text_proto([
        ("/host:CPU", {"python3": [("window", 0.0, 100 * ms, {})]}),
        ("/device:TPU:0", chip(0)), ("/device:TPU:1", chip(-5))])
    import jax
    s = trace.summarize(jax.profiler.ProfileData.from_text_proto(text),
                        chips=2)
    assert [o.opcode for o in s.ops[0]] == ["fusion", "all-reduce",
                                            "all-gather"]
    assert s.collective_time_s(0, module="decode") == pytest.approx(0.004)
    assert s.collective_time_s(0) == pytest.approx(0.006)
    # chip 1's psum overlaps its fusion: busy is the union
    assert s.chip_busy_s(0) == pytest.approx(0.016)
    assert s.chip_busy_s(1) == pytest.approx(0.012)
    assert s.busy_s == pytest.approx(0.014)
