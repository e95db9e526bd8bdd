"""The reduction of the program's own spans (bench/spans.py): interval
arithmetic by hand, the four span metrics through their readers on a
small synthetic trace, and the reduction of a short trace recorded on a
TPU v5e (``data/``)."""

import gzip
from pathlib import Path

import jax
import pytest

from bench import harness, spans, trace, workcounts
from bench.tests.test_metrics import CFG

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                    # ns
CLOCK_NS = 1 * MS       # host and device clocks agree to this in a trace


def _span(a, b, name, **stats):
    return spans.HostSpan(a * MS, b * MS, name, stats)


# One step of the harness at ms 0-100 and a wait at 100-200.  Inside the
# step: admission, a prefill with its five parts, and a decode step with
# its five.  The chip runs the prefill's program, the page writes and the
# sample, then the decode program; it idles in between.
STEP = [
    _span(1, 4, "sched.admit"),
    _span(4, 40, "engine.prefill", request=7, start=0, end=100),
    _span(4, 5, "kv.grow"),
    _span(5, 8, "engine.prefill.launch"),
    _span(8, 12, "kv.write_prefill"),
    _span(12, 20, "engine.prefill.fence"),
    _span(20, 40, "engine.sample_first"),
    _span(40, 98, "engine.decode", batch=3),
    _span(40, 42, "kv.grow"),
    _span(42, 50, "engine.decode.stage"),
    _span(50, 52, "engine.decode.launch"),
    _span(52, 90, "engine.decode.fence"),
    _span(90, 98, "engine.decode.commit"),
]
HOST = [(0.0, 100 * MS, "engine.step"), (100 * MS, 200 * MS,
                                           "generator.wait")]
OPS = [(6, 20), (22, 35), (51, 90)]         # ms on chip 0


def _summary(ops=OPS, host=HOST, window=(0.0, 200 * MS)):
    return trace.TraceSummary(
        window, {0: [trace.Op(a * MS, b * MS, "fusion.1", "jit_f")
                     for a, b in ops]}, list(host))


def test_exclusive_tiles_nested_spans_by_the_innermost():
    segs = spans.exclusive([(0, 100, "a"), (10, 40, "b"), (20, 30, "c"),
                            (50, 60, "d"), (120, 130, "e")])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 50, "a"), (50, 60, "d"),
                    (60, 100, "a"), (120, 130, "e")]


def test_idle_gap_is_named_by_the_innermost_span():
    idle = spans.idle_by_span(_summary(), STEP)
    # gaps 0-6, 20-22, 35-51 and 90-200 ms; the prefill's own span is
    # covered by its parts, so none of its idle time is its own
    assert {k: round(v / MS, 9) for k, v in idle.items()} == {
        "engine.step": 1 + 2,               # 0-1, 98-100
        "sched.admit": 3,
        "kv.grow": 1 + 2,                   # the prefill's, the decode's
        "engine.prefill.launch": 1,
        "engine.sample_first": 2 + 5,
        "engine.decode.stage": 8,
        "engine.decode.launch": 1,
        "engine.decode.commit": 8,
        "generator.wait": 100,
    }
    assert sum(idle.values()) == pytest.approx(
        (200 - sum(b - a for a, b in OPS)) * MS)


def test_a_wait_for_arrivals_is_not_host_idle():
    summary = _summary()
    # program idle: admit 3, grow 3, launch 1, sample 7, stage 8,
    # decode launch 1, commit 8 = 31 ms over one decode step; the 100 ms
    # in generator.wait and the 3 ms in the harness's step are not counted
    assert spans.host_idle_ms_per_step(summary, STEP) == pytest.approx(31.0)
    assert spans.host_idle_ms_per_step(summary, []) is None


def test_stall_sums_reset_at_each_decode_step():
    seq = [_span(0, 10, "engine.prefill", request=0),
           _span(10, 30, "engine.prefill", request=1),
           _span(30, 40, "engine.decode", batch=2),
           _span(40, 50, "engine.decode", batch=2),
           _span(50, 55, "engine.prefill", request=2),
           _span(55, 56, "engine.decode", batch=0),    # stepped nothing
           _span(56, 60, "engine.decode", batch=3)]
    assert [x / MS for x in spans.prefill_stalls_ns(seq)] == [30, 0, 5]
    assert spans.prefill_stall_p90_ms(seq) == pytest.approx(
        harness.percentile([30, 0, 5], 90))
    assert spans.decode_batch_mean(seq) == pytest.approx(7 / 3)


def test_prefill_tail_counts_requests_that_sampled():
    seq = STEP + [
        # a chunk of request 8 that has not reached its first token
        _span(100, 110, "engine.prefill", request=8),
        _span(101, 109, "engine.prefill.fence")]
    assert [x / MS for x in spans.prefill_tails_ns(seq)] == [4 + 20]
    assert spans.prefill_tail_ms(seq) == pytest.approx(24.0)
    assert spans.prefill_tail_ms([]) is None


# -- a synthetic XSpace through the readers ----------------------------------

def _xspace(with_program=True):
    host = [("window", 0.0, 200 * MS, {})]
    host += [(n, a, b - a, {}) for a, b, n in HOST]
    if with_program:
        host += [(s.name, s.start, s.end - s.start, s.stats) for s in STEP]
    ops = [(f"%fusion.{i} = f32[8] fusion()", a * MS, (b - a) * MS, {})
           for i, (a, b) in enumerate(OPS)]
    return spans.text_proto([
        ("/host:CPU", {"python3": host}),
        ("/device:TPU:0", {"XLA Modules": [("jit_f(1)", 0.0, 200 * MS, {})],
                           "XLA Ops": ops})])


def _traced_run(tmp_path, monkeypatch, text):
    """A RunData whose trace directory holds ``text`` as an .xplane.pb,
    as the harness leaves it after a ``--trace 1`` window."""
    d = tmp_path / "trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    summary = trace.summarize(trace.load(tmp_path / "trace"), chips=1)
    return harness.RunData(
        cell="c", config=CFG, dims=workcounts.Dims.of(CFG), chips=1,
        num_slots=16, peaks={}, t_open=0.0, deadline=0.2, t_end=0.2,
        setup_s=1.0, records=[], phases={}, trace=summary)


READERS = {"host_idle_ms_per_step.docqa": 31.0,
           "prefill_stall_p90_ms.docqa": 36.0,
           "prefill_tail_ms": 24.0,
           "decode_batch_mean.docqa": 3.0,
           "decode_batch_mean.backlog": 3.0}


def test_stats_survive_the_xspace():
    profile = jax.profiler.ProfileData.from_text_proto(_xspace())
    got = spans.program_spans(profile, (0.0, 200 * MS))
    assert [(s.name, s.stats) for s in got] == [(s.name, s.stats)
                                                for s in STEP]


def test_readers_on_a_synthetic_trace(tmp_path, monkeypatch):
    run = _traced_run(tmp_path, monkeypatch, _xspace())
    for name, want in READERS.items():
        assert harness.metric_reader(name)(run) == pytest.approx(want), name


def test_readers_return_none_without_program_spans(tmp_path, monkeypatch):
    """The parent commit's program opens no span: every reader gives
    nothing and raises nothing."""
    run = _traced_run(tmp_path, monkeypatch, _xspace(with_program=False))
    for name in READERS:
        assert harness.metric_reader(name)(run) is None, name
    run.trace = None
    for name in READERS:
        assert harness.metric_reader(name)(run) is None, name


# -- a short trace recorded on a TPU v5e -------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """0.9 s of the window of a qwen3-0.6b.docqa-poisson run with
    ``--trace 1`` on one TPU v5e, with this program's spans: prefills of
    1927 and 1035 tokens, each followed by a decode step (of one, then two
    live slots), cut by ``python3 bench/spans.py --cut``."""
    with gzip.open(DATA / "v5e_docqa_spans.textproto.gz", "rt") as f:
        profile = jax.profiler.ProfileData.from_text_proto(f.read())
    summary = trace.summarize(profile, chips=1)
    return profile, summary, spans.program_spans(profile, summary.window)


def test_recorded_spans_and_stats(recorded):
    _, summary, got = recorded
    assert summary.window_s == pytest.approx(0.9)
    assert summary.busy_s == pytest.approx(0.839513056, rel=1e-6)
    pres = [s for s in got if s.name == "engine.prefill"]
    assert [p.stats for p in pres] == [
        {"request": 99, "start": 0, "end": 1927},
        {"request": 100, "start": 0, "end": 1035}]
    for pre in pres:
        kids = [s for s in got if pre.start <= s.start and s.end <= pre.end
                and s is not pre]
        assert [s.name for s in kids] == [
            "kv.grow", "engine.prefill.launch", "kv.write_prefill",
            "engine.prefill.fence", "engine.sample_first"]
        assert all(k.stats == {} for k in kids)
    assert [s.stats["batch"] for s in got if s.name == "engine.decode"] \
        == [1, 2]


def test_recorded_idle_is_named_by_program_spans(recorded):
    """The recurring ~31 ms gap the harness could only call
    ``engine.step`` lies in the page writes, between the K and the V
    scatter; nearly all idle time inside ``engine.step`` falls inside a
    program span."""
    _, summary, got = recorded
    gaps = trace.gaps_ns([(o.start, o.end) for o in summary.ops[0]],
                         *summary.window)
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    assert (b - a) / MS == pytest.approx(30.666, abs=1e-3)
    assert summary.host_span_at((a + b) / 2) == "engine.step"
    segs = spans.exclusive(list(summary.host)
                           + [(s.start, s.end, s.name) for s in got])
    assert [n for s, e, n in segs if s <= a and b <= e] \
        == ["kv.write_prefill"]
    r = spans.report(summary, got)
    assert r["program_share_of_step_idle"] > 0.99
    assert r["decode_steps"] == 2
    assert r["host_idle_ms_per_step"] == pytest.approx(27.675603, rel=1e-6)
    assert r["prefill_tail_ms"] == pytest.approx(74.3396795, rel=1e-6)


def test_recorded_decode_programs_run_between_launch_and_fence(recorded):
    """The clock check: the device's decode program starts after the host
    began launching it and ends before the host's fence on it returns, so
    host spans and device ops share one clock, to within the profiler's
    alignment of the two.  Here the programs start 1.52 and 0.29 ms after
    their launch spans open and 0.21 and 0.17 ms before they close; in
    another recording one started 0.62 ms before its launch span opened,
    so the clocks agree to about a millisecond and no finer."""
    profile, summary, got = recorded
    launches = [s for s in got if s.name == "engine.decode.launch"]
    fences = [s for s in got if s.name == "engine.decode.fence"]
    mods = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for plane in profile.planes if plane.name == "/device:TPU:0"
            for line in plane.lines if line.name == trace.MODULES_LINE
            for ev in line.events if ev.name.startswith("jit__decode_sample")]
    assert len(mods) == len(launches) == len(fences) == 2
    for (s, e), launch, fence in zip(sorted(mods), launches, fences):
        assert launch.start - CLOCK_NS < s < launch.end
        assert fence.start < e < fence.end
