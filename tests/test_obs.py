"""Serve-stack observability: the tracing/metrics/attainment contract.

The whole package rides on three promises:

* **observation-only** — token streams are byte-identical with telemetry
  on or off, across the single engine, the speculative engine and the
  disaggregated router (the same contract the roofline ledger obeys);
* **loadable** — an exported trace passes ``validate_trace``: well-formed
  events, per-track call-stack span nesting, every used track named,
  balanced async request pairs, paired migration flow arrows;
* **honest projection** — the registry exposes exactly the accounting
  the stack already keeps (ledger totals, pool stats, latency traces,
  windowed roofline attainment with the binding roof NAMED), and
  harvesting twice never double-counts.
"""

import functools
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.kernels import paged_attention as pa
from repro.models import init_params
from repro.obs import Telemetry, clock
from repro.obs.metrics import Counter, Registry, harvest_serve
from repro.obs.trace import (ENGINE_TID, LIFECYCLE_TID, SLOT_TID0, Tracer,
                             span, validate_trace)
from repro.serve import (Cluster, Engine, EngineConfig, GenerateConfig,
                         RoleConfig, Router, SpecConfig, SpecEngine)


@functools.lru_cache(maxsize=None)
def _model():
    cfg = smoke(get_config("qwen3-0.6b"))
    return cfg, init_params(cfg, jax.random.key(0))


def _prompts(cfg, n=3, seed=700, repetitive=False):
    out = []
    for i in range(n):
        if repetitive:
            motif = np.asarray(jax.random.randint(
                jax.random.key(seed + i), (3,), 0, cfg.vocab_size))
            out.append(np.tile(motif, 4).astype(np.int32))
        else:
            out.append(np.asarray(jax.random.randint(
                jax.random.key(seed + i), (5 + i,), 0, cfg.vocab_size),
                np.int32))
    return out


def _ecfg(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_len", 32)
    return EngineConfig(**kw)


def _run_engine(telemetry, spec=False):
    cfg, params = _model()
    ecfg = _ecfg(telemetry=telemetry, telemetry_window=2)
    if spec:
        eng = SpecEngine(cfg, params, ecfg,
                         SpecConfig(k=3, proposer="ngram"))
    else:
        eng = Engine(cfg, params, ecfg)
    gen = GenerateConfig(max_new_tokens=6)
    reqs = [eng.submit(p, gen)
            for p in _prompts(cfg, repetitive=spec)]
    eng.run()
    return eng, [list(r.generated) for r in reqs]


# -- the clock -------------------------------------------------------------

def test_clock_monotone_nondecreasing():
    stamps = [clock.now() for _ in range(100)]
    assert all(b >= a for a, b in zip(stamps, stamps[1:]))


# -- tracer + validator units ---------------------------------------------

def _toy_tracer():
    tr = Tracer(epoch=0.0)
    tr.process(0, "engine")
    tr.thread(0, ENGINE_TID, "steps")
    tr.thread(0, LIFECYCLE_TID, "lifecycle")
    return tr


def test_tracer_roundtrip_valid(tmp_path):
    tr = _toy_tracer()
    tr.span("outer", 0, ENGINE_TID, 1e-3, 5e-3)
    tr.span("inner", 0, ENGINE_TID, 2e-3, 3e-3)   # nests: fine
    tr.instant("submit", 0, LIFECYCLE_TID, 1.5e-3, request=0)
    tr.counter("pool_pages", 0, 2e-3, {"in_use": 3})
    tr.async_begin("request", 0, LIFECYCLE_TID, 0, 1e-3)
    tr.async_end("request", 0, LIFECYCLE_TID, 0, 5e-3)
    tr.flow_start("migrate", 0, LIFECYCLE_TID, 7, 2e-3)
    tr.flow_finish("migrate", 0, LIFECYCLE_TID, 7, 4e-3)
    path = tmp_path / "t.json"
    doc = tr.export(str(path))
    assert validate_trace(doc) == []
    import json
    assert json.load(open(path)) == doc
    assert doc["displayTimeUnit"] == "ms"


def test_tracer_clamps_pre_epoch_and_backward_spans():
    tr = _toy_tracer()
    tr.span("pre", 0, ENGINE_TID, -1.0, -0.5)     # before the epoch
    tr.span("backward", 0, ENGINE_TID, 9e-3, 8e-3)  # t1 < t0
    doc = tr.export()
    assert validate_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in spans)


def test_validator_rejects_malformed_documents():
    assert validate_trace([]) != []
    assert validate_trace({"traceEvents": []}) != []
    # missing required keys
    doc = {"displayTimeUnit": "ms",
           "traceEvents": [{"ph": "X", "name": "x"}]}
    assert any("missing keys" in e for e in validate_trace(doc))
    # negative duration
    tr = _toy_tracer()
    doc = tr.export()
    doc["traceEvents"].append({"ph": "X", "name": "bad", "pid": 0,
                               "tid": ENGINE_TID, "ts": 1.0, "dur": -2.0})
    assert any("bad dur" in e for e in validate_trace(doc))


def test_validator_rejects_partial_overlap_but_allows_nesting():
    tr = _toy_tracer()
    tr.span("a", 0, ENGINE_TID, 1e-3, 3e-3)
    tr.span("b", 0, ENGINE_TID, 2e-3, 4e-3)       # partial overlap
    errs = validate_trace(tr.export())
    assert any("partially overlaps" in e for e in errs)
    tr2 = _toy_tracer()
    tr2.span("a", 0, ENGINE_TID, 1e-3, 4e-3)
    tr2.span("b", 0, ENGINE_TID, 2e-3, 3e-3)      # proper nesting
    assert validate_trace(tr2.export()) == []


def test_validator_rejects_orphans_and_unnamed_tracks():
    tr = _toy_tracer()
    tr.async_begin("request", 0, LIFECYCLE_TID, 1, 1e-3)   # no end
    tr.flow_start("migrate", 0, LIFECYCLE_TID, 2, 1e-3)    # no finish
    errs = validate_trace(tr.export())
    assert any("orphan id" in e for e in errs)
    assert any("flow id 2: orphan" in e for e in errs)
    tr2 = Tracer(epoch=0.0)                       # no metadata at all
    tr2.instant("submit", 3, 7, 1e-3)
    errs2 = validate_trace(tr2.export())
    assert any("no process_name" in e for e in errs2)
    assert any("no thread_name" in e for e in errs2)


# -- registry units --------------------------------------------------------

def test_counter_set_total_is_monotone_idempotent():
    c = Counter("x_total", "help")
    c.set_total(5.0)
    c.set_total(5.0)
    c.set_total(3.0)                              # re-harvest never rewinds
    assert c.values[()] == 5.0
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_registry_exposition_format():
    reg = Registry()
    reg.counter("serve_x_total", "things", ("kind",)).inc(2.0, kind="a")
    reg.gauge("serve_g", "a gauge").set(1.5)
    h = reg.histogram("serve_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.expose()
    assert "# HELP serve_x_total things" in text
    assert "# TYPE serve_x_total counter" in text
    assert 'serve_x_total{kind="a"} 2.0' in text
    assert "# TYPE serve_lat_seconds histogram" in text
    assert 'serve_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'serve_lat_seconds_bucket{le="1.0"} 2' in text     # cumulative
    assert 'serve_lat_seconds_bucket{le="+Inf"} 2' in text
    assert "serve_lat_seconds_count 2" in text
    assert text.endswith("\n")
    # same family, different type: refused
    with pytest.raises(TypeError):
        reg.gauge("serve_x_total")


# -- observation-only: byte identity on/off --------------------------------

def test_engine_byte_identity_telemetry_on_off():
    _, base = _run_engine(telemetry=False)
    eng, traced = _run_engine(telemetry=True)
    assert traced == base
    assert eng.obs is not None
    doc = eng.obs.export_trace()
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"engine.prefill", "engine.decode", "submit", "place",
            "first_token", "request"} <= names


def test_spec_engine_byte_identity_and_spans():
    _, base = _run_engine(telemetry=False, spec=True)
    eng, traced = _run_engine(telemetry=True, spec=True)
    assert traced == base
    doc = eng.obs.export_trace()
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"engine.propose", "engine.verify"} <= names


def test_router_byte_identity_and_migration_trace():
    cfg, params = _model()
    prompts = _prompts(cfg)
    gen = GenerateConfig(max_new_tokens=6)

    def run(telemetry):
        ecfg = _ecfg(telemetry=telemetry, telemetry_window=2)
        cluster = Cluster(cfg, params, ecfg, mesh_shape=(2, 1),
                          roles=RoleConfig.disaggregated(1, 1))
        router = Router(cluster)
        reqs = [router.submit(p, gen) for p in prompts]
        router.run()
        return cluster, router, [list(r.generated) for r in reqs]

    _, _, base = run(False)
    cluster, router, traced = run(True)
    assert traced == base
    assert router.migrations >= len(prompts)
    obs = cluster.obs
    assert obs is not None and all(
        eng.obs is obs for eng in cluster.replicas)
    obs.harvest(cluster)
    doc = obs.export_trace()
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"dispatch", "migrate", "migrate_out", "migrate_in",
            "engine.prefill", "engine.decode"} <= names
    # every migration draws one complete flow arrow between replicas
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert starts and starts == finishes
    # and the two replicas + the router each trace as their own process
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert {0, 1, 999} <= pids
    snap = obs.snapshot()
    assert "serve_migrations_total" in snap
    assert 'serve_migration_bytes_total{link="dcn"}' in snap


# -- spans inside Engine.step, on the profiler's clock ----------------------

PROGRAM = ("sched.", "engine.", "kv.")
PREFILL_CHILDREN = ["kv.grow", "engine.prefill.launch", "kv.write_prefill",
                    "engine.prefill.fence", "engine.sample_first"]
DECODE_CHILDREN = ["kv.grow", "engine.decode.stage", "engine.decode.launch",
                   "engine.decode.fence", "engine.decode.commit"]


def _serve(profile_dir=None, telemetry=False):
    """Serve three prompts over two slots (one queues), under the
    profiler when ``profile_dir`` is given; returns the engine, the
    tokens, and the program's host spans (start_ns, end_ns, name, stats)
    in start order."""
    cfg, params = _model()
    eng = Engine(cfg, params, _ecfg(telemetry=telemetry))
    reqs = [eng.submit(p, GenerateConfig(max_new_tokens=4))
            for p in _prompts(cfg)]
    if profile_dir is None:
        eng.run()
        return eng, [list(r.generated) for r in reqs], []
    jax.profiler.start_trace(str(profile_dir))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = next(profile_dir.rglob("*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(str(path))
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
              dict(ev.stats))
             for plane in profile.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PROGRAM)]
    spans.sort(key=lambda s: (s[0], -s[1]))
    return eng, [list(r.generated) for r in reqs], spans


def _children(spans, parent):
    inside = [s for s in spans if s is not parent
              and parent[0] <= s[0] and s[1] <= parent[1]]
    return [c for c in inside
            if not any(o is not c and o[0] <= c[0] and c[1] <= o[1]
                       for o in inside)]


def test_engine_step_spans_nest_with_their_stats(tmp_path):
    eng, _, spans = _serve(tmp_path)
    prompts = _prompts(eng.cfg)
    prefills = [s for s in spans if s[2] == "engine.prefill"]
    assert sorted(p[3]["request"] for p in prefills) == [0, 1, 2]
    for p in prefills:
        kids = _children(spans, p)
        assert [k[2] for k in kids] == PREFILL_CHILDREN
        n = len(prompts[p[3]["request"]])
        assert (p[3]["start"], p[3]["end"]) == (0, n)
    decodes = [s for s in spans if s[2] == "engine.decode"]
    assert len(decodes) == eng.decode_steps
    for d in decodes:
        assert [k[2] for k in _children(spans, d)] == DECODE_CHILDREN
        assert 1 <= d[3]["batch"] <= 2
    assert max(d[3]["batch"] for d in decodes) == 2
    admits = [s for s in spans if s[2] == "sched.admit"]
    assert len(admits) == eng.step_count
    # nothing of the program lies outside those three kinds of span
    tops = [s for s in spans if not any(
        o is not s and o[0] <= s[0] and s[1] <= o[1] for o in spans)]
    assert {s[2] for s in tops} == {"sched.admit", "engine.prefill",
                                    "engine.decode"}


def test_profiler_changes_no_token_and_adds_no_fence(tmp_path, monkeypatch):
    inner = jax.block_until_ready
    calls = []

    def counted(x):
        calls.append(1)
        return inner(x)

    monkeypatch.setattr(jax, "block_until_ready", counted)
    eng, plain, _ = _serve()
    n_plain = len(calls)
    calls.clear()
    eng2, traced, spans = _serve(tmp_path)
    assert spans and traced == plain
    assert eng2.step_count == eng.step_count
    assert len(calls) == n_plain


def test_phase_walls_are_launch_through_fence():
    eng, _, _ = _serve(telemetry=True)
    doc = eng.obs.export_trace()
    assert validate_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    for phase in ("prefill", "decode"):
        launch = [e for e in xs if e["name"] == f"engine.{phase}.launch"]
        fence = [e for e in xs if e["name"] == f"engine.{phase}.fence"]
        assert len(launch) == len(fence) == eng.phases[phase].steps
        wall_us = sum(f["ts"] + f["dur"] - s["ts"]
                      for s, f in zip(launch, fence))
        assert wall_us == pytest.approx(eng.phases[phase].wall_s * 1e6,
                                        rel=1e-6)
    # a prefill's spans sit on its slot's track, a decode step's on the
    # engine's
    tids = {e["name"]: e["tid"] for e in xs}
    assert tids["engine.decode.fence"] == ENGINE_TID
    assert tids["kv.write_prefill"] >= SLOT_TID0


def test_span_helper_writes_both_traces(tmp_path):
    obs = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span(obs, "engine.x", 3, LIFECYCLE_TID, a=1) as outer:
            with span(None, "kv.y") as inner:
                pass
            outer.set(b=2)
    finally:
        jax.profiler.stop_trace()
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    (ev,) = [e for e in obs.export_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert (ev["name"], ev["pid"], ev["tid"]) == ("engine.x", 3,
                                                  LIFECYCLE_TID)
    assert ev["args"] == {"a": 1, "b": 2}
    profile = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    host = {ev.name: dict(ev.stats)
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}
    assert host["engine.x"] == {"a": 1, "b": 2} and host["kv.y"] == {}


def test_failed_swap_in_records_no_span():
    """A swap-in that gets no slot or page records no ``swap_in`` event;
    the restore that follows records one."""
    cfg, params = _model()
    eng = Engine(cfg, params, _ecfg(max_len=16, num_pages=6,
                                    preempt_mode="swap", telemetry=True))
    gen = GenerateConfig(max_new_tokens=8)
    for p in _prompts(cfg, n=2):
        eng.submit(p, gen)
    sched, kv = eng._sched, eng._kv
    while not sched.preempted:
        eng.step()
    req = sched.preempted[0]
    # step on until the pool could take the restore (the other request
    # finished), then make that restore fail
    while kv.swap_in_pages_needed(req.swap_snapshot) > \
            kv.available_page_count:
        eng.step()

    def swaps():
        return [e for e in eng.obs.export_trace()["traceEvents"]
                if e["ph"] == "X" and e["name"] == "swap_in"]

    kv.swap_in = lambda snap, slot=None: None
    assert not sched._resume(req)
    assert swaps() == []
    del kv.swap_in
    eng.run()
    doc = eng.obs.export_trace()
    assert validate_trace(doc) == []
    assert len(swaps()) == sum(1 for e in doc["traceEvents"]
                               if e["name"] == "swap_out") > 0


# -- harvest / attainment --------------------------------------------------

def test_harvest_exposes_ledger_pool_and_latency():
    eng, _ = _run_engine(telemetry=True)
    eng.obs.harvest(eng)
    text = eng.obs.snapshot()
    assert "serve_decode_tokens_total" in text
    assert 'serve_flops_total{phase="decode"}' in text
    assert 'serve_level_bytes_total{level="hbm"}' in text
    assert "serve_pool_pages_in_use" in text
    for seg in ("queue_wait", "prefill", "first_decode", "total"):
        assert f'serve_ttft_seconds_bucket{{segment="{seg}"' in text
    assert "serve_itl_seconds_count" in text
    # harvesting again must not double-count anything
    eng.obs.harvest(eng)
    assert eng.obs.snapshot() == text


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_decode_span_counts_live_walk_pages(backend):
    """With telemetry on and the live-page walk in the decode step, each
    ``engine.decode`` event carries ``pages``, the table entries one
    layer's walk copies for the step's positions, and
    ``serve_live_page_walks_total`` counts the steps.  The jnp reference
    walks no pages: neither appears."""
    cfg, params = _model()
    eng = Engine(cfg, params, _ecfg(telemetry=True, kernel_backend=backend))
    reqs = [eng.submit(p, GenerateConfig(max_new_tokens=4))
            for p in _prompts(cfg)]
    seen = []
    step = eng._decode_fn

    def probe(*args):
        bt, pos = args[2], np.asarray(args[4])
        seen.append(pa.live_walk_pages(pos, eng.ecfg.page_size,
                                       bt.shape[1]))
        return step(*args)

    eng._decode_fn = probe
    eng.run()
    assert all(len(r.generated) == 4 for r in reqs)
    decodes = [e["args"] for e in eng.obs.tracer.events
               if e["name"] == "engine.decode"]
    assert len(decodes) == eng.decode_steps == len(seen)
    text = eng.obs.snapshot()
    if backend == "pallas":
        assert [d["pages"] for d in decodes] == seen
        assert min(seen) >= 2          # two slots: a live page each
        assert (f"serve_live_page_walks_total {eng.decode_steps}"
                in text)
    else:
        assert all("pages" not in d for d in decodes)
        assert "serve_live_page_walks_total" not in text


def test_attainment_windows_name_the_binding_roof():
    eng, _ = _run_engine(telemetry=True)
    eng.obs.harvest(eng)
    windows = eng.obs.attainment.windows
    assert windows, "a 6-token run must close at least one window"
    for w in windows:
        assert w.binding_roof in w.roofs
        assert w.dt_s > 0 and w.tokens > 0
        assert w.flops_per_s > 0
        # attainment is flops over the per-level roof, so the binding
        # (lowest) roof carries the HIGHEST attainment fraction
        assert w.fraction == pytest.approx(
            max(v for v in w.attainment.values()))
        assert w.fraction == pytest.approx(
            w.flops_per_s / w.roofs[w.binding_roof])
    text = eng.obs.snapshot()
    assert "serve_roofline_attainment{level=" in text
    assert "serve_roofline_binding{roof=" in text
    assert "serve_attained_flops_per_s" in text


def test_telemetry_default_off_leaves_no_hooks():
    cfg, params = _model()
    eng = Engine(cfg, params, _ecfg())
    assert eng.obs is None
    gen = GenerateConfig(max_new_tokens=4)
    eng.submit(_prompts(cfg, n=1)[0], gen)
    eng.run()
    assert eng._sched.obs is None


# -- overhead --------------------------------------------------------------

def test_tracing_overhead_within_bar():
    """Traced wall within 1.25x of untraced (min-of-3 each side; smoke
    walls on shared runners are noisy, so the estimator is the standard
    min-latency one and the whole check retries)."""
    _run_engine(telemetry=False)                  # compile warm-up
    _run_engine(telemetry=True)

    def wall(telemetry):
        t0 = time.perf_counter()
        _run_engine(telemetry=telemetry)
        return time.perf_counter() - t0

    for attempt in range(3):
        base = min(wall(False) for _ in range(3))
        traced = min(wall(True) for _ in range(3))
        if traced / base <= 1.25:
            return
    raise AssertionError(
        f"traced wall {traced * 1e3:.1f}ms exceeds 1.25x the untraced "
        f"{base * 1e3:.1f}ms on every attempt")
