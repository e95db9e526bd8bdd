"""Hierarchical + time-based roofline tests (arXiv 2009.05257 /
2009.04598 applied to the serving ledger):

* golden per-level byte pricing for one decode and one verify step of a
  GQA (qwen3) and an MLA (deepseek) smoke config,
* the time-attribution identity (budget + residual*wall == wall) and its
  zero-byte / zero-wall edges,
* the unbound convention: zero collective/level bytes render "unbound",
  never an inf/NaN roof,
* the microbench cache fingerprint guard: a foreign cache falls back to
  the analytic constants with a warning and does NOT re-measure,
* the fenced-timing regression: a measured decode window can never beat
  the compiled step's own device-time floor (an unfenced stamp would),
* observation-only accounting: exercising the phase ledger and dispatch
  probe between runs leaves greedy outputs byte-identical,
* pricing <-> artifact agreement: the VMEM kernel walk and host swap
  pack cross-checks sit at ratio 1.0.
"""

import json
import math
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.core.roofline.hardware import ChipSpec, ScopeSpec
from repro.core.roofline.microbench import (CACHE_SCHEMA, MicrobenchResult,
                                            run_microbench)
from repro.core.roofline.model import (LevelBetas, PhaseTraffic, make_terms,
                                       attribution_residual,
                                       overlapped_budget, time_attribution)
from repro.core.roofline.report import (COMM_HEADER, TIME_BUDGET_HEADER,
                                        TIME_BUDGET_OVERLAP_HEADER,
                                        comm_terms_row, hierarchy_rows,
                                        time_budget_rows)
from repro.kernels.paged_attention import paged_decode_vmem_bytes
from repro.models import init_params
from repro.serve.crosscheck import crosscheck_host, crosscheck_vmem
from repro.serve.engine import Engine, EngineConfig, GenerateConfig
from repro.serve.scheduler import (attn_kernel_vmem_bytes,
                                   decode_token_bytes,
                                   decode_token_vmem_bytes, slot_swap_bytes,
                                   verify_step_vmem_bytes)

CHIP = ChipSpec(
    name="toy",
    peak_flops=100.0,
    peak_flops_by_dtype={"bfloat16": 100.0, "float32": 50.0},
    hbm_bw=10.0,
    hbm_bytes=1 << 30,
    ici_bw=5.0,
    ici_links=1,
    dcn_bw=2.0,
    vmem_bytes=1 << 20,
    vmem_bw=40.0,
    host_bw=1.0,
)


# --------------------------------------------------------------------------
# Golden per-level byte pricing (one decode + one verify step)
# --------------------------------------------------------------------------

GOLDEN = {
    # arch: (hbm, vmem, attn_vmem, verify_vmem_T4, swap_3_blocks)
    # at context L=24, active batch B=2, page size 8.  qwen3's decode is
    # the live-page walk: one query fetch and one carry update per chunk
    # (three pages, one chunk) where the per-block grid paid three
    "qwen3-0.6b": (193024.0, 195200.0, 14976.0, 227328.0, 12288.0),
    "deepseek-v2-236b": (260416.0, 271808.0, 19392.0, 323328.0, 7680.0),
}


@pytest.mark.parametrize("arch", sorted(GOLDEN))
def test_golden_per_level_bytes(arch):
    cfg = smoke(get_config(arch))
    L, B, ps, T = 24, 2, 8, 4
    hbm, vmem, attn_vmem, verify_vmem, swap3 = GOLDEN[arch]
    assert decode_token_bytes(cfg, L, B) == hbm
    assert decode_token_vmem_bytes(cfg, L, B, ps) == vmem
    assert attn_kernel_vmem_bytes(cfg, L, ps) == attn_vmem
    assert verify_step_vmem_bytes(cfg, L, T, B, ps) == verify_vmem
    assert slot_swap_bytes(cfg, 3, ps) == swap3
    # the VMEM level sees every HBM byte pass through plus the kernel's
    # resident re-touches, so it can never undercut the HBM level
    assert vmem > hbm - attn_vmem


def test_decode_pricing_follows_the_walk_the_pools_take():
    """Unquantized decode prices the live-page walk (query once, one
    carry update per chunk); quantized pools keep the per-block grid's
    formula (query and carries once per block)."""
    KV, rows, hd, ps, nb = 2, 2, 16, 8, 5          # context 40: 5 pages
    kw = dict(context_len=40, page_size=ps, n_heads=4, kv_heads=KV,
              head_dim=hd, isize=4)
    carry = 2 * rows * (hd + 2) * 4
    qo = KV * rows * hd * 4
    line = hd * 4
    assert paged_decode_vmem_bytes(**kw) == (
        KV * nb * 2 * ps * line + qo + KV * 1 * carry + qo + 2 * KV * line)
    line = hd * 1 + 4                               # int8 + f32 scale
    assert paged_decode_vmem_bytes(**kw, kv_isize=1, scale_isize=4) == (
        KV * nb * 2 * ps * line + nb * qo + KV * nb * carry + qo
        + 2 * KV * line)


def test_vmem_bytes_grow_with_context_and_queries():
    cfg = smoke(get_config("qwen3-0.6b"))
    assert attn_kernel_vmem_bytes(cfg, 32, 8) > attn_kernel_vmem_bytes(
        cfg, 8, 8)
    assert verify_step_vmem_bytes(cfg, 24, 4, 2, 8) > \
        verify_step_vmem_bytes(cfg, 24, 1, 2, 8)


# --------------------------------------------------------------------------
# Time attribution: the budget identity and its edges
# --------------------------------------------------------------------------

def test_time_attribution_identity():
    betas = LevelBetas(pi=100.0, vmem=40.0, hbm=10.0, ici=5.0, dcn=2.0,
                       host=1.0)
    ph = PhaseTraffic(flops=50.0, vmem=80.0, hbm=30.0, host=2.0,
                      wall_s=9.0, steps=4, tokens=4)
    att = time_attribution(ph, betas, dispatch_s_per_step=0.25)
    assert att["compute"] == pytest.approx(0.5)     # 50 / 100
    assert att["vmem"] == pytest.approx(2.0)        # 80 / 40
    assert att["hbm"] == pytest.approx(3.0)         # 30 / 10
    assert att["ici"] == 0.0 and att["dcn"] == 0.0  # unbound: exactly 0
    assert att["host"] == pytest.approx(2.0)        # 2 / 1
    assert att["dispatch"] == pytest.approx(1.0)    # 4 steps x 0.25
    res = attribution_residual(ph, betas, dispatch_s_per_step=0.25)
    # the identity the report's residual column encodes:
    assert sum(att.values()) + res * ph.wall_s == pytest.approx(ph.wall_s)
    assert res == pytest.approx((9.0 - 8.5) / 9.0)


def test_time_attribution_zero_wall_is_nan_not_crash():
    betas = LevelBetas(pi=1.0, vmem=1.0, hbm=1.0, ici=1.0, dcn=1.0,
                       host=1.0)
    assert math.isnan(attribution_residual(PhaseTraffic(), betas))


# --------------------------------------------------------------------------
# Overlap extension: the overlapped bound and its identities
# --------------------------------------------------------------------------

def test_overlapped_budget_identities():
    times = {"dispatch": 0.1, "compute": 1.0, "vmem": 0.2, "hbm": 2.0,
             "ici": 0.5, "dcn": 0.0, "host": 0.0}
    # ov = 0 everywhere: the bound IS the additive serial sum
    assert overlapped_budget(times) == pytest.approx(sum(times.values()))
    assert overlapped_budget(times, {}) == pytest.approx(
        sum(times.values()))
    # full overlap: dispatch + max(compute, slowest level) — the
    # perfectly pipelined machine
    full = {lvl: 1.0 for lvl in ("vmem", "hbm", "ici", "dcn", "host")}
    assert overlapped_budget(times, full) == pytest.approx(
        0.1 + max(1.0, 2.0))
    # partial: the hidden half of hbm rides under compute, the rest
    # stays serial
    half = overlapped_budget(times, {"hbm": 0.5})
    assert half == pytest.approx(0.1 + max(1.0, 1.0)
                                 + (0.2 + 1.0 + 0.5))
    # fractions clamp into [0, 1]; the bound is monotone in overlap
    assert overlapped_budget(times, {"hbm": 7.0}) == pytest.approx(
        overlapped_budget(times, {"hbm": 1.0}))
    assert overlapped_budget(times, {"hbm": -1.0}) == pytest.approx(
        overlapped_budget(times))
    assert overlapped_budget(times, full) <= half <= sum(times.values())
    # dispatch NEVER overlaps: raising it moves the bound 1:1
    bumped = dict(times, dispatch=0.6)
    assert overlapped_budget(bumped, full) == pytest.approx(
        overlapped_budget(times, full) + 0.5)


def test_terms_t_overlapped():
    scope = ScopeSpec("t", CHIP, 1, "none")
    kw = dict(scope=scope, dtype="bfloat16", flops_dev=50.0,
              hbm_bytes_dev=30.0, ici_wire_bytes_dev=5.0,
              dcn_wire_bytes_dev=0.0, vmem_bytes_dev=80.0)
    serial = make_terms(**kw)
    assert serial.overlap == {}
    # no overlap: the overlapped bound degenerates to compute + levels
    total = serial.compute_s + sum(serial.level_times().values())
    assert serial.t_overlapped == pytest.approx(total)
    # hide the dominant level entirely: bound = max(compute, next-worst
    # hidden term) + remaining serial levels
    t = serial.level_times()
    worst = max(t, key=t.get)
    ov = make_terms(**kw, overlap={worst: 1.0})
    rest = sum(v for k, v in t.items() if k != worst)
    assert ov.t_overlapped == pytest.approx(
        max(serial.compute_s, t[worst]) + rest)
    assert ov.t_overlapped <= serial.t_overlapped


def test_time_budget_rows_overlap_columns():
    betas = LevelBetas(pi=100.0, vmem=40.0, hbm=10.0, ici=5.0, dcn=2.0,
                       host=1.0)
    phases = {"decode": PhaseTraffic(flops=50.0, vmem=80.0, hbm=30.0,
                                     wall_s=9.0, steps=4, tokens=4)}
    rows = time_budget_rows(phases, betas, dispatch_s_per_step=0.25)
    assert all(len(r) == len(TIME_BUDGET_HEADER) for r in rows)
    ov_rows = time_budget_rows(phases, betas, dispatch_s_per_step=0.25,
                               overlap={"vmem": 1.0})
    assert all(len(r) == len(TIME_BUDGET_OVERLAP_HEADER) for r in ov_rows)
    assert TIME_BUDGET_OVERLAP_HEADER[:len(TIME_BUDGET_HEADER)] \
        == TIME_BUDGET_HEADER
    # the historical columns are byte-identical; only the two overlap
    # columns are appended
    for r, ov_r in zip(rows, ov_rows):
        assert ov_r[:len(TIME_BUDGET_HEADER)] == r


def test_pipeline_pricing_shrinks_vmem_only():
    """pipeline="double" collapses the per-block query re-read to one
    fetch — the VMEM pricing of the per-block walks (MLA, verify) drops,
    everything else (HBM, swap) is untouched, and the default stays
    exactly the GOLDEN values.  GQA decode at "off" is the live-page
    walk, which also updates its carries once per chunk instead of once
    per block: it prices under the double walk."""
    for arch in sorted(GOLDEN):
        cfg = smoke(get_config(arch))
        L, B, ps, T = 24, 2, 8, 4
        dbl = attn_kernel_vmem_bytes(cfg, L, ps, pipeline="double")
        off = attn_kernel_vmem_bytes(cfg, L, ps)
        dbl_tok = decode_token_vmem_bytes(cfg, L, B, ps, pipeline="double")
        off_tok = decode_token_vmem_bytes(cfg, L, B, ps)
        if arch == "qwen3-0.6b":
            assert off < dbl and off_tok < dbl_tok
        else:
            assert dbl < off and dbl_tok < off_tok
        assert verify_step_vmem_bytes(cfg, L, T, B, ps,
                                      pipeline="double") < \
            verify_step_vmem_bytes(cfg, L, T, B, ps)
        assert decode_token_bytes(cfg, L, B) == GOLDEN[arch][0]
        assert decode_token_vmem_bytes(cfg, L, B, ps) == GOLDEN[arch][1]


def test_overlapped_levels_from_engine_config():
    from repro.serve.crosscheck import overlapped_levels
    assert overlapped_levels(EngineConfig()) == []
    assert overlapped_levels(EngineConfig(pipeline="double")) == ["vmem"]
    assert overlapped_levels(EngineConfig(overlap="ring")) == ["ici"]
    assert overlapped_levels(
        EngineConfig(pipeline="double", overlap="ring")) == ["vmem", "ici"]


def test_time_budget_rows_render_unbound_levels():
    betas = LevelBetas(pi=100.0, vmem=40.0, hbm=10.0, ici=5.0, dcn=2.0,
                       host=1.0)
    rows = time_budget_rows(
        {"decode": PhaseTraffic(flops=50.0, hbm=30.0, wall_s=4.0,
                                steps=2, tokens=2)}, betas)
    flat = " ".join(" ".join(r) for r in rows)
    assert "inf" not in flat and "nan" not in flat


# --------------------------------------------------------------------------
# Unbound convention (zero collective / zero level bytes)
# --------------------------------------------------------------------------

def _terms(**kw):
    base = dict(flops_dev=50.0, hbm_bytes_dev=10.0, ici_wire_bytes_dev=0.0,
                dcn_wire_bytes_dev=0.0, dtype="bfloat16")
    base.update(kw)
    return make_terms(scope=ScopeSpec("toy", CHIP, 1, "none"), **base)


def test_zero_collective_bytes_unbound_not_inf():
    t = _terms()
    roofs = t.roofs()
    assert "ici" not in roofs and "dcn" not in roofs and "host" not in roofs
    assert all(math.isfinite(v) for v in roofs.values())
    assert t.level_roof("ici") is None
    assert t.binding_roof in roofs          # never picks an absent level
    row = comm_terms_row("decode", t)
    assert len(row) == len(COMM_HEADER)
    assert "unbound" in row and "inf" not in " ".join(row)
    flat = " ".join(" ".join(r) for r in hierarchy_rows("decode", t))
    assert "inf" not in flat and "nan" not in flat


def test_bound_levels_price_finitely():
    t = _terms(ici_wire_bytes_dev=5.0, vmem_bytes_dev=20.0,
               host_bytes_dev=1.0)
    roofs = t.roofs()
    assert roofs["ici"] == pytest.approx(50.0)      # 50/5 * 5
    assert roofs["vmem"] == pytest.approx(100.0)    # 50/20 * 40
    assert roofs["host"] == pytest.approx(50.0)     # 50/1 * 1
    assert t.vmem_s == pytest.approx(0.5) and t.host_s == pytest.approx(1.0)


# --------------------------------------------------------------------------
# Microbench cache fingerprint guard
# --------------------------------------------------------------------------

def test_foreign_cache_falls_back_analytic_without_remeasure(tmp_path):
    cache = tmp_path / "microbench.json"
    foreign = MicrobenchResult(
        fma_flops=1.0, matmul_flops=1.0,
        bandwidth={"copy": 1.0, "fill": 1.0, "triad": 1.0, "best": 1.0},
        level_bw={"hbm": 1.0},
        fingerprint={"schema": CACHE_SCHEMA, "device_kind": "tpu-v999",
                     "n_devices": 4096})
    import dataclasses
    cache.write_text(json.dumps(dataclasses.asdict(foreign)))
    before = cache.read_text()
    with pytest.warns(UserWarning, match="falling back to the analytic"):
        res = run_microbench(cache_path=str(cache))
    assert res.source == "analytic"
    assert res.peak_flops > 1.0             # data-sheet, not the stale 1.0
    assert cache.read_text() == before      # no silent re-measure/rewrite


def test_matching_cache_roundtrips(tmp_path):
    cache = tmp_path / "microbench.json"
    first = run_microbench(cache_path=str(cache), quick=True)
    assert first.source == "measured" and os.path.exists(cache)
    again = run_microbench(cache_path=str(cache))
    assert again.source == "measured"
    assert again.peak_flops == pytest.approx(first.peak_flops)
    assert again.level_bw == first.level_bw
    assert again.overlap == first.overlap


def test_schema3_cache_carries_overlap_fractions(tmp_path):
    """Schema 3 added the measured compute/transfer overlap fractions:
    the probe always exercises the host DMA engine, the JSON roundtrips
    the dict, and a pre-overlap (schema-2 shaped) cache is foreign — it
    warns and falls back instead of loading with silently-missing
    overlap."""
    assert CACHE_SCHEMA == 3
    cache = tmp_path / "microbench.json"
    res = run_microbench(cache_path=str(cache), quick=True)
    assert "host" in res.overlap
    assert all(0.0 <= v <= 1.0 for v in res.overlap.values())
    d = json.loads(cache.read_text())
    assert d["overlap"] == res.overlap
    assert d["fingerprint"]["schema"] == 3
    # forge the previous schema's fingerprint: same machine, older layout
    d["fingerprint"]["schema"] = 2
    del d["overlap"]
    cache.write_text(json.dumps(d))
    with pytest.warns(UserWarning, match="falling back to the analytic"):
        stale = run_microbench(cache_path=str(cache))
    assert stale.source == "analytic" and stale.overlap == {}


# --------------------------------------------------------------------------
# Engine-level: fenced timing floor + observation-only accounting
# --------------------------------------------------------------------------

def _smoke_engine(arch="qwen3-0.6b", **eckw):
    cfg = smoke(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_slots=2, page_size=8, max_len=48, **eckw)
    return Engine(cfg, params, ecfg), cfg


def _drive(eng, new_tokens=6, seed=3):
    rng = np.random.default_rng(seed)
    outs = []
    for _ in range(2):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, 5).astype(np.int32),
                   GenerateConfig(max_new_tokens=new_tokens))
    done = eng.run()
    for r in sorted(done, key=lambda r: r.request_id):
        outs.append(list(r.generated))
    return outs


def test_fenced_decode_wall_respects_device_floor():
    """Satellite-2 regression: the decode phase's fenced wall can never
    undercut the compiled step's own device-time estimate (bytes/beta +
    flops/pi at data-sheet peaks).  An unfenced stamp — recording async
    dispatch instead of completion — reports microsecond walls and fails
    this immediately."""
    from repro.core.roofline.hardware import HOST_CPU_FALLBACK
    from repro.serve.crosscheck import step_cost_analysis
    eng, _ = _smoke_engine()
    _drive(eng)                             # warm the compile caches
    eng.reset_phases()
    _drive(eng)
    ph = eng.phases["decode"]
    assert ph.steps > 0 and ph.wall_s > 0
    cost = step_cost_analysis(eng)
    chip = HOST_CPU_FALLBACK
    floor = ph.steps * max(cost["flops"] / chip.peak_flops,
                           cost["bytes"] / chip.hbm_bw)
    assert ph.wall_s >= floor
    # and the phase must actually carry per-level traffic
    assert ph.hbm > 0 and ph.vmem > 0 and ph.flops > 0


def test_phase_accounting_is_observation_only():
    """Reading phases, measuring dispatch overhead, and resetting the
    phase ledger between runs must not perturb greedy outputs."""
    eng, _ = _smoke_engine()
    base = _drive(eng)
    eng.reset_phases()
    eng.measure_dispatch_overhead(repeats=2)
    _ = dict(eng.phases)
    again = _drive(eng)
    assert again == base


def test_dispatch_overhead_positive_and_cached():
    eng, _ = _smoke_engine()
    _drive(eng)
    d1 = eng.measure_dispatch_overhead(repeats=2)
    assert d1 > 0
    assert eng.measure_dispatch_overhead() == d1    # cached until reset


# --------------------------------------------------------------------------
# Pricing <-> artifact cross-checks (VMEM kernel walk, host swap pack)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", ["off", "double"])
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-236b"])
def test_vmem_and_host_crosscheck_ratios(arch, pipeline):
    eng, _ = _smoke_engine(arch, pipeline=pipeline)
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, 5).astype(np.int32),
                   GenerateConfig(max_new_tokens=4))
    eng.step()
    cv = crosscheck_vmem(eng)       # prices the engine's own pipeline mode
    assert cv["pipeline"] == pipeline
    assert cv["vmem_ratio"] == pytest.approx(1.0)
    assert cv["analytic_vmem_bytes"] > 0
    ch = crosscheck_host(eng)
    assert ch["host_ratio"] == pytest.approx(1.0)
    assert ch["hlo_output_bytes"] > 0


def test_hierarchy_report_renders(capsys):
    eng, _ = _smoke_engine()
    _drive(eng)
    text = eng.hierarchy_report()
    for level in ("vmem", "hbm", "ici", "dcn", "host"):
        assert level in text
    assert "decode" in text and "residual" in text
    assert "inf" not in text and "nan" not in text
