"""Quantized KV page pools (``kv_dtype`` in {bf16, int8, fp8_e4m3}).

The contracts pinned here are the quantized-pool acceptance bars:

* quantize/dequantize round-trips within the per-line absmax/qmax error
  bound, with scales shaped per cache line (per (page, line, kv_head) for
  GQA pools, per (page, line) for MLA latent pools);
* all four paged-attention Pallas kernels (GQA/MLA x decode/verify), in
  both the single- and double-buffered pipelines, match their
  identically-quantized jnp oracles to kernel tolerance, so the
  engine's backend token-identity checks hold;
* engine-level token equality between the pallas and jnp backends at
  int8 for a GQA arch and an MLA arch, plain decode and speculative
  verify;
* the roofline ledger prices the shrunk line: ``kv_line_bytes`` drops
  >= 1.8x at int8 on the full-size configs, and the VMEM closed form
  still matches the kernel-grid walk;
* lifecycle: copy-on-write isolates quantized pages (scales included),
  preemption swap round-trips them byte-exactly, disaggregated KV-page
  migration stays byte-identical through the cut, and the scale leaves
  ride the SAME single-DMA SwapSnapshot as the values;
* capacity: ``capacity_report`` recomputes page_bytes from the quantized
  line, so the capacity-implied max batch grows >= 1.8x at int8.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.kernels import paged_attention as pa
from repro.kernels import quantize as kvq
from repro.models import init_params, prefill
from repro.models.common import BlockDef
from repro.serve import (Cluster, Engine, EngineConfig, GenerateConfig,
                         PagedKVCache, RoleConfig, Router, SpecConfig,
                         make_engine)
from repro.serve.crosscheck import capacity_report, crosscheck_vmem
from repro.serve.scheduler import kv_line_bytes

QDTYPES = ["int8", "fp8_e4m3"]


def _supported(kv_dtype):
    try:
        kvq.validate_kv_dtype(kv_dtype)
    except ValueError:
        pytest.skip(f"{kv_dtype} not supported by this jax build")


@functools.lru_cache(maxsize=None)
def _gqa():
    cfg = smoke(get_config("qwen3-0.6b"))
    return cfg, init_params(cfg, jax.random.key(0))


@functools.lru_cache(maxsize=None)
def _mla():
    # MoE-free MLA smoke config (same rationale as test_router_cluster:
    # expert-capacity cutoffs carry a batch-composition discontinuity
    # that would break exact byte-identity comparisons)
    cfg = smoke(get_config("deepseek-v2-236b"))
    cfg = dataclasses.replace(
        cfg, name="mla-dense-smoke", mla_absorb=True, n_experts=0,
        moe_top_k=0, moe_d_ff=0, n_shared_experts=0, moe_first_dense=0,
        n_layers=2, block_pattern=(BlockDef("mla", "dense"),))
    return cfg, init_params(cfg, jax.random.key(0))


def _prompt(cfg, seed, length):
    return np.asarray(jax.random.randint(jax.random.key(seed), (length,), 0,
                                         cfg.vocab_size), np.int32)


def _ragged_tables(rng, B, n_blocks, page, num_pages):
    bt = np.zeros((B, n_blocks), np.int32)
    pos = np.zeros((B,), np.int32)
    free = list(range(1, num_pages))
    for b in range(B):
        live = rng.randint(1, n_blocks + 1)
        for j in range(live):
            bt[b, j] = free.pop()
        pos[b] = rng.randint(0, live * page)
    return jnp.asarray(bt), jnp.asarray(pos)


# -- the quantizer ---------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", QDTYPES)
def test_quantize_roundtrip_error_bound(kv_dtype):
    """Symmetric absmax quantization along the last axis: stored values
    take the storage dtype, scales are float32 per leading index, and the
    dequantized round-trip sits within the per-line step size."""
    _supported(kv_dtype)
    x = np.asarray(jax.random.normal(jax.random.key(0), (3, 4, 2, 16)),
                   np.float32) * 5.0
    q, s = kvq.quantize(jnp.asarray(x), kv_dtype, -1)
    assert q.dtype == kvq.store_dtype(kv_dtype, "bfloat16")
    assert s.dtype == jnp.float32 and s.shape == x.shape[:-1]
    dq = np.asarray(kvq.dequantize(q, s), np.float32)
    absmax = np.abs(x).max(axis=-1, keepdims=True)
    if kv_dtype == "int8":
        bound = absmax / 127.0 * 0.5 + 1e-6      # half an int8 step
    else:
        # e4m3: 3 mantissa bits -> half-ulp relative error 2^-4, plus a
        # floor for values scaled into the subnormal range
        bound = np.abs(x) * 2.0 ** -4 + absmax / 448.0
    assert np.all(np.abs(x - dq) < bound)


def test_quantized_pool_defs_and_store_dtype():
    """The pool ParamDefs switch to the storage dtype and grow per-line
    float32 scale leaves exactly when the config asks for quantization."""
    cfg, _ = _gqa()
    qcfg = dataclasses.replace(cfg, kv_dtype="int8")
    assert not kvq.is_quantized(cfg.kv_dtype)
    assert kvq.is_quantized(qcfg.kv_dtype)
    assert kvq.store_itemsize(qcfg.kv_dtype, qcfg.dtype) == 1
    kv = PagedKVCache(qcfg, num_slots=2, page_size=4, max_len=16)
    blk = kv.pools[0][next(iter(kv.pools[0]))]
    assert blk["k"].dtype == jnp.int8 and blk["v"].dtype == jnp.int8
    assert blk["k_scale"].dtype == jnp.float32
    # one (1, page) scale row per (page, kv_head): k (reps, P, KV, page, hd)
    reps, P, KV, page, _ = blk["k"].shape
    assert blk["k_scale"].shape == (reps, P, KV, 1, page)


# -- kernel oracle identity ---------------------------------------------------

@pytest.mark.parametrize("pipeline", ["off", "double"])
@pytest.mark.parametrize("kv_dtype", QDTYPES)
def test_gqa_decode_kernel_matches_quantized_oracle(kv_dtype, pipeline):
    _supported(kv_dtype)
    B, KV, G, hd, page, nb = 3, 2, 2, 16, 4, 5
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    kq, k_s = kvq.quantize(jax.random.normal(ks[1], (P, KV, page, hd)),
                           kv_dtype, -1)
    vq, v_s = kvq.quantize(jax.random.normal(ks[2], (P, KV, page, hd)),
                           kv_dtype, -1)
    k_s, v_s = k_s[:, :, None], v_s[:, :, None]     # (P, KV, 1, page)
    bt, pos = _ragged_tables(np.random.RandomState(7), B, nb, page, P)
    ref = pa.paged_attention_reference(q, kq, vq, bt, pos, scale=hd ** -0.5,
                                       k_scale=k_s, v_scale=v_s)
    out = pa.paged_attention(q, kq, vq, bt, pos, scale=hd ** -0.5,
                             k_scale=k_s, v_scale=v_s, interpret=True,
                             pipeline=pipeline)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("pipeline", ["off", "double"])
def test_gqa_verify_kernel_matches_quantized_oracle(pipeline):
    B, T, KV, G, hd, page, nb = 2, 3, 2, 2, 16, 4, 4
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(22), 3)
    q = jax.random.normal(ks[0], (B, T, KV, G, hd))
    kq, k_s = kvq.quantize(jax.random.normal(ks[1], (P, KV, page, hd)),
                           "int8", -1)
    vq, v_s = kvq.quantize(jax.random.normal(ks[2], (P, KV, page, hd)),
                           "int8", -1)
    k_s, v_s = k_s[:, :, None], v_s[:, :, None]     # (P, KV, 1, page)
    bt, pos = _ragged_tables(np.random.RandomState(9), B, nb, page, P)
    pos = jnp.minimum(pos, nb * page - T)
    ref = pa.paged_attention_verify_reference(
        q, kq, vq, bt, pos, scale=hd ** -0.5, k_scale=k_s, v_scale=v_s)
    out = pa.paged_attention_verify(
        q, kq, vq, bt, pos, scale=hd ** -0.5, k_scale=k_s, v_scale=v_s,
        interpret=True, pipeline=pipeline)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("pipeline", ["off", "double"])
@pytest.mark.parametrize("kv_dtype", QDTYPES)
def test_mla_decode_kernel_matches_quantized_oracle(kv_dtype, pipeline):
    _supported(kv_dtype)
    B, H, r, dr, page, nb = 3, 4, 32, 8, 4, 4
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(23), 4)
    ql = jax.random.normal(ks[0], (B, H, r))
    qr = jax.random.normal(ks[1], (B, H, dr))
    cq, c_s = kvq.quantize(jax.random.normal(ks[2], (P, page, r)),
                           kv_dtype, -1)
    rq, r_s = kvq.quantize(jax.random.normal(ks[3], (P, page, dr)),
                           kv_dtype, -1)
    c_s, r_s = c_s[:, None], r_s[:, None]           # (P, 1, page)
    bt, pos = _ragged_tables(np.random.RandomState(11), B, nb, page, P)
    ref = pa.mla_paged_attention_reference(
        ql, qr, cq, rq, bt, pos, scale=(r + dr) ** -0.5,
        c_scale=c_s, r_scale=r_s)
    out = pa.mla_paged_attention(
        ql, qr, cq, rq, bt, pos, scale=(r + dr) ** -0.5,
        c_scale=c_s, r_scale=r_s, interpret=True, pipeline=pipeline)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("pipeline", ["off", "double"])
def test_mla_verify_kernel_matches_quantized_oracle(pipeline):
    B, T, H, r, dr, page, nb = 2, 3, 4, 32, 8, 4, 4
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(24), 4)
    ql = jax.random.normal(ks[0], (B, T, H, r))
    qr = jax.random.normal(ks[1], (B, T, H, dr))
    cq, c_s = kvq.quantize(jax.random.normal(ks[2], (P, page, r)),
                           "int8", -1)
    rq, r_s = kvq.quantize(jax.random.normal(ks[3], (P, page, dr)),
                           "int8", -1)
    c_s, r_s = c_s[:, None], r_s[:, None]           # (P, 1, page)
    bt, pos = _ragged_tables(np.random.RandomState(13), B, nb, page, P)
    pos = jnp.minimum(pos, nb * page - T)
    ref = pa.mla_paged_attention_verify_reference(
        ql, qr, cq, rq, bt, pos, scale=(r + dr) ** -0.5,
        c_scale=c_s, r_scale=r_s)
    out = pa.mla_paged_attention_verify(
        ql, qr, cq, rq, bt, pos, scale=(r + dr) ** -0.5,
        c_scale=c_s, r_scale=r_s, interpret=True, pipeline=pipeline)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


# -- engine byte-identity --------------------------------------------------

def _engine_tokens(cfg, params, backend, kv_dtype, prompts, gen,
                   pipeline="off"):
    eng = Engine(cfg, params, EngineConfig(
        num_slots=2, page_size=4, max_len=32, kernel_backend=backend,
        kv_dtype=kv_dtype, pipeline=pipeline))
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    return [list(r.generated) for r in reqs]


@pytest.mark.parametrize("cfg_fn", [_gqa, _mla])
def test_engine_pallas_matches_quantized_jnp_oracle(cfg_fn):
    """The end-to-end bar: at int8 the pallas-kernel engine and the jnp
    oracle engine quantize identically, so their greedy tokens match."""
    cfg, params = cfg_fn()
    prompts = [_prompt(cfg, 40 + i, 5 + i) for i in range(2)]
    gen = GenerateConfig(max_new_tokens=6)
    a = _engine_tokens(cfg, params, "pallas", "int8", prompts, gen)
    b = _engine_tokens(cfg, params, "jnp", "int8", prompts, gen)
    assert a == b
    assert all(len(t) == 6 for t in a)


def test_engine_double_pipeline_quantized_byte_identity():
    cfg, params = _gqa()
    prompts = [_prompt(cfg, 44 + i, 5 + i) for i in range(2)]
    gen = GenerateConfig(max_new_tokens=6)
    a = _engine_tokens(cfg, params, "pallas", "int8", prompts, gen,
                       pipeline="double")
    b = _engine_tokens(cfg, params, "jnp", "int8", prompts, gen)
    assert a == b


def test_spec_verify_quantized_byte_identity():
    """Speculative verify walks the same quantized pages: pallas and jnp
    backends must agree token for token through draft/verify rounds."""
    cfg, params = _gqa()
    motif = _prompt(cfg, 47, 4)
    prompt = np.tile(motif, 4)
    gen = GenerateConfig(max_new_tokens=8)
    outs = {}
    for be in ("pallas", "jnp"):
        eng = make_engine(cfg, params,
                          EngineConfig(num_slots=2, page_size=4, max_len=48,
                                       kernel_backend=be, kv_dtype="int8"),
                          SpecConfig(k=3, proposer="ngram"))
        req = eng.submit(prompt, gen)
        eng.run()
        outs[be] = list(req.generated)
    assert outs["pallas"] == outs["jnp"]
    assert len(outs["pallas"]) == 8


def test_engine_config_kv_dtype_overrides_model_config():
    cfg, params = _gqa()
    eng = Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                           max_len=16, kv_dtype="int8"))
    assert eng.cfg.kv_dtype == "int8"
    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                         max_len=16, kv_dtype="int3"))


# -- ledger pricing --------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-236b"])
def test_kv_line_bytes_shrink_at_int8(arch):
    """The acceptance bar: the all-layer decode KV line drops >= 1.8x at
    int8 on the FULL-SIZE configs (values at 1 byte + per-line f32
    scales vs bf16 values) — the direct AI multiplier decode inherits."""
    cfg = get_config(arch)
    base = kv_line_bytes(cfg)
    q8 = kv_line_bytes(dataclasses.replace(cfg, kv_dtype="int8"))
    assert q8 < base
    assert base / q8 >= 1.8, (base, q8)


def test_vmem_crosscheck_quantized():
    """The closed-form VMEM pricing and the independent kernel-grid walk
    must stay in lockstep at the quantized line size."""
    cfg, params = _gqa()
    eng = Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                           max_len=32,
                                           kernel_backend="pallas",
                                           kv_dtype="int8"))
    gen = GenerateConfig(max_new_tokens=6)
    done = [eng.submit(_prompt(cfg, 50 + i, 5), gen) for i in range(2)]
    eng.run()
    cv = crosscheck_vmem(eng, requests=done)
    assert abs(cv["vmem_ratio"] - 1.0) <= 0.02, cv


# -- capacity --------------------------------------------------------------

def test_capacity_max_batch_grows_at_int8():
    """Satellite bar: capacity_report recomputes page_bytes from the
    quantized line, so the HBM-implied max batch grows >= 1.8x."""
    cfg, params = _gqa()
    caps = {}
    gen = GenerateConfig(max_new_tokens=4)
    for kvd in (None, "int8"):
        eng = Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                               max_len=16, kv_dtype=kvd))
        eng.submit(_prompt(cfg, 60, 6), gen)
        eng.run()
        caps[kvd] = capacity_report(eng)
    ratio = caps[None]["page_bytes"] / caps["int8"]["page_bytes"]
    assert ratio >= 1.8, caps
    assert (caps["int8"]["capacity_max_batch"]
            >= 1.8 * caps[None]["capacity_max_batch"]), caps


# -- lifecycle -------------------------------------------------------------

def _prefilled_q(cfg_fn, kv_dtype, S):
    cfg, params = cfg_fn()
    cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    prompt = jax.random.randint(jax.random.key(1), (1, S), 0,
                                cfg.vocab_size)
    _, states = prefill(params, cfg, prompt)
    return cfg, prompt, states


def test_cow_isolates_quantized_pages():
    """Copy-on-write must copy the quantized values AND their scales: the
    writer's copy carries identical dequantized bytes, the sibling's
    view never moves."""
    S = 8
    cfg, prompt, states = _prefilled_q(_gqa, "int8", S)
    kv = PagedKVCache(cfg, num_slots=2, page_size=4, max_len=16,
                      prefix_cache=True)
    toks = np.asarray(prompt[0])
    a = kv.alloc(S, budget=16, tokens=toks)
    kv.write_prefill_states(a, states, S)
    b = kv.alloc(S, budget=16, tokens=toks)
    np.testing.assert_array_equal(kv.block_tables[a][:2],
                                  kv.block_tables[b][:2])
    before_a = np.asarray(jax.tree.leaves(kv.dense_view(a)[0])[0]).copy()
    assert kv.ensure_writable(b, S - 1, S)       # CoW in the shared page
    assert kv.pool.stats.cow_copies == 1
    assert kv.block_tables[a][1] != kv.block_tables[b][1]
    after_a = np.asarray(jax.tree.leaves(kv.dense_view(a)[0])[0])
    np.testing.assert_array_equal(before_a, after_a)
    va = jax.tree.leaves(kv.dense_view(a)[0])[0]
    vb = jax.tree.leaves(kv.dense_view(b)[0])[0]
    np.testing.assert_array_equal(np.asarray(va[:, :, :S]),
                                  np.asarray(vb[:, :, :S]))
    kv.pool.check(kv.table_refs())


@pytest.mark.parametrize("cfg_fn", [_gqa, _mla])
def test_swap_roundtrip_quantized_single_dma(cfg_fn):
    """swap_out -> swap_in round-trips quantized pages byte-exactly, and
    the scale leaves pack into the SAME single host DMA as the values
    (transfers saved = all leaves but one)."""
    S = 6
    cfg, prompt, states = _prefilled_q(cfg_fn, "int8", S)
    kv = PagedKVCache(cfg, num_slots=3, page_size=4, max_len=12)
    s = kv.alloc(S, budget=12)
    kv.write_prefill_states(s, states, S)
    before = [np.asarray(x) for x in jax.tree.leaves(kv.dense_view(s))]
    n_leaves = sum(len(jax.tree.leaves(seg)) for seg in kv.pools)
    n_scales = sum(1 for seg in kv.pools for blk in seg.values()
                   for name in blk if name.endswith("_scale"))
    assert n_scales > 0
    snap = kv.swap_out(s)
    assert kv.pool.stats.swap_dmas == 1
    assert kv.pool.stats.swap_transfers_saved == n_leaves - 1
    blocker = kv.alloc(4, slot=s)                # force a different slot
    s2 = kv.swap_in(snap)
    assert s2 is not None and s2 != s
    after = [np.asarray(x) for x in jax.tree.leaves(kv.dense_view(s2))]
    for x, y in zip(before, after):
        np.testing.assert_array_equal(x, y)
    kv.free(blocker)
    kv.pool.check(kv.table_refs())


def test_preemption_swap_byte_identity_quantized():
    """An undersized pool at int8: preempted requests swap their
    quantized pages (scales riding along) to host and resume
    byte-identically to the fully backed quantized run."""
    cfg, params = _gqa()
    prompts = [_prompt(cfg, 70 + i, 6) for i in range(3)]
    gen = GenerateConfig(max_new_tokens=6)

    def run(num_pages):
        eng = Engine(cfg, params, EngineConfig(
            num_slots=2, page_size=4, max_len=16, kv_dtype="int8",
            num_pages=num_pages, preempt_mode="swap"))
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.run()
        return eng, [list(r.generated) for r in reqs]

    _, base = run(None)
    eng, tight = run(6)
    assert tight == base
    assert eng._sched.preempt_count > 0, "the pool never ran dry"
    eng._kv.pool.check(eng._kv.table_refs())


@pytest.mark.parametrize("cfg_fn,seed", [(_gqa, 500), (_mla, 600)])
def test_migration_quantized_byte_identity(cfg_fn, seed):
    """Disaggregated prefill/decode at int8: the packed-snapshot handoff
    moves quantized pages + scales over the wire and the decode replica
    continues byte-identically to a single quantized engine."""
    cfg, params = cfg_fn()
    cfg = dataclasses.replace(cfg, kv_dtype="int8")
    ecfg = EngineConfig(num_slots=2, page_size=4, max_len=32)
    prompts = [_prompt(cfg, seed + i, 5 + i) for i in range(3)]
    gen = GenerateConfig(max_new_tokens=6)
    single = Engine(cfg, params, ecfg)
    base = [single.submit(p, gen) for p in prompts]
    single.run()
    base = [list(r.generated) for r in base]
    cluster = Cluster(cfg, params, ecfg, mesh_shape=(2, 1),
                      roles=RoleConfig.disaggregated(1, 1))
    router = Router(cluster)
    reqs = [router.submit(p, gen) for p in prompts]
    router.run()
    assert [list(r.generated) for r in reqs] == base
    assert router.migrations >= len(prompts)
    assert router.migration_bytes > 0
