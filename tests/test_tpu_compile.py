"""Compile rehearsal for TPU v5e: the paged kernels and one whole decode
step, compiled by Mosaic/XLA for a described ``v5e:2x2`` topology with no
chip attached.  Interpret mode accepts block shapes and slices the TPU
compiler refuses; these compiles catch that on the CPU.

Widths are the published ones: qwen3-0.6b GQA (8 KV heads, 2 query heads
per group, head_dim 128) and deepseek-v2 MLA (128 heads, latent rank 512,
rope 64), page 16.  The topology is described inside a module fixture
only: describing it loads the TPU library, which one process at a time
may hold, so no import-time code may do it.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.kernels import quantize as kvq
from repro.models import abstract_params, decode_step_paged, paged_cache_defs
from repro.parallel import sharding as shd

PAGE = 16
SLOTS = 8
N_BLOCKS = 34                    # 544-token context: 512 prompt + 32 new
NUM_PAGES = 1 + SLOTS * N_BLOCKS
GQA = dict(KV=8, G=2, hd=128)    # qwen3-0.6b
MLA = dict(H=128, r=512, dr=64)  # deepseek-v2
T_VERIFY = 5                     # speculative chain: k = 4 drafts + 1
HBM_BYTES = 16 * 1024 ** 3       # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile()


def _tables():
    return [((SLOTS, N_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32)]


def _gqa_pools(kv_dtype):
    KV, hd = GQA["KV"], GQA["hd"]
    store = kvq.store_dtype(kv_dtype, jnp.bfloat16)
    pools = [((NUM_PAGES, KV, PAGE, hd), store)] * 2
    if kvq.is_quantized(kv_dtype):
        pools += [((NUM_PAGES, KV, 1, PAGE), jnp.float32)] * 2
    return pools


def _mla_pools(kv_dtype):
    r, dr = MLA["r"], MLA["dr"]
    store = kvq.store_dtype(kv_dtype, jnp.bfloat16)
    pools = [((NUM_PAGES, PAGE, r), store), ((NUM_PAGES, PAGE, dr), store)]
    if kvq.is_quantized(kv_dtype):
        pools += [((NUM_PAGES, 1, PAGE), jnp.float32)] * 2
    return pools


def _gqa_call(verify, kv_dtype, pipeline):
    kernel = pa.paged_attention_verify if verify else pa.paged_attention
    quantized = kvq.is_quantized(kv_dtype)

    def fn(q, bt, pos, k, v, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales)) if quantized else {}
        return kernel(q, k, v, bt, pos, scale=GQA["hd"] ** -0.5,
                      interpret=False, pipeline=pipeline, **kw)

    KV, G, hd = GQA["KV"], GQA["G"], GQA["hd"]
    q = ((SLOTS, T_VERIFY, KV, G, hd) if verify else (SLOTS, KV, G, hd),
         jnp.bfloat16)
    return fn, [q] + _tables() + _gqa_pools(kv_dtype)


def _mla_call(verify, kv_dtype, pipeline):
    kernel = pa.mla_paged_attention_verify if verify else pa.mla_paged_attention
    quantized = kvq.is_quantized(kv_dtype)

    def fn(ql, qr, bt, pos, c, r, *scales):
        kw = dict(zip(("c_scale", "r_scale"), scales)) if quantized else {}
        return kernel(ql, qr, c, r, bt, pos,
                      scale=(MLA["r"] + MLA["dr"]) ** -0.5,
                      interpret=False, pipeline=pipeline, **kw)

    H, r, dr = MLA["H"], MLA["r"], MLA["dr"]
    lead = (SLOTS, T_VERIFY, H) if verify else (SLOTS, H)
    queries = [(lead + (r,), jnp.bfloat16), (lead + (dr,), jnp.bfloat16)]
    return fn, queries + _tables() + _mla_pools(kv_dtype)


# What Mosaic still refuses in the double-buffered walks: their manual
# DMAs slice rows narrower than the 128-lane tiling out of the pools.
_DOUBLE_REFUSED = {
    "gqa-int8": "Slice shape along dimension 3 must be aligned to tiling "
                "(128), but is 16 (the (1, page) scale-row DMA)",
    "mla-bf16": "Slice shape along dimension 2 must be aligned to tiling "
                "(128), but is 64 (the 64-wide rope-page DMA)",
    "mla-int8": "Slice shape along dimension 2 must be aligned to tiling "
                "(128), but is 64 (the 64-wide rope-page DMA)",
}


def _kernel_cases():
    for pipeline in ("off", "double"):
        for kind in ("gqa", "mla"):
            for verify in (False, True):
                for kv_dtype in ("bf16", "int8"):
                    refused = (_DOUBLE_REFUSED.get(f"{kind}-{kv_dtype}")
                               if pipeline == "double" else None)
                    marks = ([pytest.mark.xfail(strict=True, reason=refused)]
                             if refused else [])
                    name = "verify" if verify else "decode"
                    yield pytest.param(
                        kind, verify, kv_dtype, pipeline, marks=marks,
                        id=f"{pipeline}-{kind}-{name}-{kv_dtype}")


@pytest.mark.parametrize("kind,verify,kv_dtype,pipeline", _kernel_cases())
def test_paged_kernel_compiles_for_v5e(one_chip, kind, verify, kv_dtype,
                                       pipeline):
    build = _gqa_call if kind == "gqa" else _mla_call
    fn, shapes = build(verify, kv_dtype, pipeline)
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_heads", [8, 2], ids=["one-chip", "tp4-shard"])
def test_live_walk_compiles_for_v5e_at_cell_widths(one_chip, kv_heads):
    """The live-page decode walk at the docqa cell's widths (16 slots,
    260-entry tables, 2816 pages), with all 8 KV heads of one chip and
    with the 2 a tp=4 shard holds."""
    B, n_blocks, num_pages = 16, 260, 2816
    G, hd = GQA["G"], GQA["hd"]

    def fn(q, bt, pos, k, v):
        return pa.paged_attention(q, k, v, bt, pos, scale=hd ** -0.5,
                                  interpret=False)

    pool = ((num_pages, kv_heads, PAGE, hd), jnp.bfloat16)
    compiled = _compile(fn, one_chip, ((B, kv_heads, G, hd), jnp.bfloat16),
                        ((B, n_blocks), jnp.int32), ((B,), jnp.int32),
                        pool, pool)
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_compiles_and_fits_v5e(one_chip, monkeypatch):
    """One whole paged decode step of qwen3-0.6b at published widths, with
    the Pallas kernels as a TPU process would resolve them, fits one
    chip's HBM."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = get_config("qwen3-0.6b")
    params = abstract_params(cfg)
    pools = shd.tree_abstract(paged_cache_defs(cfg, SLOTS, NUM_PAGES, PAGE))

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def step(p, pl, bt, tok, pos, act):
        return decode_step_paged(p, cfg, pl, bt, tok, pos, act,
                                 page_size=PAGE, backend="pallas")

    args = place((params, pools)) + tuple(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
            ((SLOTS, N_BLOCKS), jnp.int32), ((SLOTS, 1), jnp.int32),
            ((SLOTS,), jnp.int32), ((SLOTS,), jnp.bool_)])
    with _no_persistent_cache():
        compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total
