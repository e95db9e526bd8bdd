"""Paged-attention decode: Pallas kernel parity (interpret mode) vs the
jnp gather reference, across GQA/MLA shapes, ragged page counts, and idle
trash-page lanes — plus end-to-end engine byte-identity between the
pallas-dispatch path and the jnp reference path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config, smoke
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import init_params
from repro.serve import Engine, EngineConfig, GenerateConfig


def _ragged_tables(rng, B, n_blocks, page, num_pages):
    """Random ragged block tables: slot b owns 1..n_blocks live pages;
    dead entries stay 0 (the trash page)."""
    bt = np.zeros((B, n_blocks), np.int32)
    pos = np.zeros((B,), np.int32)
    free = list(range(1, num_pages))
    for b in range(B):
        live = rng.randint(1, n_blocks + 1)
        for j in range(live):
            bt[b, j] = free.pop()
        pos[b] = rng.randint(0, live * page)
    return jnp.asarray(bt), jnp.asarray(pos)


@pytest.mark.parametrize("B,KV,G,hd,page,nb", [
    (3, 2, 2, 16, 4, 5),      # GQA, odd block count
    (2, 4, 1, 32, 8, 3),      # MHA (G=1)
    (4, 1, 8, 64, 16, 2),     # MQA-style single KV head
])
def test_gqa_kernel_matches_reference(B, KV, G, hd, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 7 + nb), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _ragged_tables(np.random.RandomState(B), B, nb, page, P)
    scale = hd ** -0.5
    ref = pa.paged_attention_reference(q, kp, vp, bt, pos, scale=scale)
    out = pa.paged_attention(q, kp, vp, bt, pos, scale=scale,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_gqa_kernel_soft_cap():
    B, KV, G, hd, page, nb = 2, 2, 2, 16, 4, 3
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd)) * 4.0
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _ragged_tables(np.random.RandomState(3), B, nb, page, P)
    ref = pa.paged_attention_reference(q, kp, vp, bt, pos, scale=0.25,
                                       soft_cap=30.0)
    out = pa.paged_attention(q, kp, vp, bt, pos, scale=0.25, soft_cap=30.0,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_gqa_kernel_idle_trash_lane_is_finite():
    """An idle lane (pos=0, all-trash block table) must produce finite
    garbage, exactly like the reference — the engine discards it."""
    B, KV, G, hd, page, nb = 2, 2, 2, 16, 4, 3
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt = jnp.zeros((B, nb), jnp.int32)        # every lane idle -> trash page
    pos = jnp.zeros((B,), jnp.int32)
    out = pa.paged_attention(q, kp, vp, bt, pos, scale=hd ** -0.5,
                             interpret=True)
    ref = pa.paged_attention_reference(q, kp, vp, bt, pos, scale=hd ** -0.5)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


# -- live-page walk (pipeline="off", unquantized pools) --------------------
# One program per slot walks only that slot's live pages, C pages of every
# KV head per loop step.  Each case packs the edges of that walk into the
# lanes of one batch: an idle all-trash lane, a single live token, a
# context ending exactly on a chunk edge and one just past it, a full
# table, and a ragged one.

def _edge_tables(rng, nb, page, chunk, num_pages):
    ends = [None, 0, chunk * page - 1, min(chunk * page, nb * page - 1),
            nb * page - 1, rng.randint(0, nb * page)]
    bt = np.zeros((len(ends), nb), np.int32)
    pos = np.zeros((len(ends),), np.int32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    for b, end in enumerate(ends):
        if end is None:                       # idle lane: pos 0, all trash
            continue
        for j in range(end // page + 1):
            bt[b, j] = free.pop()
        pos[b] = end
    return jnp.asarray(bt), jnp.asarray(pos)


def _edge_case(KV, G, hd, page, nb, seed):
    chunk = min(nb, pa.live_walk_chunk_pages(page, KV, hd, 4))
    P = 1 + 6 * nb
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (6, KV, G, hd)) * 2.0
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _edge_tables(np.random.RandomState(seed), nb, page, chunk, P)
    return q, kp, vp, bt, pos


@pytest.mark.parametrize("soft_cap", [0.0, 30.0])
@pytest.mark.parametrize("KV,G,hd,page,nb", [
    (1, 8, 64, 16, 40),       # MQA-style, C = 16: three chunks
    (2, 2, 16, 8, 70),        # GQA, C = 32: three chunks
    (8, 1, 32, 4, 24),        # MHA (G=1), page 4: one chunk of the table
    (8, 2, 128, 16, 20),      # qwen3-0.6b heads, C = 16: two chunks
])
def test_live_walk_matches_reference(KV, G, hd, page, nb, soft_cap):
    q, kp, vp, bt, pos = _edge_case(KV, G, hd, page, nb, KV * 31 + page)
    kw = dict(scale=hd ** -0.5, soft_cap=soft_cap)
    ref = pa.paged_attention_reference(q, kp, vp, bt, pos, **kw)
    out = pa.paged_attention(q, kp, vp, bt, pos, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_live_walk_never_reads_dead_pages():
    """Trash and unused pool pages full of NaN, and VMEM scratch starting
    as NaN: the walk copies only live pages and zeroes the V rows it did
    not copy, so every live lane stays finite and matches the reference
    over a clean pool."""
    KV, G, hd, page, nb = 2, 2, 16, 16, 40
    q, kp, vp, bt, pos = _edge_case(KV, G, hd, page, nb, 3)
    live = slice(1, None)                     # lane 0 is idle: trash only
    q, bt, pos = q[live], bt[live], pos[live]
    used = np.zeros((kp.shape[0],), bool)
    for b in range(bt.shape[0]):
        used[np.asarray(bt[b, :int(pos[b]) // page + 1])] = True
    dead = jnp.asarray(~used)[:, None, None, None]
    kp_nan = jnp.where(dead, jnp.nan, kp)
    vp_nan = jnp.where(dead, jnp.nan, vp)
    out = pa.paged_attention(
        q, kp_nan, vp_nan, bt, pos, scale=hd ** -0.5,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    ref = pa.paged_attention_reference(q, kp, vp, bt, pos, scale=hd ** -0.5)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("page,KV,hd,itemsize,chunk", [
    (16, 8, 128, 2, 16),      # qwen3-0.6b: 256 keys, 2 MiB of slabs
    (16, 2, 128, 2, 16),      # its tp=4 shard
    (4, 2, 16, 4, 64),
    (16, 32, 256, 2, 4),      # wide heads: halved under the VMEM cap
])
def test_live_walk_chunk_pages(page, KV, hd, itemsize, chunk):
    assert pa.live_walk_chunk_pages(page, KV, hd, itemsize) == chunk
    slabs = 4 * KV * chunk * page * hd * itemsize
    assert slabs <= pa.LIVE_WALK_VMEM_BYTES


def test_live_walk_pages_counts_live_entries():
    pos = np.array([0, 0, 15, 16, 4159, 9999], np.int32)
    # idle and single-token lanes walk one page; the last is capped
    assert pa.live_walk_pages(pos, 16, 260) == 1 + 1 + 1 + 2 + 260 + 260


def test_live_walk_engages_only_off_and_unquantized():
    assert pa.walks_live_pages(quantized=False, pipeline="off")
    assert not pa.walks_live_pages(quantized=True, pipeline="off")
    assert not pa.walks_live_pages(quantized=False, pipeline="double")


@pytest.mark.parametrize("B,H,r,dr,page,nb", [
    (3, 4, 32, 8, 4, 4),
    (2, 8, 64, 16, 8, 2),
])
def test_mla_kernel_matches_reference(B, H, r, dr, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 13 + nb), 4)
    ql = jax.random.normal(ks[0], (B, H, r))
    qr = jax.random.normal(ks[1], (B, H, dr))
    cp = jax.random.normal(ks[2], (P, page, r))
    rp = jax.random.normal(ks[3], (P, page, dr))
    bt, pos = _ragged_tables(np.random.RandomState(B + 1), B, nb, page, P)
    scale = (r + dr) ** -0.5
    ref = pa.mla_paged_attention_reference(ql, qr, cp, rp, bt, pos,
                                           scale=scale)
    out = pa.mla_paged_attention(ql, qr, cp, rp, bt, pos, scale=scale,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("B,T,KV,G,hd,page,nb", [
    (3, 4, 2, 2, 16, 4, 5),   # GQA, odd block count
    (2, 2, 4, 1, 32, 8, 3),   # MHA (G=1)
    (2, 5, 1, 8, 64, 16, 2),  # MQA-style single KV head
])
def test_gqa_verify_kernel_matches_reference(B, T, KV, G, hd, page, nb):
    """Multi-token verification kernel vs the gather reference, ragged
    contexts: all T query rows share one page walk, per-row causal mask
    ``k_pos <= pos + t``."""
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 17 + T), 3)
    q = jax.random.normal(ks[0], (B, T, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _ragged_tables(np.random.RandomState(B + T), B, nb, page, P)
    scale = hd ** -0.5
    ref = pa.paged_attention_verify_reference(q, kp, vp, bt, pos,
                                              scale=scale, soft_cap=20.0)
    out = pa.paged_attention_verify(q, kp, vp, bt, pos, scale=scale,
                                    soft_cap=20.0, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("B,T,H,r,dr,page,nb", [
    (3, 3, 4, 32, 8, 4, 4),
    (2, 5, 8, 64, 16, 8, 2),
])
def test_mla_verify_kernel_matches_reference(B, T, H, r, dr, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 19 + T), 4)
    ql = jax.random.normal(ks[0], (B, T, H, r))
    qr = jax.random.normal(ks[1], (B, T, H, dr))
    cp = jax.random.normal(ks[2], (P, page, r))
    rp = jax.random.normal(ks[3], (P, page, dr))
    bt, pos = _ragged_tables(np.random.RandomState(B + T + 1), B, nb, page,
                             P)
    scale = (r + dr) ** -0.5
    ref = pa.mla_paged_attention_verify_reference(ql, qr, cp, rp, bt, pos,
                                                  scale=scale)
    out = pa.mla_paged_attention_verify(ql, qr, cp, rp, bt, pos,
                                        scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_verify_t1_matches_decode_reference():
    """A 1-token verification IS a decode step: both references must agree
    exactly (the contract that lets T=1 reasoning carry over)."""
    B, KV, G, hd, page, nb = 2, 2, 2, 16, 4, 3
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(23), 3)
    q = jax.random.normal(ks[0], (B, 1, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _ragged_tables(np.random.RandomState(7), B, nb, page, P)
    dec = pa.paged_attention_reference(q[:, 0], kp, vp, bt, pos,
                                       scale=hd ** -0.5)
    ver = pa.paged_attention_verify_reference(q, kp, vp, bt, pos,
                                              scale=hd ** -0.5)[:, 0]
    np.testing.assert_allclose(np.asarray(ver), np.asarray(dec),
                               rtol=1e-6, atol=1e-7)


# -- double-buffered page streaming (pipeline="double") --------------------
# The manual-DMA kernels prefetch page b+1 into a second VMEM slab while
# computing page b; the schedule changes, the per-block f32 op sequence
# does not — so parity with the single-buffered kernel is BITWISE, not
# approximate, across ragged page counts and idle trash lanes.

@pytest.mark.parametrize("B,KV,G,hd,page,nb", [
    (3, 2, 2, 16, 4, 5),      # GQA, odd block count
    (2, 4, 1, 32, 8, 3),      # MHA (G=1)
    (4, 1, 8, 64, 16, 2),     # MQA-style single KV head
])
def test_gqa_pipeline_double_bitwise(B, KV, G, hd, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 7 + nb), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _ragged_tables(np.random.RandomState(B), B, nb, page, P)
    kw = dict(scale=hd ** -0.5, soft_cap=25.0, interpret=True)
    off = pa.paged_attention_blocks(q, kp, vp, bt, pos, **kw)
    dbl = pa.paged_attention(q, kp, vp, bt, pos, pipeline="double", **kw)
    np.testing.assert_array_equal(np.asarray(dbl), np.asarray(off))
    ref = pa.paged_attention_reference(q, kp, vp, bt, pos, scale=hd ** -0.5,
                                       soft_cap=25.0)
    np.testing.assert_allclose(np.asarray(dbl), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("B,H,r,dr,page,nb", [
    (3, 4, 32, 8, 4, 4),
    (2, 8, 64, 16, 8, 2),
])
def test_mla_pipeline_double_bitwise(B, H, r, dr, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 13 + nb), 4)
    ql = jax.random.normal(ks[0], (B, H, r))
    qr = jax.random.normal(ks[1], (B, H, dr))
    cp = jax.random.normal(ks[2], (P, page, r))
    rp = jax.random.normal(ks[3], (P, page, dr))
    bt, pos = _ragged_tables(np.random.RandomState(B + 1), B, nb, page, P)
    kw = dict(scale=(r + dr) ** -0.5, interpret=True)
    off = pa.mla_paged_attention(ql, qr, cp, rp, bt, pos, **kw)
    dbl = pa.mla_paged_attention(ql, qr, cp, rp, bt, pos,
                                 pipeline="double", **kw)
    np.testing.assert_array_equal(np.asarray(dbl), np.asarray(off))
    ref = pa.mla_paged_attention_reference(ql, qr, cp, rp, bt, pos,
                                           scale=(r + dr) ** -0.5)
    np.testing.assert_allclose(np.asarray(dbl), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("B,T,KV,G,hd,page,nb", [
    (3, 4, 2, 2, 16, 4, 5),
    (2, 5, 1, 8, 64, 16, 2),
])
def test_gqa_verify_pipeline_double_bitwise(B, T, KV, G, hd, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 17 + T), 3)
    q = jax.random.normal(ks[0], (B, T, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt, pos = _ragged_tables(np.random.RandomState(B + T), B, nb, page, P)
    kw = dict(scale=hd ** -0.5, soft_cap=20.0, interpret=True)
    off = pa.paged_attention_verify(q, kp, vp, bt, pos, **kw)
    dbl = pa.paged_attention_verify(q, kp, vp, bt, pos, pipeline="double",
                                    **kw)
    np.testing.assert_array_equal(np.asarray(dbl), np.asarray(off))
    ref = pa.paged_attention_verify_reference(q, kp, vp, bt, pos,
                                              scale=hd ** -0.5,
                                              soft_cap=20.0)
    np.testing.assert_allclose(np.asarray(dbl), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("B,T,H,r,dr,page,nb", [
    (3, 3, 4, 32, 8, 4, 4),
    (2, 5, 8, 64, 16, 8, 2),
])
def test_mla_verify_pipeline_double_bitwise(B, T, H, r, dr, page, nb):
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(B * 19 + T), 4)
    ql = jax.random.normal(ks[0], (B, T, H, r))
    qr = jax.random.normal(ks[1], (B, T, H, dr))
    cp = jax.random.normal(ks[2], (P, page, r))
    rp = jax.random.normal(ks[3], (P, page, dr))
    bt, pos = _ragged_tables(np.random.RandomState(B + T + 1), B, nb, page,
                             P)
    kw = dict(scale=(r + dr) ** -0.5, interpret=True)
    off = pa.mla_paged_attention_verify(ql, qr, cp, rp, bt, pos, **kw)
    dbl = pa.mla_paged_attention_verify(ql, qr, cp, rp, bt, pos,
                                        pipeline="double", **kw)
    np.testing.assert_array_equal(np.asarray(dbl), np.asarray(off))
    ref = pa.mla_paged_attention_verify_reference(ql, qr, cp, rp, bt, pos,
                                                  scale=(r + dr) ** -0.5)
    np.testing.assert_allclose(np.asarray(dbl), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_pipeline_double_idle_trash_lane_is_finite():
    """The double-buffered kernel prefetches through all-trash block
    tables too (every DMA source is the trash page); idle lanes must
    stay finite and bitwise-match the single-buffered kernel."""
    B, KV, G, hd, page, nb = 2, 2, 2, 16, 4, 3
    P = 1 + B * nb
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    kp = jax.random.normal(ks[1], (P, KV, page, hd))
    vp = jax.random.normal(ks[2], (P, KV, page, hd))
    bt = jnp.zeros((B, nb), jnp.int32)        # every lane idle -> trash page
    pos = jnp.zeros((B,), jnp.int32)
    off = pa.paged_attention(q, kp, vp, bt, pos, scale=hd ** -0.5,
                             interpret=True)
    dbl = pa.paged_attention(q, kp, vp, bt, pos, scale=hd ** -0.5,
                             interpret=True, pipeline="double")
    assert np.isfinite(np.asarray(dbl)).all()
    np.testing.assert_array_equal(np.asarray(dbl), np.asarray(off))


def test_pipeline_rejects_unknown_mode():
    B, KV, G, hd, page, nb = 2, 2, 2, 16, 4, 3
    P = 1 + B * nb
    q = jnp.zeros((B, KV, G, hd))
    kp = jnp.zeros((P, KV, page, hd))
    vp = jnp.zeros((P, KV, page, hd))
    bt = jnp.zeros((B, nb), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    with pytest.raises(ValueError):
        pa.paged_attention(q, kp, vp, bt, pos, scale=1.0, interpret=True,
                           pipeline="triple")


def test_registry_resolves_backends():
    impls = ops.registered_kernels()
    assert {"paged_attention", "mla_paged_attention",
            "paged_attention_verify", "mla_paged_attention_verify",
            "flash_attention"} <= set(impls)
    assert ops.resolve("paged_attention", "jnp") \
        is pa.paged_attention_reference
    # pallas resolution binds interpret for this (CPU) process
    fn = ops.resolve("paged_attention", "pallas")
    assert fn.func is pa.paged_attention
    assert fn.keywords["interpret"] == (jax.default_backend() != "tpu")
    with ops.use_backend("jnp"):
        assert ops.resolve("mla_paged_attention") \
            is pa.mla_paged_attention_reference
    assert ops.default_backend() == "auto"
    with pytest.raises(ValueError):
        ops.resolve("paged_attention", "mosaic")


def test_registry_resolves_pipeline():
    """pipeline="double" binds into the pallas partial of pipelined ops
    only; the jnp reference has no pages to stream, and non-paged ops
    reject the request outright."""
    fn = ops.resolve("paged_attention", "pallas", pipeline="double")
    assert fn.func is pa.paged_attention
    assert fn.keywords["pipeline"] == "double"
    assert ops.resolve("paged_attention", "pallas").keywords["pipeline"] \
        == "off"
    # the reference path ignores the schedule — there is nothing to stream
    assert ops.resolve("paged_attention", "jnp", pipeline="double") \
        is pa.paged_attention_reference
    # flash_attention is not a paged streaming kernel
    with pytest.raises(ValueError):
        ops.resolve("flash_attention", "pallas", pipeline="double")
    with pytest.raises(ValueError):
        ops.resolve("paged_attention", "pallas", pipeline="triple")
    assert ops.default_pipeline() == "off"
    with ops.use_pipeline("double"):
        assert ops.resolve("mla_paged_attention", "pallas") \
            .keywords["pipeline"] == "double"
    assert ops.default_pipeline() == "off"


# -- end-to-end: engine tokens, pallas dispatch vs jnp reference ------------

def _engine_tokens(cfg, params, backend, arch_seed, pipeline="off"):
    eng = Engine(cfg, params, EngineConfig(
        num_slots=2, page_size=4, max_len=32, kernel_backend=backend,
        pipeline=pipeline))
    gen = GenerateConfig(max_new_tokens=6)
    prompts = [np.asarray(jax.random.randint(
        jax.random.key(arch_seed + i), (5 + i,), 0, cfg.vocab_size))
        for i in range(3)]
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    return [list(r.generated) for r in reqs]


@pytest.mark.parametrize("arch,seed", [("qwen3-0.6b", 100),
                                       ("deepseek-v2-236b", 200)])
def test_engine_pallas_dispatch_byte_identical(arch, seed):
    """Continuous-engine output with the Pallas kernels (interpret mode)
    is byte-identical to the jnp reference path — dense GQA and MLA."""
    cfg = smoke(get_config(arch))
    params = init_params(cfg, jax.random.key(0))
    tok_jnp = _engine_tokens(cfg, params, "jnp", seed)
    tok_pallas = _engine_tokens(cfg, params, "pallas", seed)
    assert tok_jnp == tok_pallas


@pytest.mark.parametrize("arch,seed", [("qwen3-0.6b", 100),
                                       ("deepseek-v2-236b", 200)])
def test_engine_pipeline_double_byte_identical(arch, seed):
    """End-to-end: the engine with the double-buffered page walk emits
    byte-identical greedy tokens to the single-buffered pallas path AND
    the jnp reference — GQA decode and MLA latent decode."""
    cfg = smoke(get_config(arch))
    params = init_params(cfg, jax.random.key(0))
    tok_jnp = _engine_tokens(cfg, params, "jnp", seed)
    tok_dbl = _engine_tokens(cfg, params, "pallas", seed,
                             pipeline="double")
    assert tok_jnp == tok_dbl


def test_engine_pallas_dispatch_mla_absorb_equivalent():
    """mla_absorb only changes compute order; the paged path runs latent
    -space attention either way and tokens must agree."""
    cfg = smoke(get_config("deepseek-v2-236b"))
    params = init_params(cfg, jax.random.key(0))
    base = _engine_tokens(cfg, params, "jnp", 300)
    absorbed = _engine_tokens(
        dataclasses.replace(cfg, mla_absorb=True), params, "pallas", 300)
    assert base == absorbed


def test_mla_continuous_matches_static_byte_for_byte():
    """MLA continuous-vs-static byte identity (the contract the attn/xlstm
    tests pin for their cache families).  MoE-free MLA config so expert
    -capacity discontinuities can't confound; mla_absorb=True so the
    static dense decode runs the same latent form the paged path always
    uses."""
    from repro.models.common import BlockDef
    from repro.serve import StaticEngine
    cfg = smoke(get_config("deepseek-v2-236b"))
    cfg = dataclasses.replace(
        cfg, name="mla-dense-smoke", mla_absorb=True, n_experts=0,
        moe_top_k=0, moe_d_ff=0, n_shared_experts=0, moe_first_dense=0,
        n_layers=2, block_pattern=(BlockDef("mla", "dense"),))
    params = init_params(cfg, jax.random.key(0))
    gen = GenerateConfig(max_new_tokens=6)
    prompts = [np.asarray(jax.random.randint(
        jax.random.key(400 + i), (5 + i,), 0, cfg.vocab_size))
        for i in range(3)]
    eng = Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                           max_len=32))
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    static = StaticEngine(cfg, params)
    for p, r in zip(prompts, reqs):
        ref = static.generate(jnp.asarray(p[None]), gen)
        np.testing.assert_array_equal(
            np.asarray(r.generated),
            np.asarray(ref["tokens"])[0, len(p):])
