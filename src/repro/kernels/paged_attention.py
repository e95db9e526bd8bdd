"""Paged-attention decode — Pallas TPU kernels + jnp references.

The serving hot loop: one query token per decode slot attends to that
slot's whole KV history, which lives in *physical pages* shared across
slots (see serve/kv_cache.py).  The jnp reference materializes the
gathered (B, S, KV, hd) K/V to HBM before attending — per the roofline
model that doubles the dominant Q term of the most memory-bound workload
we serve.  The Pallas kernels stream each page HBM->VMEM exactly once and
keep scores/softmax state in VMEM, so HBM traffic collapses to

    Q_kernel ~= (context_len + 1) * kv_line_bytes  +  q/o vectors

— the ledger's analytic model (scheduler.decode_token_bytes), which is why
the per-request ledger and the HLO cross-check can agree on W/Q for the
decode step (the ``paged_attention`` named scope marks the region;
core/roofline/hlo_cost.TRACKED_SCOPES prices it, substitute.py swaps the
reference's gather traffic for the kernel's).

Kernel layout (GQA decode, the live-page walk):
  grid (num_slots,); the block table and per-slot positions ride in as
  *scalar prefetch*, and each program loops over only its slot's live
  pages, ``pos // page + 1`` of them, C pages per loop step.  The pools
  are head-major, (P, KV, page, hd), so one page of every KV head is one
  contiguous (KV, page, hd) block; explicit async copies land C of them
  in a (KV, C * page, hd) VMEM slab per stream, double-buffered across
  loop steps, and each head runs one online-softmax update over its
  (G, C * page) scores per step.  Dead table entries get no copy.

Per-block grid walk (quantized pools; the bitwise partner of
``pipeline="double"``; the verify kernels):
  grid (num_slots, kv_heads, n_blocks); per grid step one (page, hd) K
  slab and V slab of the mapped KV head are resident in VMEM — the
  trailing two dims of the head-major pool, the only single-head block
  Mosaic accepts.  Pallas double-buffers the page fetches across the
  innermost grid dim; online softmax carries (m, l, acc) in VMEM scratch
  across the walk of every table entry, dead ones included; the output
  block is written on the last block.  A grid step costs a fixed ~0.35
  us on v5e, which is why decode no longer takes this path.

MLA variant: attention runs entirely in the compressed latent space
(absorbed form, DeepSeek-V2 §5): scores = q_lat @ c_kv^T + q_rope @
k_rope^T over (page, kv_lora + rope_hd) slabs, acc accumulates w @ c_kv.
The cache line is ~57x smaller than the equivalent GQA line, so decode
intensity I = W/Q rises by the same factor — the paper's eq. 1 lever.

VMEM budget: the live-page walk's four (KV, C * page, hd) slabs are held
under 4 MiB (2 MiB for qwen3-0.6b); per-block grid steps hold 2 *
page_size * hd K/V slabs + (G, hd) q/acc + 2 * (G, 1) carries (GQA) or
page_size * (r + rope_hd) slabs + (H, r + rope_hd) queries + (H, r) acc
(MLA), well under 1 MiB — all far below the ~16 MiB/core limit.

Ragged contexts: slots own different numbers of live pages; dead block
-table entries point at the reserved trash page (physical page 0), which
the live-page walk never copies and the per-block walks mask: the
``k_pos <= pos`` mask zeroes their probability exactly.  Idle lanes
(pos = 0, all-trash tables) compute a harmless garbage row the engine
discards — same contract as the jnp reference.

Multi-token verification (speculative decoding): the ``*_verify`` variants
score T = k+1 query tokens per slot against the same paged KV in ONE page
walk.  Query t sits at position ``pos + t`` and is causally masked to
``k_pos <= pos + t`` — token t attends the committed context plus the
drafted tokens before it, exactly the sequential decode it replaces.  The
kernels flatten the (T, G) / (T, H) query rows into one VMEM slab so the
block walk, the scalar-prefetched table, and the online-softmax carries
are shared across all T tokens: HBM traffic stays ~one page walk while
the FLOPs scale by T — the roofline lever speculative decoding exists to
pull (measured intensity -> (k+1) * I at the same memory ceiling).

Pipelined page streaming (``pipeline="double"``): every public kernel also
ships a two-stage double-buffered variant that drops the block dim from
the grid and walks the table inside the kernel with EXPLICIT async DMAs —
two VMEM slabs per stream, DMA semaphores, and a one-block lookahead:
start the copy of page b+1 into slab ``1 - (b % 2)`` before waiting on
page b, so the HBM->VMEM transfer of the next page hides behind the
current page's flash-attention math.  The compute per block is the exact
op sequence of the single-buffered per-block kernel (same f32 online-
softmax chain, same order), so ``pipeline="double"`` is bit-identical to
the per-block grid walk (``paged_attention_blocks`` for GQA decode, and
``"off"`` for the other kernels); the
q/o slabs are fetched ONCE per program instead of re-read per grid step,
which the VMEM pricing below reflects (``pipeline`` kwarg).  Selection
rides the kernel registry (kernels/ops.py ``pipeline=off|double``),
keeping the single-buffered kernel and the jnp gather the byte-checked
references.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Quantized KV pools (kernels/quantize.py): every kernel and reference
# below optionally takes float32 scale pools alongside the K/V (or latent)
# pools — GQA scales (P, KV, 1, page) per (page, kv_head, line), MLA
# scales (P, 1, page) per (page, line).  The trailing (1, page) row is a
# block Mosaic accepts (it equals the array's trailing dims), and a grid
# step reads exactly its own head's page of scales; the kernel turns the
# row into a (page, 1) column with an exact select (``_row_to_column``).
# Dequantization is ``values.astype(f32) * scale`` applied to each
# streamed slab BEFORE the score matmul, the same op sequence in the
# Pallas walk and the jnp gather, so the oracle stays byte-comparable.
# Dequant happens in VMEM after the (smaller) quantized page crossed
# HBM->VMEM — bandwidth-free on the HBM level the decode roofline is
# bound by.


# --------------------------------------------------------------------------
# jnp references (the byte-checked oracles; extracted verbatim from the
# pre-registry models/attention.py + models/mla.py inline gathers)
# --------------------------------------------------------------------------

def _gather_kv(pool, scale_pool, block_tables, B, S, KV, hd):
    """Gather head-major pages (P, KV, page, hd) to (B, S, KV, hd),
    dequantizing when a scale pool is supplied (scale (P, KV, 1, page) ->
    broadcast over hd)."""
    g = jnp.swapaxes(pool[block_tables], 2, 3).reshape(B, S, KV, hd)
    if scale_pool is None:
        return g
    s = jnp.swapaxes(scale_pool[block_tables][:, :, :, 0], 2, 3
                     ).reshape(B, S, KV)
    return g.astype(jnp.float32) * s[..., None]


def _gather_latent(pool, scale_pool, block_tables, B, S):
    """Gather latent pages to (B, S, d), dequantizing when quantized
    (scale (P, 1, page))."""
    g = pool[block_tables].reshape(B, S, -1)
    if scale_pool is None:
        return g
    s = scale_pool[block_tables].reshape(B, S)
    return g.astype(jnp.float32) * s[..., None]


def paged_attention_reference(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, pos: jax.Array, *,
    scale: float, soft_cap: float = 0.0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """GQA paged decode, gather-and-attend.

    q (B, KV, G, hd); k/v pools (P, KV, page, hd); block_tables
    (B, n_blocks); pos (B,) last written position.  Returns (B, KV, G, hd).
    ``k_scale``/``v_scale`` (P, KV, 1, page) float32 dequantize a
    quantized pool before attending.
    """
    B = q.shape[0]
    _, KV, page_size, hd = k_pool.shape
    S = block_tables.shape[1] * page_size
    posb = pos.astype(jnp.int32)[:, None]                       # (B, 1)
    k = _gather_kv(k_pool, k_scale, block_tables, B, S, KV, hd)
    v = _gather_kv(v_pool, v_scale, block_tables, B, S, KV, hd)
    qb = q[:, None]                                             # (B,1,KV,G,hd)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    s = jnp.einsum("bqkgh,bskh->bkgqs", qb, k).astype(jnp.float32) * scale
    if soft_cap > 0:
        s = jnp.tanh(s / soft_cap) * soft_cap
    m = posb[:, :, None] >= k_pos[:, None, :]                   # (B, 1, S)
    s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
    p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p_attn, v)
    return o[:, 0].astype(q.dtype)


def paged_attention_verify_reference(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, pos: jax.Array, *,
    scale: float, soft_cap: float = 0.0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """GQA multi-token paged verification, gather-and-attend.

    q (B, T, KV, G, hd) — T query tokens per slot at positions
    ``pos + t``; k/v pools (P, KV, page, hd); block_tables (B, n_blocks);
    pos (B,) position of the FIRST query token.  Returns (B, T, KV, G, hd).
    """
    B, T = q.shape[0], q.shape[1]
    _, KV, page_size, hd = k_pool.shape
    S = block_tables.shape[1] * page_size
    k = _gather_kv(k_pool, k_scale, block_tables, B, S, KV, hd)
    v = _gather_kv(v_pool, v_scale, block_tables, B, S, KV, hd)
    q_pos = pos.astype(jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    s = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32) * scale
    if soft_cap > 0:
        s = jnp.tanh(s / soft_cap) * soft_cap
    m = q_pos[:, :, None] >= k_pos[:, None, :]                  # (B, T, S)
    s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
    p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgts,bskh->btkgh", p_attn, v).astype(q.dtype)


def mla_paged_attention_reference(
    q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
    r_pool: jax.Array, block_tables: jax.Array, pos: jax.Array, *,
    scale: float,
    c_scale: Optional[jax.Array] = None,
    r_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """MLA paged decode in the compressed latent space (absorbed form).

    q_lat (B, H, r) — q_nope already folded through wk_b; q_rope (B, H, dr);
    c/r pools (P, page, r) / (P, page, dr); pos (B,).  Returns o_lat
    (B, H, r) — the caller folds wv_b/wo back out.  ``c_scale``/``r_scale``
    (P, 1, page) float32 dequantize a quantized latent pool.
    """
    B = q_lat.shape[0]
    page_size = c_pool.shape[1]
    S = block_tables.shape[1] * page_size
    c_kv = _gather_latent(c_pool, c_scale, block_tables, B, S)
    k_rope = _gather_latent(r_pool, r_scale, block_tables, B, S)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c_kv)
         + jnp.einsum("bhk,bsk->bhs", q_rope, k_rope))
    s = s.astype(jnp.float32) * scale
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(c_kv.dtype)
    return jnp.einsum("bhs,bsr->bhr", w, c_kv).astype(q_lat.dtype)


def mla_paged_attention_verify_reference(
    q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
    r_pool: jax.Array, block_tables: jax.Array, pos: jax.Array, *,
    scale: float,
    c_scale: Optional[jax.Array] = None,
    r_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """MLA multi-token paged verification in the compressed latent space.

    q_lat (B, T, H, r); q_rope (B, T, H, dr); pools (P, page, r) /
    (P, page, dr); pos (B,) position of the first query token.  Returns
    o_lat (B, T, H, r).
    """
    B, T = q_lat.shape[0], q_lat.shape[1]
    page_size = c_pool.shape[1]
    S = block_tables.shape[1] * page_size
    c_kv = _gather_latent(c_pool, c_scale, block_tables, B, S)
    k_rope = _gather_latent(r_pool, r_scale, block_tables, B, S)
    s = (jnp.einsum("bthr,bsr->bhts", q_lat, c_kv)
         + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope))
    s = s.astype(jnp.float32) * scale
    q_pos = pos.astype(jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)
    valid = q_pos[:, :, None] >= jnp.arange(S, dtype=jnp.int32)[None, None, :]
    s = jnp.where(valid[:, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(c_kv.dtype)
    return jnp.einsum("bhts,bsr->bthr", w, c_kv).astype(q_lat.dtype)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------

PIPELINES = ("off", "double")


def _check_pipeline(pipeline: str) -> None:
    if pipeline not in PIPELINES:
        raise ValueError(f"pipeline {pipeline!r} not in {PIPELINES}")


def _row_to_column(row: jax.Array) -> jax.Array:
    """(1, n) scale row -> (n, 1) column through a diagonal select and a
    lane sum: one nonzero term per row, so the value is exact, and no
    unaligned transpose reaches Mosaic."""
    n = row.shape[1]
    sq = jnp.broadcast_to(row, (n, n))
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, sq, 0.0), axis=1, keepdims=True)


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                         page_size: int, scale: float, soft_cap: float,
                         quantized: bool = False):
    """One (slot, kv_head, block) grid step of the per-block GQA decode
    walk.  When ``quantized`` two float32 (1, page) scale rows of the
    mapped head follow k/v and dequantize the streamed slab in VMEM."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(2)
    n_blocks = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (G, hd)
    k = k_ref[...].astype(jnp.float32)                      # (page, hd)
    v = v_ref[...].astype(jnp.float32)
    if quantized:
        k = k * _row_to_column(ks_ref[...])
        v = v * _row_to_column(vs_ref[...])
    s = (q @ k.T) * scale                                   # (G, page)
    if soft_cap > 0:
        s = jnp.tanh(s / soft_cap) * soft_cap
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(k_pos <= pos_ref[b], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _gqa_page_specs(page_size: int, hd: int, quantized: bool):
    """K, V (and scale) BlockSpecs of a (slot, kv_head, block) grid: one
    head's (page, hd) slab of the head-major pools and its (1, page) scale
    row, of the physical page the block table maps."""
    kv = pl.BlockSpec((None, None, page_size, hd),
                      lambda b, h, j, bt, ps: (bt[b, j], h, 0, 0))
    specs = [kv, kv]
    if quantized:
        specs += [pl.BlockSpec((None, None, 1, page_size),
                               lambda b, h, j, bt, ps: (bt[b, j], h, 0, 0))
                  ] * 2
    return specs


def paged_attention(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, pos: jax.Array, *,
    scale: float, soft_cap: float = 0.0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False, pipeline: str = "off",
) -> jax.Array:
    """Pallas GQA paged decode; same contract as the reference.

    ``pipeline="off"`` runs the live-page walk on unquantized pools and
    the per-block grid walk (:func:`paged_attention_blocks`) on quantized
    ones; ``"double"`` runs the double-buffered per-block walk."""
    _check_pipeline(pipeline)
    G = q.shape[2]
    if pipeline == "double":
        return _gqa_paged_double(
            q, k_pool, v_pool, block_tables, pos, n_group=G, scale=scale,
            soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
            interpret=interpret)
    if walks_live_pages(quantized=k_scale is not None, pipeline=pipeline):
        return _gqa_live_walk(q, k_pool, v_pool, block_tables, pos,
                              scale=scale, soft_cap=soft_cap,
                              interpret=interpret)
    return paged_attention_blocks(
        q, k_pool, v_pool, block_tables, pos, scale=scale,
        soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        interpret=interpret)


def paged_attention_blocks(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, pos: jax.Array, *,
    scale: float, soft_cap: float = 0.0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """The per-block grid walk, grid (B, KV, n_blocks): one head's page
    per grid step over every table entry, dead ones included.  It serves
    quantized pools and is the bitwise partner of ``pipeline="double"``."""
    B, KV, G, hd = q.shape
    page_size = k_pool.shape[2]
    n_blocks = block_tables.shape[1]
    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, scale=scale,
        soft_cap=soft_cap, quantized=quantized)
    in_specs = [pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, j, bt, ps: (b, h, 0, 0))]
    in_specs += _gqa_page_specs(page_size, hd, quantized)
    args = [block_tables, pos.astype(jnp.int32), q, k_pool, v_pool]
    if quantized:
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block tables + positions
        grid=(B, KV, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, bt, ps: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention_blocks",
    )(*args)


# Live-page walk: the GQA decode kernel for unquantized pools.  A step of
# the per-block grid costs a fixed ~0.35 us on v5e whatever its work, and
# that grid walks B x KV x n_blocks steps, dead table entries included.
# This walk runs one program per slot and loops over that slot's live
# pages only, C pages of all KV heads per loop step.

LIVE_WALK_KEYS = 256                 # keys (C * page) one loop step aims for
LIVE_WALK_VMEM_BYTES = 4 << 20       # cap on the K+V slabs, both buffers


def walks_live_pages(*, quantized: bool, pipeline: str) -> bool:
    """Whether :func:`paged_attention` runs the live-page walk: decode at
    ``pipeline="off"`` over unquantized pools."""
    return pipeline == "off" and not quantized


def live_walk_chunk_pages(page_size: int, kv_heads: int, head_dim: int,
                          itemsize: int) -> int:
    """Pages C the live-page walk copies per loop step: C * page ~ 256
    keys, halved until the double-buffered K and V slabs, 4 x (KV,
    C * page, hd), fit :data:`LIVE_WALK_VMEM_BYTES`.  qwen3-0.6b (KV 8,
    hd 128, bf16, page 16): C = 16, 2 MiB of slabs."""
    c = max(1, LIVE_WALK_KEYS // page_size)
    while c > 1 and (4 * kv_heads * c * page_size * head_dim * itemsize
                     > LIVE_WALK_VMEM_BYTES):
        c //= 2
    return c


def live_walk_pages(pos: np.ndarray, page_size: int, n_blocks: int) -> int:
    """Table entries one live-page walk copies over host positions
    ``pos`` (B,): each slot's ``pos // page + 1`` live pages, one for an
    idle lane (pos 0), as in :func:`_live_walk_kernel`."""
    return int(np.minimum(pos // page_size + 1, n_blocks).sum())


def _live_walk_kernel(bt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                      k_buf, v_buf, sems, *, page_size: int, chunk: int,
                      n_blocks: int, scale: float, soft_cap: float):
    """Grid (B,): slot b's live pages, ``chunk`` pages per loop step.

    Page j of all KV heads is one contiguous (KV, page, hd) block of the
    head-major pool; it lands at rows ``(j % chunk) * page`` of the
    (KV, chunk * page, hd) slab, so each head reads one (chunk * page, hd)
    slab.  The next chunk's copies start before the current chunk's
    wait (two slabs per stream).  Dead entries get no copy; their V rows
    are zeroed instead, since ``p * v`` is NaN for p = 0 and v = NaN,
    and their keys fall past ``pos`` under the mask."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    n_live = jnp.minimum(pos // page_size + 1, n_blocks)     # live pages
    n_chunks = (n_live + chunk - 1) // chunk
    kv_heads, rows, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    keys = chunk * page_size

    def page_copies(slot, i, j):
        dst = (slot, slice(None), pl.ds(i * page_size, page_size))
        page = bt_ref[b, j]
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[dst],
                                      sems.at[slot]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[dst],
                                      sems.at[slot]))

    def fetch(c, slot):
        for i in range(chunk):
            j = c * chunk + i

            @pl.when(j < n_live)
            def _start():
                for dma in page_copies(slot, i, j):
                    dma.start()

            @pl.when(j >= n_live)
            def _zero():
                v_buf[slot, :, pl.ds(i * page_size, page_size)] = (
                    jnp.zeros((kv_heads, page_size, hd), v_buf.dtype))

    def wait(c, slot):
        for i in range(chunk):
            j = c * chunk + i

            @pl.when(j < n_live)
            def _wait():
                for dma in page_copies(slot, i, j):
                    dma.wait()

    fetch(0, 0)
    qs = [q_ref[0, h] for h in range(kv_heads)]             # (rows, hd)

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            fetch(c + 1, 1 - slot)

        wait(c, slot)
        k_pos = c * keys + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        out = []
        for h, (m_prev, l_prev, acc_prev) in enumerate(carry):
            k = k_buf[slot, h]                              # (keys, hd)
            v = v_buf[slot, h]
            s = jnp.einsum("gd,td->gt", qs[h], k,
                           preferred_element_type=jnp.float32) * scale
            if soft_cap > 0:
                s = jnp.tanh(s / soft_cap) * soft_cap
            s = jnp.where(k_pos <= pos, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc_prev * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc_new))
        return tuple(out)

    init = tuple((jnp.full((rows, 1), NEG_INF, jnp.float32),
                  jnp.zeros((rows, 1), jnp.float32),
                  jnp.zeros((rows, hd), jnp.float32))
                 for _ in range(kv_heads))
    carry = jax.lax.fori_loop(0, n_chunks, body, init)
    for h, (_, l, acc) in enumerate(carry):
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _gqa_live_walk(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   block_tables: jax.Array, pos: jax.Array, *,
                   scale: float, soft_cap: float,
                   interpret: bool) -> jax.Array:
    """q (B, KV, G, hd) -> (B, KV, G, hd) over unquantized head-major
    pools (P, KV, page, hd)."""
    B, KV, G, hd = q.shape
    page_size = k_pool.shape[2]
    n_blocks = block_tables.shape[1]
    chunk = min(n_blocks, live_walk_chunk_pages(
        page_size, KV, hd, k_pool.dtype.itemsize))
    kernel = functools.partial(
        _live_walk_kernel, page_size=page_size, chunk=chunk,
        n_blocks=n_blocks, scale=scale, soft_cap=soft_cap)
    qo_spec = pl.BlockSpec((1, KV, G, hd), lambda b, bt, ps: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block tables + positions
        grid=(B,),
        in_specs=[qo_spec,
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, KV, chunk * page_size, hd), k_pool.dtype),
            pltpu.VMEM((2, KV, chunk * page_size, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
    )(block_tables, pos.astype(jnp.int32), q, k_pool, v_pool)


def _mla_paged_decode_kernel(bt_ref, pos_ref, ql_ref, qr_ref, c_ref, r_ref,
                             *rest, page_size: int, scale: float,
                             quantized: bool = False):
    """One (slot, block) grid step of the latent-space MLA decode walk.
    When ``quantized`` two float32 per-line scale slabs follow c/kr."""
    if quantized:
        cs_ref, rs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lat = ql_ref[0].astype(jnp.float32)                   # (H, r)
    q_rope = qr_ref[0].astype(jnp.float32)                  # (H, dr)
    c = c_ref[0].astype(jnp.float32)                        # (page, r)
    kr = r_ref[0].astype(jnp.float32)                       # (page, dr)
    if quantized:
        c = c * _row_to_column(cs_ref[...])
        kr = kr * _row_to_column(rs_ref[...])
    s = (q_lat @ c.T + q_rope @ kr.T) * scale               # (H, page)
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(k_pos <= pos_ref[b], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ c
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _mla_scale_spec(page_size: int):
    """The (1, page) scale row of the mapped page, on a (slot, block)
    grid."""
    return pl.BlockSpec((None, 1, page_size),
                        lambda b, j, bt, ps: (bt[b, j], 0, 0))


def mla_paged_attention(
    q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
    r_pool: jax.Array, block_tables: jax.Array, pos: jax.Array, *,
    scale: float,
    c_scale: Optional[jax.Array] = None,
    r_scale: Optional[jax.Array] = None,
    interpret: bool = False, pipeline: str = "off",
) -> jax.Array:
    """Pallas MLA paged decode over the compressed cache."""
    _check_pipeline(pipeline)
    B, H, r = q_lat.shape
    if pipeline == "double":
        return _mla_paged_double(
            q_lat, q_rope, c_pool, r_pool, block_tables, pos, n_heads=H,
            scale=scale, c_scale=c_scale, r_scale=r_scale,
            interpret=interpret)
    dr = q_rope.shape[-1]
    page_size = c_pool.shape[1]
    n_blocks = block_tables.shape[1]
    quantized = c_scale is not None
    kernel = functools.partial(
        _mla_paged_decode_kernel, page_size=page_size, scale=scale,
        quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, H, r), lambda b, j, bt, ps: (b, 0, 0)),
        pl.BlockSpec((1, H, dr), lambda b, j, bt, ps: (b, 0, 0)),
        pl.BlockSpec((1, page_size, r),
                     lambda b, j, bt, ps: (bt[b, j], 0, 0)),
        pl.BlockSpec((1, page_size, dr),
                     lambda b, j, bt, ps: (bt[b, j], 0, 0)),
    ]
    args = [block_tables, pos.astype(jnp.int32), q_lat, q_rope, c_pool,
            r_pool]
    if quantized:
        in_specs += [_mla_scale_spec(page_size)] * 2
        args += [c_scale, r_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, r), lambda b, j, bt, ps: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        interpret=interpret,
        name="mla_paged_attention_decode",
    )(*args)


# --------------------------------------------------------------------------
# Multi-token verification kernels (speculative decoding)
# --------------------------------------------------------------------------

def _paged_verify_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                         page_size: int, n_group: int, scale: float,
                         soft_cap: float, quantized: bool = False):
    """One (slot, kv_head, block) grid step scoring T*G flattened query
    rows; row r belongs to draft token t = r // n_group at position
    ``pos + t``."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(2)
    n_blocks = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (T*G, hd)
    k = k_ref[...].astype(jnp.float32)                      # (page, hd)
    v = v_ref[...].astype(jnp.float32)
    if quantized:
        k = k * _row_to_column(ks_ref[...])
        v = v * _row_to_column(vs_ref[...])
    s = (q @ k.T) * scale                                   # (T*G, page)
    if soft_cap > 0:
        s = jnp.tanh(s / soft_cap) * soft_cap
    k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_group
    s = jnp.where(k_pos <= pos_ref[b] + t, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_verify(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, pos: jax.Array, *,
    scale: float, soft_cap: float = 0.0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False, pipeline: str = "off",
) -> jax.Array:
    """Pallas GQA multi-token verify; same contract as the reference.

    All T query tokens of a slot ride in one (T*G, hd) VMEM slab, so the
    page walk (and its HBM traffic) is paid once for the whole draft chain.
    """
    _check_pipeline(pipeline)
    B, T, KV, G, hd = q.shape
    page_size = k_pool.shape[2]
    n_blocks = block_tables.shape[1]
    qf = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, T * G, hd)
    if pipeline == "double":
        o = _gqa_paged_double(
            qf, k_pool, v_pool, block_tables, pos, n_group=G, scale=scale,
            soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
            interpret=interpret)
        return o.reshape(B, KV, T, G, hd).transpose(0, 2, 1, 3, 4)
    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_verify_kernel, page_size=page_size, n_group=G, scale=scale,
        soft_cap=soft_cap, quantized=quantized)
    in_specs = [pl.BlockSpec((1, 1, T * G, hd),
                             lambda b, h, j, bt, ps: (b, h, 0, 0))]
    in_specs += _gqa_page_specs(page_size, hd, quantized)
    args = [block_tables, pos.astype(jnp.int32), qf, k_pool, v_pool]
    if quantized:
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, T * G, hd),
                               lambda b, h, j, bt, ps: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, hd), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, T * G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention_verify",
    )(*args)
    return o.reshape(B, KV, T, G, hd).transpose(0, 2, 1, 3, 4)


def _mla_paged_verify_kernel(bt_ref, pos_ref, ql_ref, qr_ref, c_ref, r_ref,
                             *rest, page_size: int, n_heads: int,
                             scale: float, quantized: bool = False):
    """One (slot, block) grid step over T*H flattened latent query rows;
    row r belongs to draft token t = r // n_heads."""
    if quantized:
        cs_ref, rs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lat = ql_ref[0].astype(jnp.float32)                   # (T*H, r)
    q_rope = qr_ref[0].astype(jnp.float32)                  # (T*H, dr)
    c = c_ref[0].astype(jnp.float32)                        # (page, r)
    kr = r_ref[0].astype(jnp.float32)                       # (page, dr)
    if quantized:
        c = c * _row_to_column(cs_ref[...])
        kr = kr * _row_to_column(rs_ref[...])
    s = (q_lat @ c.T + q_rope @ kr.T) * scale               # (T*H, page)
    k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_heads
    s = jnp.where(k_pos <= pos_ref[b] + t, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ c
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def mla_paged_attention_verify(
    q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
    r_pool: jax.Array, block_tables: jax.Array, pos: jax.Array, *,
    scale: float,
    c_scale: Optional[jax.Array] = None,
    r_scale: Optional[jax.Array] = None,
    interpret: bool = False, pipeline: str = "off",
) -> jax.Array:
    """Pallas MLA multi-token verify over the compressed cache."""
    _check_pipeline(pipeline)
    B, T, H, r = q_lat.shape
    dr = q_rope.shape[-1]
    page_size = c_pool.shape[1]
    n_blocks = block_tables.shape[1]
    qlf = q_lat.reshape(B, T * H, r)
    qrf = q_rope.reshape(B, T * H, dr)
    if pipeline == "double":
        o = _mla_paged_double(
            qlf, qrf, c_pool, r_pool, block_tables, pos, n_heads=H,
            scale=scale, c_scale=c_scale, r_scale=r_scale,
            interpret=interpret)
        return o.reshape(B, T, H, r)
    quantized = c_scale is not None
    kernel = functools.partial(
        _mla_paged_verify_kernel, page_size=page_size, n_heads=H,
        scale=scale, quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, T * H, r), lambda b, j, bt, ps: (b, 0, 0)),
        pl.BlockSpec((1, T * H, dr), lambda b, j, bt, ps: (b, 0, 0)),
        pl.BlockSpec((1, page_size, r),
                     lambda b, j, bt, ps: (bt[b, j], 0, 0)),
        pl.BlockSpec((1, page_size, dr),
                     lambda b, j, bt, ps: (bt[b, j], 0, 0)),
    ]
    args = [block_tables, pos.astype(jnp.int32), qlf, qrf, c_pool, r_pool]
    if quantized:
        in_specs += [_mla_scale_spec(page_size)] * 2
        args += [c_scale, r_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T * H, r), lambda b, j, bt, ps: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T * H, 1), jnp.float32),
            pltpu.VMEM((T * H, 1), jnp.float32),
            pltpu.VMEM((T * H, r), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T * H, r), q_lat.dtype),
        interpret=interpret,
        name="mla_paged_attention_verify",
    )(*args)
    return o.reshape(B, T, H, r)


# --------------------------------------------------------------------------
# Double-buffered kernels (pipeline="double"): manual two-slab DMA walk
# --------------------------------------------------------------------------

def _gqa_double_kernel(bt_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest,
                       page_size: int, n_group: int, n_blocks: int,
                       scale: float, soft_cap: float,
                       quantized: bool = False):
    """Grid (B, KV): the whole block walk runs inside the kernel.  Two
    (page, hd) VMEM slabs per stream; the DMA for page j+1 starts before
    the wait on page j, so the fetch pipelines one block ahead of the
    flash math.  Row r of the (rows, hd) query slab belongs to draft
    token t = r // n_group (t = 0 everywhere for single-token decode) —
    the per-block compute is the exact op sequence of the single-buffered
    kernels, so the output is bit-identical to ``pipeline="off"``.
    Quantized pools add two (1, page) f32 scale rows that ride the same
    one-block lookahead; the dequant multiply sits at the identical op
    position as the single-buffered kernel's, keeping the bit-identity."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_slab, v_slab, ks_slab, vs_slab,
         k_sem, v_sem, ks_sem, vs_sem) = rest
    else:
        o_ref, k_slab, v_slab, k_sem, v_sem = rest
    b, h = pl.program_id(0), pl.program_id(1)

    def k_dma(slot, j):
        return pltpu.make_async_copy(
            k_hbm.at[bt_ref[b, j], h], k_slab.at[slot], k_sem.at[slot])

    def v_dma(slot, j):
        return pltpu.make_async_copy(
            v_hbm.at[bt_ref[b, j], h], v_slab.at[slot], v_sem.at[slot])

    def scale_dmas(slot, j):
        return (pltpu.make_async_copy(
                    ks_hbm.at[bt_ref[b, j], h], ks_slab.at[slot],
                    ks_sem.at[slot]),
                pltpu.make_async_copy(
                    vs_hbm.at[bt_ref[b, j], h], vs_slab.at[slot],
                    vs_sem.at[slot]))

    k_dma(0, 0).start()
    v_dma(0, 0).start()
    if quantized:
        for dma in scale_dmas(0, 0):
            dma.start()
    q = q_ref[0, 0].astype(jnp.float32)                     # (rows, hd)
    rows, hd = q_ref.shape[2], q_ref.shape[3]

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _prefetch():
            k_dma(1 - slot, j + 1).start()
            v_dma(1 - slot, j + 1).start()
            if quantized:
                for dma in scale_dmas(1 - slot, j + 1):
                    dma.start()

        k_dma(slot, j).wait()
        v_dma(slot, j).wait()
        k = k_slab[slot].astype(jnp.float32)                # (page, hd)
        v = v_slab[slot].astype(jnp.float32)
        if quantized:
            for dma in scale_dmas(slot, j):
                dma.wait()
            k = k * _row_to_column(ks_slab[slot])
            v = v * _row_to_column(vs_slab[slot])
        s = (q @ k.T) * scale                               # (rows, page)
        if soft_cap > 0:
            s = jnp.tanh(s / soft_cap) * soft_cap
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_group
        s = jnp.where(k_pos <= pos_ref[b] + t, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((rows, 1), NEG_INF, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, hd), jnp.float32)))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _gqa_paged_double(qf: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, pos: jax.Array, *,
                      n_group: int, scale: float, soft_cap: float,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      interpret: bool) -> jax.Array:
    """qf (B, KV, rows, hd) flattened queries -> (B, KV, rows, hd)."""
    B, KV, rows, hd = qf.shape
    page_size = k_pool.shape[2]
    n_blocks = block_tables.shape[1]
    quantized = k_scale is not None
    kernel = functools.partial(
        _gqa_double_kernel, page_size=page_size, n_group=n_group,
        n_blocks=n_blocks, scale=scale, soft_cap=soft_cap,
        quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1, rows, hd),
                     lambda b, h, bt, ps: (b, h, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
    ]
    args = [block_tables, pos.astype(jnp.int32), qf, k_pool, v_pool]
    scratch = [
        pltpu.VMEM((2, page_size, hd), k_pool.dtype),
        pltpu.VMEM((2, page_size, hd), v_pool.dtype),
    ]
    sems = [pltpu.SemaphoreType.DMA((2,))] * 2
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)] * 2
        args += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, 1, page_size), jnp.float32)] * 2
        sems += [pltpu.SemaphoreType.DMA((2,))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda b, h, bt, ps: (b, h, 0, 0)),
        scratch_shapes=scratch + sems,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), qf.dtype),
        interpret=interpret,
        name="paged_attention_double",
    )(*args)


def _mla_double_kernel(bt_ref, pos_ref, ql_ref, qr_ref, c_hbm, r_hbm,
                       *rest, page_size: int, n_heads: int, n_blocks: int,
                       scale: float, quantized: bool = False):
    """Grid (B,): the latent block walk with two (page, r) + (page, dr)
    slabs and a one-block DMA lookahead.  Row r of the flattened query
    slabs belongs to draft token t = r // n_heads (0 for decode).
    Quantized pools add two (1, page) f32 scale slabs on the same
    lookahead; dequant sits at the single-buffered kernel's op position
    so the output stays bit-identical to ``pipeline="off"``."""
    if quantized:
        (cs_hbm, rs_hbm, o_ref, c_slab, r_slab, cs_slab, rs_slab,
         c_sem, r_sem, cs_sem, rs_sem) = rest
    else:
        o_ref, c_slab, r_slab, c_sem, r_sem = rest
    b = pl.program_id(0)

    def c_dma(slot, j):
        return pltpu.make_async_copy(
            c_hbm.at[bt_ref[b, j]], c_slab.at[slot], c_sem.at[slot])

    def r_dma(slot, j):
        return pltpu.make_async_copy(
            r_hbm.at[bt_ref[b, j]], r_slab.at[slot], r_sem.at[slot])

    def scale_dmas(slot, j):
        return (pltpu.make_async_copy(
                    cs_hbm.at[bt_ref[b, j]], cs_slab.at[slot],
                    cs_sem.at[slot]),
                pltpu.make_async_copy(
                    rs_hbm.at[bt_ref[b, j]], rs_slab.at[slot],
                    rs_sem.at[slot]))

    c_dma(0, 0).start()
    r_dma(0, 0).start()
    if quantized:
        for dma in scale_dmas(0, 0):
            dma.start()
    q_lat = ql_ref[0].astype(jnp.float32)                   # (rows, r)
    q_rope = qr_ref[0].astype(jnp.float32)                  # (rows, dr)
    rows, r = ql_ref.shape[1], ql_ref.shape[2]

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _prefetch():
            c_dma(1 - slot, j + 1).start()
            r_dma(1 - slot, j + 1).start()
            if quantized:
                for dma in scale_dmas(1 - slot, j + 1):
                    dma.start()

        c_dma(slot, j).wait()
        r_dma(slot, j).wait()
        c = c_slab[slot].astype(jnp.float32)                # (page, r)
        kr = r_slab[slot].astype(jnp.float32)               # (page, dr)
        if quantized:
            for dma in scale_dmas(slot, j):
                dma.wait()
            c = c * _row_to_column(cs_slab[slot])
            kr = kr * _row_to_column(rs_slab[slot])
        s = (q_lat @ c.T + q_rope @ kr.T) * scale           # (rows, page)
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_heads
        s = jnp.where(k_pos <= pos_ref[b] + t, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + p @ c
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((rows, 1), NEG_INF, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, r), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _mla_paged_double(qlf: jax.Array, qrf: jax.Array, c_pool: jax.Array,
                      r_pool: jax.Array, block_tables: jax.Array,
                      pos: jax.Array, *, n_heads: int, scale: float,
                      c_scale: Optional[jax.Array] = None,
                      r_scale: Optional[jax.Array] = None,
                      interpret: bool) -> jax.Array:
    """qlf (B, rows, r) / qrf (B, rows, dr) -> o_lat (B, rows, r)."""
    B, rows, r = qlf.shape
    dr = qrf.shape[-1]
    page_size = c_pool.shape[1]
    n_blocks = block_tables.shape[1]
    quantized = c_scale is not None
    kernel = functools.partial(
        _mla_double_kernel, page_size=page_size, n_heads=n_heads,
        n_blocks=n_blocks, scale=scale, quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, rows, r), lambda b, bt, ps: (b, 0, 0)),
        pl.BlockSpec((1, rows, dr), lambda b, bt, ps: (b, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
    ]
    args = [block_tables, pos.astype(jnp.int32), qlf, qrf, c_pool, r_pool]
    scratch = [
        pltpu.VMEM((2, page_size, r), c_pool.dtype),
        pltpu.VMEM((2, page_size, dr), r_pool.dtype),
    ]
    sems = [pltpu.SemaphoreType.DMA((2,))] * 2
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)] * 2
        args += [c_scale, r_scale]
        scratch += [pltpu.VMEM((2, 1, page_size), jnp.float32)] * 2
        sems += [pltpu.SemaphoreType.DMA((2,))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, r), lambda b, bt, ps: (b, 0, 0)),
        scratch_shapes=scratch + sems,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, r), qlf.dtype),
        interpret=interpret,
        name="mla_paged_attention_double",
    )(*args)


# --------------------------------------------------------------------------
# VMEM traffic pricing (hierarchical roofline, arXiv 2009.05257)
#
# The HBM ledger prices the page walk once per line (kv_line_bytes * L).
# The VMEM level sees MORE traffic than that: the streamed slabs cross it
# on their way in, the query slab is re-read from VMEM on every block step
# of a per-block grid, and the fp32 softmax carries (m, l, acc) are read
# AND written once per block step (once per chunk in the live-page walk).
# These formulas are derived from the BlockSpecs and scratch shapes of the
# kernels above — if a kernel's grid or scratch changes, the pricing here
# must change with it (serve/crosscheck.py walks the grids independently
# and is the tripwire).
# --------------------------------------------------------------------------


def live_blocks(context_len: int, page_size: int, n_q: int = 1) -> int:
    """Pages holding live KV for a slot whose LAST query sits at position
    ``context_len + n_q - 2`` (decode: n_q=1 -> lines 0..L-1).  The
    live-page walk copies exactly these; a per-block grid walks the whole
    block table, but steps beyond the live prefix mask to no-ops, so we
    price only the live walk, like the HBM ledger."""
    lines = max(1, int(context_len) + int(n_q) - 1)
    return -(-lines // int(page_size))


def paged_decode_vmem_bytes(
    *, context_len: int, page_size: int, n_heads: int, kv_heads: int,
    head_dim: int, isize: int, n_q: int = 1, pipeline: str = "off",
    kv_isize: int = 0, scale_isize: int = 0,
) -> float:
    """VMEM bytes one slot moves in the GQA paged decode (``n_q == 1``)
    or verify (``n_q == T``) kernel.

    Per-block grid (B, KV, n_blocks): per (kv_head, block) step the
    kernel streams one (page, hd) K slab and one V slab HBM->VMEM,
    re-reads the (G * n_q, hd) query slab, and reads+writes the fp32
    carries (m, l: (rows, 1) each; acc: (rows, hd)).  The output flush
    and the n_q freshly appended cache lines cross VMEM once.

    Decode over unquantized pools at ``pipeline="off"`` runs the
    live-page walk (:func:`walks_live_pages`): the same live pages
    stream in, the query slab is fetched once per (slot) program, and the
    carries are updated once per chunk of :func:`live_walk_chunk_pages`
    pages instead of once per block.

    ``pipeline="double"`` prices the two-slab manual-DMA kernel: the
    block walk runs inside one (slot, kv_head) program, so the query
    slab is fetched ONCE instead of re-read per block step (the streamed
    page bytes and the per-block fp32 carry updates are unchanged — the
    second slab doubles VMEM *capacity*, not traffic).

    Quantized pools (``kv_isize`` = storage itemsize, ``scale_isize`` = 4
    for the f32 per-(line, kv_head) scale) shrink the STREAMED slab bytes
    — the query slab, fp32 carries, and output flush stay at the
    activation ``isize``.  ``kv_isize=0`` means unquantized (pages stored
    at ``isize``, no scale stream)."""
    g = n_heads // kv_heads
    rows = g * n_q
    nb = live_blocks(context_len, page_size, n_q)
    q_steps = nb if pipeline == "off" else 1
    carry_steps = nb
    if n_q == 1 and walks_live_pages(quantized=scale_isize > 0,
                                     pipeline=pipeline):
        chunk = live_walk_chunk_pages(page_size, kv_heads, head_dim,
                                      kv_isize or isize)
        q_steps, carry_steps = 1, -(-nb // chunk)
    kv_line = head_dim * (kv_isize or isize) + scale_isize
    stream = kv_heads * nb * 2 * page_size * kv_line
    q_reread = kv_heads * q_steps * rows * head_dim * isize
    carries = kv_heads * carry_steps * 2 * rows * (head_dim + 2) * 4
    out = kv_heads * rows * head_dim * isize
    appended = n_q * 2 * kv_heads * kv_line
    return float(stream + q_reread + carries + out + appended)


def mla_paged_decode_vmem_bytes(
    *, context_len: int, page_size: int, n_heads: int, lora_rank: int,
    rope_dim: int, isize: int, n_q: int = 1, pipeline: str = "off",
    kv_isize: int = 0, scale_isize: int = 0,
) -> float:
    """VMEM bytes one slot moves in the MLA paged decode/verify kernel.

    Grid is (B, n_blocks); per block step the kernel streams one
    (page, r) latent slab and one (page, dr) rope slab, re-reads the
    (H * n_q, r) + (H * n_q, dr) query slabs, and reads+writes the fp32
    carries (m, l: (rows, 1); acc: (rows, r)).  ``pipeline="double"``:
    grid (B,), query slabs fetched once per program (see
    :func:`paged_decode_vmem_bytes`).  Quantized pools: the streamed
    latent+rope line shrinks to ``(r + dr) * kv_isize`` plus TWO f32
    scales per line (latent + rope streams); query slabs stay at
    ``isize``."""
    rows = n_heads * n_q
    nb = live_blocks(context_len, page_size, n_q)
    q_steps = nb if pipeline == "off" else 1
    line = (lora_rank + rope_dim) * isize
    kv_line = (lora_rank + rope_dim) * (kv_isize or isize) + 2 * scale_isize
    stream = nb * page_size * kv_line
    q_reread = q_steps * rows * line
    carries = nb * 2 * rows * (lora_rank + 2) * 4
    out = rows * lora_rank * isize
    appended = n_q * kv_line
    return float(stream + q_reread + carries + out + appended)
