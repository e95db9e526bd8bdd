"""Attention mixers: GQA self-attention (+qk_norm, RoPE), cross-attention,
KV-cache decode.  MLA lives in mla.py.

Sharding strategy (resolved by the legalizer, see parallel/sharding.py):
* heads divisible by the ``model`` axis  -> Megatron head-parallel attention
* heads NOT divisible (40H/36H/24H/12H on a 16-way axis) -> the ``seq_fb``
  logical axis picks up the freed ``model`` capacity and attention runs
  sequence-parallel (context-parallel): q is sharded over its sequence dim,
  K/V are gathered — the all-gather-KV flavor of ring attention.  This is why
  every assigned head count compiles on the fixed 16x16 production mesh.

Memory strategy: q-chunked attention (lax.map over query chunks) bounds the
score matrix to (B, H, chunk, S) — the jnp analogue of flash attention's
outer loop; the Pallas kernel (kernels/flash_attention.py) is the TPU-native
inner loop.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.kernels import quantize as kvq
from repro.parallel import collectives as coll
from repro.parallel.sharding import ParamDef, constrain
from .common import ModelConfig
from .layers import apply_rope, rms_head_norm, rope_cos_sin

NEG_INF = -1e30


def attn_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamDef]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.dtype
    defs = {
        "wq": ParamDef((D, H, hd), ("d_model", "heads", "head_dim"), dt,
                       fan_in_axes=(0,)),
        "wk": ParamDef((D, KV, hd), ("d_model", "kv_heads", "head_dim"), dt,
                       fan_in_axes=(0,)),
        "wv": ParamDef((D, KV, hd), ("d_model", "kv_heads", "head_dim"), dt,
                       fan_in_axes=(0,)),
        "wo": ParamDef((H, hd, D), ("heads", "head_dim", "d_model"), dt,
                       fan_in_axes=(0, 1)),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), "float32", init="ones")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), "float32", init="ones")
    if cross:
        # tanh-gated residual (llama-3.2-vision style, init 0 = identity)
        defs["gate"] = ParamDef((), (), "float32", init="zeros")
    return defs


def _project_qkv(p, x: jax.Array, kv_src: jax.Array, cfg: ModelConfig,
                 q_pos: Optional[jax.Array], k_pos: Optional[jax.Array]):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_emb == "rope" and q_pos is not None:
        cq, sq = rope_cos_sin(q_pos, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cq, sq)
    if cfg.pos_emb == "rope" and k_pos is not None:
        ck, sk = rope_cos_sin(k_pos, cfg.hd, cfg.rope_theta)
        k = apply_rope(k, ck, sk)
    return q, k, v


def _attn_core(q, k, v, q_pos, k_pos, *, causal: bool, scale: float,
               soft_cap: float = 0.0) -> jax.Array:
    """q (B,Sq,KV,G,hd)  k,v (B,Sk,KV,hd)  ->  (B,Sq,KV,G,hd).

    KV heads stay un-repeated; the group dim G rides along so GQA does not
    materialize repeated K/V.  The ``fused_attention`` scope marks the
    region the Pallas flash kernel replaces on TPU — the roofline analysis
    attributes its HBM traffic separately (hlo_cost.TRACKED_SCOPES).
    """
    with jax.named_scope("fused_attention"):
        s = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
        if soft_cap > 0:
            s = jnp.tanh(s / soft_cap) * soft_cap
        if causal:
            m = q_pos[:, :, None] >= k_pos[:, None, :]          # (B, Sq, Sk)
            s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
        elif k_pos is not None and q_pos is not None:
            m = k_pos[:, None, :] >= 0                           # padding mask
            s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
        p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskh->bqkgh", p_attn, v)


def multihead_attention(
    p, x: jax.Array, cfg: ModelConfig,
    *,
    kv_src: Optional[jax.Array] = None,     # cross-attn source
    q_positions: Optional[jax.Array] = None,  # (B, Sq) int32
    k_positions: Optional[jax.Array] = None,  # (B, Sk)
    causal: Optional[bool] = None,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder)."""
    B, Sq, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    cross = kv_src is not None
    src = kv_src if cross else x
    causal = (cfg.causal and not cross) if causal is None else causal
    rope_q = q_positions if not cross else None
    rope_k = k_positions if not cross else None
    q, k, v = _project_qkv(p, x, src, cfg, rope_q, rope_k)
    q = constrain(q.reshape(B, Sq, KV, G, hd), "batch", "seq_fb", "kv_heads",
                  "heads_q", "head_dim")
    k = constrain(k, "batch", None, "kv_heads", "head_dim")
    v = constrain(v, "batch", None, "kv_heads", "head_dim")
    scale = 1.0 / (hd ** 0.5)

    Sk = src.shape[1]
    chunk = cfg.attn_chunk
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32), (B, Sk))

    if Sq > 2 * chunk and Sq % chunk == 0:
        nq = Sq // chunk
        qc = jnp.moveaxis(q.reshape(B, nq, chunk, KV, G, hd), 1, 0)
        pc = jnp.moveaxis(q_positions.reshape(B, nq, chunk), 1, 0)
        o = jax.lax.map(
            lambda args: _attn_core(
                args[0], k, v, args[1], k_positions,
                causal=causal, scale=scale,
                soft_cap=cfg.attn_logit_soft_cap),
            (qc, pc),
        )
        o = jnp.moveaxis(o, 0, 1).reshape(B, Sq, KV, G, hd)
    else:
        o = _attn_core(q, k, v, q_positions, k_positions,
                       causal=causal, scale=scale,
                       soft_cap=cfg.attn_logit_soft_cap)
    o = constrain(o, "batch", "seq_fb", "kv_heads", "heads_q", "head_dim")
    if cfg.tp_attn_inner:
        # row-parallel o-proj: flatten heads to the 128-aligned (H*hd) dim,
        # shard it over `model`, contract -> partial sums + one all-reduce.
        # Removes the model-axis-redundant o-proj the baseline HLO shows
        # when the head count does not divide the axis (§Perf lever).
        o_flat = constrain(o.reshape(B, Sq, H * hd), "batch", "seq",
                           "attn_inner")
        out = o_flat @ constrain(p["wo"].reshape(H * hd, D), "attn_inner",
                                 "d_model")
    else:
        out = jnp.einsum("bqhx,hxd->bqd", o.reshape(B, Sq, H, hd), p["wo"])
    if cross and "gate" in p:
        out = jnp.tanh(p["gate"]).astype(out.dtype) * out
    return constrain(out, "batch", "seq", "d_model")


# --------------------------------------------------------------------------
# KV cache (decode)
# --------------------------------------------------------------------------

def init_cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, ParamDef]:
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": ParamDef((batch, max_len, KV, hd),
                      ("batch", "kv_seq", "kv_heads", "head_dim"), cfg.dtype,
                      init="zeros"),
        "v": ParamDef((batch, max_len, KV, hd),
                      ("batch", "kv_seq", "kv_heads", "head_dim"), cfg.dtype,
                      init="zeros"),
    }


def paged_pool_defs(cfg: ModelConfig, num_pages: int, page_size: int
                    ) -> Dict[str, ParamDef]:
    """Physical page pool for the GQA KV cache, head-major:
    (num_pages, KV, page_size, hd).

    Pages carry no batch dim — a per-slot block table maps logical block
    index -> physical page, so slots of different lengths share one pool
    (vLLM-style paging; the block table is shared across layers).  Heads
    sit ahead of the page lines so one page of every head is one
    contiguous (KV, page, hd) block, the copy the live-page walk makes,
    and one head's (page, hd) slab is the trailing two dims of the pool,
    the block the per-block walk reads per grid step
    (kernels/paged_attention.py).

    With ``cfg.kv_dtype`` quantized (int8 / fp8_e4m3) the k/v pools store
    quantized values plus float32 absmax scales per (page, kv_head, line),
    laid out (num_pages, KV, 1, page_size) so one head's scales of a page
    are a (1, page) block the page walk reads alongside its slab.  Scales
    carry the pools' ``kv_heads``/``kv_seq`` logical axes, so under tensor
    parallelism they shard WITH the kv heads and every page lifecycle op
    (CoW, swap, migration) treats them as just another paged leaf."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    store = kvq.store_dtype(cfg.kv_dtype, cfg.dtype)
    defs = {
        "k": ParamDef((num_pages, KV, page_size, hd),
                      ("none", "kv_heads", "kv_seq", "head_dim"), store,
                      init="zeros"),
        "v": ParamDef((num_pages, KV, page_size, hd),
                      ("none", "kv_heads", "kv_seq", "head_dim"), store,
                      init="zeros"),
    }
    if kvq.is_quantized(cfg.kv_dtype):
        for name in ("k_scale", "v_scale"):
            defs[name] = ParamDef((num_pages, KV, 1, page_size),
                                  ("none", "kv_heads", "none", "kv_seq"),
                                  "float32", init="ones")
    return defs


def _commit_kv(pool: Dict[str, jax.Array], name: str, blk, off, new,
               cfg: ModelConfig) -> Dict[str, jax.Array]:
    """Write new K or V lines into the head-major page pool, quantizing on
    the way in when the pool is quantized.  ``new`` (..., KV, hd) indexed
    by ``blk``/``off`` of matching leading shape; returns the updated
    leaves ({name} and, when quantized, {name}_scale)."""
    out = {}
    if f"{name}_scale" in pool:
        new, s = kvq.quantize(new, cfg.kv_dtype, -1)
        out[f"{name}_scale"] = pool[f"{name}_scale"].at[blk, :, 0, off].set(s)
    out[name] = pool[name].at[blk, :, off].set(new.astype(pool[name].dtype))
    return out


def _gather_pages(pool: Dict[str, jax.Array], name: str, block_table,
                  cfg: ModelConfig) -> jax.Array:
    """One slot's K or V pages as a (S, KV, hd) sequence in the model
    dtype, dequantized when the pool is quantized."""
    g = jnp.swapaxes(pool[name][block_table], 1, 2)     # (nb, page, KV, hd)
    if f"{name}_scale" in pool:
        s = jnp.swapaxes(pool[f"{name}_scale"][block_table][:, :, 0], 1, 2)
        g = kvq.dequantize(g, s).astype(cfg.dtype)
    return g.reshape(-1, *g.shape[2:])


def decode_attention_paged(
    p, x: jax.Array, pool: Dict[str, jax.Array], block_tables: jax.Array,
    pos: jax.Array, cfg: ModelConfig, *, page_size: int,
    backend: Optional[str] = None, pipeline: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode for every slot against a paged pool.

    x (B,1,D); pool k/v (P, KV, page, hd); block_tables (B, n_blocks)
    logical block -> physical page; pos (B,) per-slot write position.
    Inactive slots must map to a reserved trash page (their writes collide
    harmlessly) and are masked out by the caller.

    The attention core (page walk + online softmax) dispatches through the
    kernel registry (kernels/ops.py ``paged_attention``): the Pallas decode
    kernel on TPU / interpret mode, or the jnp gather reference; the
    ``paged_attention`` named scope marks the region for the roofline
    accounting either way (hlo_cost.TRACKED_SCOPES).
    """
    B, _, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    posb = pos.astype(jnp.int32)[:, None]                       # (B, 1)
    q, k_new, v_new = _project_qkv(p, x, x, cfg, posb, posb)
    blk = jnp.take_along_axis(block_tables, posb // page_size, axis=1)[:, 0]
    off = pos % page_size
    pool = {**pool,
            **_commit_kv(pool, "k", blk, off, k_new[:, 0], cfg),
            **_commit_kv(pool, "v", blk, off, v_new[:, 0], cfg)}
    with jax.named_scope("paged_attention"):
        o = kernel_ops.paged_attention(
            q.reshape(B, KV, G, hd), pool["k"], pool["v"], block_tables,
            pos, scale=1.0 / (hd ** 0.5),
            soft_cap=cfg.attn_logit_soft_cap,
            k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
            backend=backend, sharded=cfg.tp_axis is not None,
            pipeline=pipeline,
            ).reshape(B, 1, H, hd)
    if cfg.tp_axis is not None and cfg.tp_overlap == "ring":
        # same contraction as the einsum below, flattened so the ring
        # epilogue can chunk the d_model columns
        out = coll.row_parallel_matmul(
            o.astype(x.dtype).reshape(B, 1, H * hd),
            p["wo"].reshape(H * hd, -1), cfg.tp_axis, "ring")
    else:
        out = jnp.einsum("bqhx,hxd->bqd", o.astype(x.dtype), p["wo"])
        if cfg.tp_axis is not None:
            # head-parallel shard: the o-proj contracted local heads only
            out = coll.row_parallel_psum(out, cfg.tp_axis)
    return constrain(out, "batch", "seq", "d_model"), pool


def decode_verify_paged(
    p, x: jax.Array, pool: Dict[str, jax.Array], block_tables: jax.Array,
    pos: jax.Array, cfg: ModelConfig, *, page_size: int,
    backend: Optional[str] = None, pipeline: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token verification decode for every slot (spec decoding).

    x (B, T, D) — the draft chain [last committed token, d_1..d_k] at
    positions ``pos + t``; pos (B,) the first token's write position.
    Writes all T K/V lines into the slot's pages, then scores all T query
    tokens in one page walk (kernels ``paged_attention_verify``).  Writes
    beyond the slot's reserved pages land on the trash page (block-table
    entries are 0 there) and rejected-draft writes are unobservable: the
    causal mask hides positions beyond the committed context and the
    engine re-feeds the committed token at that position next step,
    overwriting them — the "rollback" is host-side position bookkeeping.
    """
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    posq = (pos.astype(jnp.int32)[:, None]
            + jnp.arange(T, dtype=jnp.int32)[None, :])          # (B, T)
    q, k_new, v_new = _project_qkv(p, x, x, cfg, posq, posq)
    n_blocks = block_tables.shape[1]
    blk_idx = jnp.minimum(posq // page_size, n_blocks - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)    # (B, T)
    off = posq % page_size
    pool = {**pool,
            **_commit_kv(pool, "k", blk, off, k_new, cfg),
            **_commit_kv(pool, "v", blk, off, v_new, cfg)}
    with jax.named_scope("paged_attention"):
        o = kernel_ops.paged_attention_verify(
            q.reshape(B, T, KV, G, hd), pool["k"], pool["v"], block_tables,
            pos, scale=1.0 / (hd ** 0.5),
            soft_cap=cfg.attn_logit_soft_cap,
            k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
            backend=backend, sharded=cfg.tp_axis is not None,
            pipeline=pipeline,
            ).reshape(B, T, H, hd)
    if cfg.tp_axis is not None and cfg.tp_overlap == "ring":
        out = coll.row_parallel_matmul(
            o.astype(x.dtype).reshape(B, T, H * hd),
            p["wo"].reshape(H * hd, -1), cfg.tp_axis, "ring")
    else:
        out = jnp.einsum("bqhx,hxd->bqd", o.astype(x.dtype), p["wo"])
        if cfg.tp_axis is not None:
            out = coll.row_parallel_psum(out, cfg.tp_axis)
    return constrain(out, "batch", "seq", "d_model"), pool


def prefill_attention_paged(
    p, x: jax.Array, pool: Dict[str, jax.Array], block_table: jax.Array,
    offset: jax.Array, cfg: ModelConfig, *, page_size: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked prefill for ONE request: x (1,T,D) at positions
    offset..offset+T-1, attending to everything this slot has cached
    (earlier chunks + causal self).  block_table (n_blocks,)."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    idx = offset + jnp.arange(T, dtype=jnp.int32)               # (T,)
    q, k_new, v_new = _project_qkv(p, x, x, cfg, idx[None, :], idx[None, :])
    blk, off = block_table[idx // page_size], idx % page_size
    pool = {**pool,
            **_commit_kv(pool, "k", blk, off, k_new[0], cfg),
            **_commit_kv(pool, "v", blk, off, v_new[0], cfg)}
    S = block_table.shape[0] * page_size
    # chunked prefill re-reads earlier chunks through the (quantized)
    # pages — the same values every later decode step sees
    k = _gather_pages(pool, "k", block_table, cfg)[None]
    v = _gather_pages(pool, "v", block_table, cfg)[None]
    q = q.reshape(B, T, KV, G, hd)
    k_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    o = _attn_core(q, k, v, idx[None, :], k_pos, causal=True,
                   scale=1.0 / (hd ** 0.5),
                   soft_cap=cfg.attn_logit_soft_cap).reshape(B, T, H, hd)
    out = jnp.einsum("bqhx,hxd->bqd", o, p["wo"])
    return constrain(out, "batch", "seq", "d_model"), pool


def decode_attention(
    p, x: jax.Array, cache: Dict[str, jax.Array], pos: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x (B,1,D); cache k/v (B,Smax,KV,hd); pos scalar."""
    B, _, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    posb = jnp.broadcast_to(pos.astype(jnp.int32), (B, 1))
    q, k_new, v_new = _project_qkv(p, x, x, cfg, posb, posb)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    k = constrain(k, "batch", "kv_seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "kv_seq", "kv_heads", "head_dim")
    q = q.reshape(B, 1, KV, G, hd)
    Smax = k.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(Smax, dtype=jnp.int32), (B, Smax))
    o = _attn_core(q, k, v, posb, k_pos, causal=True,
                   scale=1.0 / (hd ** 0.5),
                   soft_cap=cfg.attn_logit_soft_cap).reshape(B, 1, H, hd)
    out = jnp.einsum("bqhx,hxd->bqd", o, p["wo"])
    return constrain(out, "batch", "seq", "d_model"), {"k": k, "v": v}
