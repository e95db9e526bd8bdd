"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to a per-token latent ``c_kv`` of rank ``kv_lora_rank``
plus one shared RoPE key of ``rope_head_dim``; per-head K/V are re-expanded
through ``wk_b``/``wv_b``.  The decode cache stores only
``(B, S, kv_lora + rope_hd)`` — 576 floats/token for DeepSeek-V2 vs
2*128*128 = 32768 for the equivalent GQA cache.

Two decode paths:
* ``naive``    — re-expand K/V from the latent every step (paper-faithful
  baseline; compute O(S * r * H * d) per token).
* ``absorbed`` — fold ``wk_b`` into the query and ``wv_b`` into the output
  so attention runs entirely in the latent space (compute O(S * r * H));
  enabled by ``cfg.mla_absorb`` and measured in EXPERIMENTS.md §Perf.
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
from .layers import rope_cos_sin
from .attention import NEG_INF


def _rope_pairs(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """RoPE over the last dim of x (..., S, [H,] r)."""
    r = x.shape[-1]
    cos, sin = rope_cos_sin(pos, r, theta)          # (B, S, r/2)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    c, s = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def mla_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, H = cfg.d_model, cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    dt = cfg.dtype
    defs: Dict[str, ParamDef] = {}
    if r_q:
        defs["wq_a"] = ParamDef((D, r_q), ("d_model", "none"), dt)
        defs["q_a_norm"] = ParamDef((r_q,), ("none",), "float32", init="ones")
        defs["wq_b"] = ParamDef((r_q, H, dn + dr), ("none", "heads", "head_dim"), dt,
                                fan_in_axes=(0,))
    else:
        defs["wq"] = ParamDef((D, H, dn + dr), ("d_model", "heads", "head_dim"), dt,
                              fan_in_axes=(0,))
    defs["wkv_a"] = ParamDef((D, r_kv + dr), ("d_model", "none"), dt)
    defs["kv_a_norm"] = ParamDef((r_kv,), ("none",), "float32", init="ones")
    defs["wk_b"] = ParamDef((r_kv, H, dn), ("none", "heads", "head_dim"), dt,
                            fan_in_axes=(0,))
    defs["wv_b"] = ParamDef((r_kv, H, dv), ("none", "heads", "head_dim"), dt,
                            fan_in_axes=(0,))
    defs["wo"] = ParamDef((H, dv, D), ("heads", "head_dim", "d_model"), dt,
                          fan_in_axes=(0, 1))
    return defs


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


def _queries(p, x: jax.Array, pos: jax.Array, cfg: ModelConfig):
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = _rms(x @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _rope_pairs(q_rope, pos, cfg.rope_theta)
    return q_nope, q_rope


def _latent_kv(p, x: jax.Array, pos: jax.Array, cfg: ModelConfig):
    r_kv, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    ckv = x @ p["wkv_a"]                              # (B, S, r+dr)
    c_kv = _rms(ckv[..., :r_kv], p["kv_a_norm"], cfg.norm_eps)
    k_rope = _rope_pairs(ckv[..., r_kv:], pos, cfg.rope_theta)   # (B, S, dr)
    return c_kv, k_rope


def mla_attention(p, x: jax.Array, cfg: ModelConfig,
                  q_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence MLA (train / prefill): expand K,V per head."""
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q_nope, q_rope = _queries(p, x, q_positions, cfg)
    c_kv, k_rope = _latent_kv(p, x, q_positions, cfg)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    q_nope = constrain(q_nope, "batch", "seq_fb", "heads", "head_dim")
    k_nope = constrain(k_nope, "batch", None, "heads", "head_dim")

    scale = 1.0 / ((dn + dr) ** 0.5)

    def chunk_attn(args):
        qn, qr, qp = args
        with jax.named_scope("fused_attention"):
            s = (jnp.einsum("bqhk,bshk->bhqs", qn, k_nope)
                 + jnp.einsum("bqhk,bsk->bhqs", qr, k_rope))
            s = s.astype(jnp.float32) * scale
            m = qp[:, :, None] >= q_positions[:, None, :]
            s = jnp.where(m[:, None, :, :], s, NEG_INF)
            w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("bhqs,bshk->bqhk", w, v)

    chunk = cfg.attn_chunk
    if S > 2 * chunk and S % chunk == 0:
        nq = S // chunk
        qn = jnp.moveaxis(q_nope.reshape(B, nq, chunk, H, dn), 1, 0)
        qr = jnp.moveaxis(q_rope.reshape(B, nq, chunk, H, dr), 1, 0)
        qp = jnp.moveaxis(q_positions.reshape(B, nq, chunk), 1, 0)
        o = jax.lax.map(chunk_attn, (qn, qr, qp))
        o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, dv)
    else:
        o = chunk_attn((q_nope, q_rope, q_positions))
    out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"])
    return constrain(out, "batch", "seq", "d_model")


# --------------------------------------------------------------------------
# Decode with the latent cache
# --------------------------------------------------------------------------

def mla_cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, ParamDef]:
    return {
        "c_kv": ParamDef((batch, max_len, cfg.kv_lora_rank),
                         ("batch", "kv_seq", "none"), cfg.dtype, init="zeros"),
        "k_rope": ParamDef((batch, max_len, cfg.rope_head_dim),
                           ("batch", "kv_seq", "none"), cfg.dtype, init="zeros"),
    }


def mla_paged_pool_defs(cfg: ModelConfig, num_pages: int, page_size: int
                        ) -> Dict[str, ParamDef]:
    """Physical page pool for the latent cache: (num_pages, page, r) — same
    block-table indirection as the GQA pool, ~57x fewer bytes per token.

    With ``cfg.kv_dtype`` quantized the latent/rope pools store quantized
    values plus a float32 absmax scale per (page, line) — the latent
    vector is one quantization group.  The scales are laid out
    (num_pages, 1, page) so a page's scales are one (1, page) block the
    Pallas walk can read.  Latent pools replicate under TP, so the
    per-line scales do too."""
    store = kvq.store_dtype(cfg.kv_dtype, cfg.dtype)
    defs = {
        "c_kv": ParamDef((num_pages, page_size, cfg.kv_lora_rank),
                         ("none", "kv_seq", "none"), store, init="zeros"),
        "k_rope": ParamDef((num_pages, page_size, cfg.rope_head_dim),
                           ("none", "kv_seq", "none"), store, init="zeros"),
    }
    if kvq.is_quantized(cfg.kv_dtype):
        for name in ("c_kv_scale", "k_rope_scale"):
            defs[name] = ParamDef((num_pages, 1, page_size),
                                  ("none", "none", "kv_seq"), "float32",
                                  init="ones")
    return defs


def _commit_latent(pool: Dict[str, jax.Array], name: str, blk, off, new,
                   cfg: ModelConfig) -> Dict[str, jax.Array]:
    """Write new latent/rope lines into the page pool, quantizing on the
    way in when the pool is quantized (see attention._commit_kv)."""
    out = {}
    if f"{name}_scale" in pool:
        new, s = kvq.quantize(new, cfg.kv_dtype, -1)
        out[f"{name}_scale"] = pool[f"{name}_scale"].at[blk, 0, off].set(s)
    out[name] = pool[name].at[blk, off].set(new.astype(pool[name].dtype))
    return out


def _gather_latent_pages(pool: Dict[str, jax.Array], name: str,
                         block_table, cfg: ModelConfig) -> jax.Array:
    """One slot's latent (or rope) pages as a (1, S, d) sequence in the
    model dtype, dequantized when the pool is quantized."""
    g = pool[name][block_table]                          # (nb, page, d)
    if f"{name}_scale" in pool:
        g = kvq.dequantize(g, pool[f"{name}_scale"][block_table][:, 0]
                           ).astype(cfg.dtype)
    return g.reshape(1, -1, g.shape[-1])


def _mla_attend(p, q_nope, q_rope, c_kv, k_rope, valid, cfg: ModelConfig):
    """Shared paged-attention core.  q_* (B,T,H,*); c_kv (B,S,r);
    k_rope (B,S,dr); valid (B,T,S) bool."""
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    scale = 1.0 / ((dn + dr) ** 0.5)
    if cfg.mla_absorb:
        q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])
        s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv)
             + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope))
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, :, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(c_kv.dtype)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", w, c_kv)
        o = jnp.einsum("bqhr,rhk->bqhk", o_lat, p["wv_b"])
    else:
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
        s = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
             + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope))
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, :, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqs,bshk->bqhk", w, v)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"])


def mla_decode_paged(p, x: jax.Array, pool: Dict[str, jax.Array],
                     block_tables: jax.Array, pos: jax.Array,
                     cfg: ModelConfig, *, page_size: int,
                     backend: Optional[str] = None,
                     pipeline: Optional[str] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token MLA decode against the paged latent pool.  x (B,1,D);
    pool c_kv (P,page,r) / k_rope (P,page,dr); block_tables (B,n_blocks);
    pos (B,).

    Paged decode always runs in the compressed latent space (the absorbed
    form: fold ``wk_b`` into q, attend against ``c_kv`` directly, fold
    ``wv_b`` back out) regardless of ``cfg.mla_absorb`` — it is the
    IO-optimal form the Pallas kernel implements, and it is mathematically
    identical to the per-head re-expansion.  The attention core dispatches
    through the kernel registry (kernels/ops.py ``mla_paged_attention``);
    the dense-cache :func:`mla_decode` keeps honoring ``cfg.mla_absorb``.
    """
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    posb = pos.astype(jnp.int32)[:, None]
    q_nope, q_rope = _queries(p, x, posb, cfg)
    c_new, kr_new = _latent_kv(p, x, posb, cfg)
    blk = jnp.take_along_axis(block_tables, posb // page_size, axis=1)[:, 0]
    off = pos % page_size
    pool = {**pool,
            **_commit_latent(pool, "c_kv", blk, off, c_new[:, 0], cfg),
            **_commit_latent(pool, "k_rope", blk, off, kr_new[:, 0], cfg)}
    scale = 1.0 / ((dn + dr) ** 0.5)
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])     # (B,1,H,r)
    with jax.named_scope("paged_attention"):
        o_lat = kernel_ops.mla_paged_attention(
            q_lat[:, 0], q_rope[:, 0], pool["c_kv"], pool["k_rope"],
            block_tables, pos, scale=scale,
            c_scale=pool.get("c_kv_scale"),
            r_scale=pool.get("k_rope_scale"),
            backend=backend,
            sharded=cfg.tp_axis is not None,
            pipeline=pipeline)[:, None]                         # (B,1,H,r)
    o = jnp.einsum("bqhr,rhk->bqhk", o_lat.astype(x.dtype), p["wv_b"])
    if cfg.tp_axis is not None and cfg.tp_overlap == "ring":
        H_loc, dk = o.shape[2], o.shape[3]
        out = coll.row_parallel_matmul(
            o.reshape(B, 1, H_loc * dk),
            p["wo"].reshape(H_loc * dk, -1), cfg.tp_axis, "ring")
    else:
        out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"])
        if cfg.tp_axis is not None:
            # head-parallel shard over the latent: replicated c_kv/k_rope
            # pages, partitioned q/o projections — the o-proj contracted
            # local heads only
            out = coll.row_parallel_psum(out, cfg.tp_axis)
    out = constrain(out, "batch", "seq", "d_model")
    return out, pool


def mla_decode_verify_paged(p, x: jax.Array, pool: Dict[str, jax.Array],
                            block_tables: jax.Array, pos: jax.Array,
                            cfg: ModelConfig, *, page_size: int,
                            backend: Optional[str] = None,
                            pipeline: Optional[str] = None
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token MLA verification against the paged latent pool (spec
    decoding).  x (B, T, D) draft-chain tokens at positions ``pos + t``;
    pos (B,) first-token write position.  Like :func:`mla_decode_paged`
    this always runs the absorbed/latent form; all T latent lines are
    written, then all T queries share one page walk
    (kernels ``mla_paged_attention_verify``).  Rejected-draft writes are
    rolled back by host-side position bookkeeping (see
    attention.decode_verify_paged).
    """
    B, T, _ = x.shape
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    posq = (pos.astype(jnp.int32)[:, None]
            + jnp.arange(T, dtype=jnp.int32)[None, :])          # (B, T)
    q_nope, q_rope = _queries(p, x, posq, cfg)                  # (B,T,H,*)
    c_new, kr_new = _latent_kv(p, x, posq, cfg)                 # (B,T,*)
    n_blocks = block_tables.shape[1]
    blk_idx = jnp.minimum(posq // page_size, n_blocks - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)
    off = posq % page_size
    pool = {**pool,
            **_commit_latent(pool, "c_kv", blk, off, c_new, cfg),
            **_commit_latent(pool, "k_rope", blk, off, kr_new, cfg)}
    scale = 1.0 / ((dn + dr) ** 0.5)
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])     # (B,T,H,r)
    with jax.named_scope("paged_attention"):
        o_lat = kernel_ops.mla_paged_attention_verify(
            q_lat, q_rope, pool["c_kv"], pool["k_rope"], block_tables, pos,
            scale=scale,
            c_scale=pool.get("c_kv_scale"),
            r_scale=pool.get("k_rope_scale"),
            backend=backend,
            sharded=cfg.tp_axis is not None,
            pipeline=pipeline)                                  # (B,T,H,r)
    o = jnp.einsum("bqhr,rhk->bqhk", o_lat.astype(x.dtype), p["wv_b"])
    if cfg.tp_axis is not None and cfg.tp_overlap == "ring":
        H_loc, dk = o.shape[2], o.shape[3]
        out = coll.row_parallel_matmul(
            o.reshape(B, T, H_loc * dk),
            p["wo"].reshape(H_loc * dk, -1), cfg.tp_axis, "ring")
    else:
        out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"])
        if cfg.tp_axis is not None:
            out = coll.row_parallel_psum(out, cfg.tp_axis)
    out = constrain(out, "batch", "seq", "d_model")
    return out, pool


def mla_prefill_paged(p, x: jax.Array, pool: Dict[str, jax.Array],
                      block_table: jax.Array, offset: jax.Array,
                      cfg: ModelConfig, *, page_size: int
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked MLA prefill for one request: x (1,T,D) at positions
    offset..offset+T-1; block_table (n_blocks,)."""
    B, T, _ = x.shape
    idx = offset + jnp.arange(T, dtype=jnp.int32)
    q_nope, q_rope = _queries(p, x, idx[None, :], cfg)
    c_new, kr_new = _latent_kv(p, x, idx[None, :], cfg)
    blk, off = block_table[idx // page_size], idx % page_size
    pool = {**pool,
            **_commit_latent(pool, "c_kv", blk, off, c_new[0], cfg),
            **_commit_latent(pool, "k_rope", blk, off, kr_new[0], cfg)}
    S = block_table.shape[0] * page_size
    c_kv = _gather_latent_pages(pool, "c_kv", block_table, cfg)
    k_rope = _gather_latent_pages(pool, "k_rope", block_table, cfg)
    valid = (idx[:, None] >= jnp.arange(S, dtype=jnp.int32)[None, :])[None]
    out = _mla_attend(p, q_nope, q_rope, c_kv, k_rope, valid, cfg)
    out = constrain(out, "batch", "seq", "d_model")
    return out, pool


def mla_decode(p, x: jax.Array, cache: Dict[str, jax.Array], pos: jax.Array,
               cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B, _, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    posb = jnp.broadcast_to(pos.astype(jnp.int32), (B, 1))
    q_nope, q_rope = _queries(p, x, posb, cfg)                 # (B,1,H,*)
    c_new, kr_new = _latent_kv(p, x, posb, cfg)                # (B,1,*)
    c_kv = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos, axis=1)
    c_kv = constrain(c_kv, "batch", "kv_seq", None)
    k_rope = constrain(k_rope, "batch", "kv_seq", None)
    Smax = c_kv.shape[1]
    scale = 1.0 / ((dn + dr) ** 0.5)
    valid = jnp.arange(Smax, dtype=jnp.int32)[None, :] <= pos

    if cfg.mla_absorb:
        # fold wk_b into q, run attention in latent space, fold wv_b out
        q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])     # (B,1,H,r)
        s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv)
             + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope))
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(c_kv.dtype)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", w, c_kv)               # (B,1,H,r)
        o = jnp.einsum("bqhr,rhk->bqhk", o_lat, p["wv_b"])          # (B,1,H,dv)
    else:
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
        s = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
             + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope))
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqs,bshk->bqhk", w, v)
    out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"])
    out = constrain(out, "batch", "seq", "d_model")
    return out, {"c_kv": c_kv, "k_rope": k_rope}
