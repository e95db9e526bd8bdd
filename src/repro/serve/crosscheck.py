"""Ledger <-> HLO cross-check for the paged decode step.

The scheduler's per-request roofline ledger prices one decode token
*analytically* (scheduler.decode_token_flops/bytes).  This module closes
the loop the way the paper cross-checks its FLOP/traffic counters against
PMU measurements (§2.4): lower and compile the engine's actual jitted
decode step, walk the partitioned HLO with the full-module cost model
(core/roofline/hlo_cost), and compare W and Q.

One correction is applied before comparing, mirroring
``substitute_flash``: the compiled *reference* decode materializes the
gathered (B, S, KV, hd) K/V to HBM (the ``paged_attention`` scope's
measured bytes), which the Pallas kernel never does — its traffic is the
page walk itself, exactly the ledger's ``(L + 1) * kv_line`` term.  So the
scope's measured bytes are swapped for the kernel pricing
(substitute.substitute_paged_attention) and the remainder of the step
(weight reads, FFN, norms, logits, cache writes) is compared as measured.

The decode-only step is characterized (without the fused sampling tail):
the ledger models decode; sampling adds O(B * V) sort/RNG traffic that is
deliberately outside the ledger's W/Q.

Speculative phase split: :func:`crosscheck_verify` runs the same loop for
the multi-token *verification* step (models.decode_step_verify_paged, the
speculative subsystem's target-model pass) — per-phase attribution in the
spirit of the time-based / hierarchical roofline follow-ups (arXiv
2009.04598, 2009.05257).  The substitution prices the verify kernel's
shared page walk ((L + 2T - 1) lines, see
substitute.paged_attention_kernel_bytes ``n_q``), so the cross-check
confirms the claim the whole subsystem rests on: W scales by T while Q
stays ~flat, i.e. measured arithmetic intensity really does approach
T * I_decode.

HBM-capacity axis: :func:`capacity_report` extends the accounting from
bandwidth (bytes *moved* per token) to capacity (bytes *resident* per
request) — the hierarchy level "Hierarchical Roofline Performance
Analysis" treats per memory tier.  Decode throughput is memory-BOUND, so
at fixed intensity the only lever left is concurrency; concurrency is
capped by how many KV pages fit beside the weights in HBM.  The report
prices one physical page across every cache leaf, counts pages in use /
deduplicated by prefix sharing / reclaimed by preemption, and compares
the engine's effective batch against the capacity-implied maximum — the
throughput-per-byte-saved view the block pool exists to improve.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.roofline import extract
from repro.core.roofline.substitute import substitute_paged_attention
from repro.models import decode_step_paged, decode_step_verify_paged
from repro.models.common import param_counts

from .scheduler import (attn_kernel_vmem_bytes, decode_collective_count,
                        decode_step_ici_bytes, decode_token_bytes,
                        decode_token_flops, kv_line_bytes,
                        params_bytes_active, slot_swap_bytes, state_bytes)


def decode_step_character(engine) -> extract.StepCharacter:
    """Compile the engine's decode step (jnp reference backend, so the HLO
    is analyzable) at its current shapes and characterize it."""
    if engine._kv is None:
        raise ValueError("engine has no live pool; submit work or reset()")
    cfg, kv, e = engine.cfg, engine._kv, engine.ecfg
    ps = e.page_size

    def step(p, pools, bt, tok, pos, act):
        return decode_step_paged(p, cfg, pools, bt, tok, pos, act,
                                 page_size=ps, backend="jnp")

    B = e.num_slots
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (engine.params, kv.pools,
         jnp.zeros((B, kv.blocks_per_slot), jnp.int32),
         jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32),
         jnp.zeros((B,), bool)))
    compiled = jax.jit(step).lower(*abstract).compile()
    return extract.characterize(compiled)


def crosscheck_decode(engine, requests: Optional[List] = None) -> Dict:
    """Compare the analytic ledger's W/Q for one decode step against the
    compiled step's HLO measurement (kernel-substituted; see module
    docstring).  ``requests`` defaults to the engine's currently decoding
    requests.  Returns both sides plus their ratios."""
    cfg = engine.cfg
    if requests is None:
        requests = engine._sched.decode_requests()
    if not requests:
        raise ValueError("no decoding requests to cross-check")
    contexts = [r.context_len for r in requests]
    n_active = len(contexts)

    analytic_flops = sum(decode_token_flops(cfg, L) for L in contexts)
    analytic_bytes = sum(decode_token_bytes(cfg, L, n_active)
                         for L in contexts)

    char = extract.character_as_dict(decode_step_character(engine))
    sub = substitute_paged_attention(char, contexts, kv_line_bytes(cfg))
    hlo = sub or char
    return {
        "analytic_flops": analytic_flops,
        "analytic_bytes": analytic_bytes,
        "hlo_flops": hlo["flops_dev"],
        "hlo_bytes": hlo["hbm_bytes_dev"],
        "hlo_bytes_raw": char["hbm_bytes_dev"],
        "scope_bytes_raw": (char.get("scopes", {})
                            .get("paged_attention", {}).get("bytes", 0.0)),
        "flops_ratio": analytic_flops / max(hlo["flops_dev"], 1.0),
        "bytes_ratio": analytic_bytes / max(hlo["hbm_bytes_dev"], 1.0),
        "substituted": sub is not None,
        "contexts": contexts,
    }


def capacity_report(engine) -> Dict:
    """The HBM-capacity axis of the serving roofline (see module
    docstring): page economics of the engine's live block pool.

    ``capacity_max_batch`` is the concurrency ceiling the target chip's
    HBM implies at this engine's ``max_len``:

        B_max = (HBM - params_bytes) / (pages_per_request * page_bytes)

    ``effective_batch`` (live decode slots) compared against it says
    whether the deployment is slot-limited or capacity-limited; every
    deduplicated or on-demand-deferred page moves B_max's denominator.

    A :class:`~repro.serve.cluster.Cluster` aggregates: per-replica rows
    (each replica owns its own pool, so pages in use / peak are
    per-replica facts) plus cluster-level sums — B_max adds across
    replicas because each brings its own HBM.
    """
    if hasattr(engine, "replicas"):
        return _cluster_capacity_report(engine)
    if engine._kv is None:
        raise ValueError("engine has no live pool; submit work or reset()")
    kv, cfg, chip = engine._kv, engine.cfg, engine.ecfg.chip
    pool = kv.pool
    pb = kv.page_bytes
    pages_per_req = kv.pages_needed(kv.max_len)
    params_b = param_counts(cfg)["total"] * jnp.dtype(cfg.dtype).itemsize
    hbm_for_kv = max(chip.hbm_bytes - params_b, 0.0)
    cap_batch = int(hbm_for_kv // max(pages_per_req * pb, 1))
    active = [r for r in engine._sched.active.values()] \
        if engine._sched else []
    return {
        "page_bytes": pb,
        "pages_total": kv.num_pages - 1,            # minus the trash page
        "pages_in_use": pool.pages_in_use,
        "pages_peak": pool.stats.peak_in_use,
        "pages_cached": pool.pages_cached,
        "pages_deduped": pool.stats.dedup_hits,
        "cow_copies": pool.stats.cow_copies,
        "evictions": pool.stats.evictions,
        "preemptions": engine._sched.preempt_count if engine._sched else 0,
        "pool_bytes": pb * (kv.num_pages - 1),
        "params_bytes": float(params_b),
        "pages_per_request": pages_per_req,
        "effective_batch": len(active),
        "capacity_max_batch": cap_batch,
    }


_CAP_SUM_KEYS = ("pages_total", "pages_in_use", "pages_peak", "pages_cached",
                 "pages_deduped", "cow_copies", "evictions", "preemptions",
                 "pool_bytes", "effective_batch", "capacity_max_batch")


def _cluster_capacity_report(cluster) -> Dict:
    """Fleet capacity view: one row per live replica (role-tagged), sums
    on the page/batch axes.  Replicas that never received work carry no
    pool and are listed but not summed (``replicas_live``)."""
    per = []
    for i, eng in enumerate(cluster.replicas):
        row: Dict = {"replica": i, "role": cluster.role(i)}
        if eng._kv is None:
            row["live"] = False
        else:
            row.update(capacity_report(eng))
            row["live"] = True
        per.append(row)
    live = [r for r in per if r["live"]]
    if not live:
        raise ValueError("no replica has a live pool; route work through "
                         "the Router (or engine.reset()) first")
    out: Dict = {k: sum(r[k] for r in live) for k in _CAP_SUM_KEYS}
    # per-chip facts are fleet-invariant (same cfg/ecfg on every replica)
    for k in ("page_bytes", "params_bytes", "pages_per_request"):
        out[k] = live[0][k]
    agg = cluster.aggregate_ledger()
    out.update(replicas=per, replicas_live=len(live),
               migrations=int(agg.migrations),
               migration_bytes=float(agg.migration_bytes))
    return out


def crosscheck_collectives(engine) -> Dict:
    """Ledger <-> HLO cross-check for the COMMUNICATION roofline axis.

    The sharded engine's ledger charges each decode step an analytic
    per-device ICI wire cost (scheduler.decode_step_ici_bytes: one ring
    all-reduce per row-parallel matmul epilogue, one tiled all-gather for
    an untied vocab-sharded head).  This closes the loop the same way the
    decode cross-check does for HBM traffic: compile the engine's LIVE
    shard_map decode step, parse the partitioned module's collective ops
    (core/roofline/hlo — the "uncore counter" of the distributed
    machine), attribute them to mesh axes, and compare per-device wire
    bytes.  ``engine`` must be a serve.shard.ShardedEngine (or subclass)
    on a tp > 1 mesh.
    """
    mesh = getattr(engine, "mesh", None)
    if mesh is None:
        raise ValueError("engine has no tp > 1 mesh; build a "
                         "ShardedEngine(mesh_shape=(1, tp)) and submit "
                         "work first")
    cfg, e = engine.cfg, engine.ecfg
    analytic = decode_step_ici_bytes(cfg, e.num_slots, engine.tp)
    compiled = engine.decode_step_compiled()
    char = extract.characterize(compiled, mesh=mesh)
    hlo_ici = char.collectives.ici_wire_bytes
    return {
        "analytic_ici_bytes": analytic,
        "hlo_ici_bytes": hlo_ici,
        "hlo_dcn_bytes": char.collectives.dcn_wire_bytes,
        "ici_ratio": analytic / max(hlo_ici, 1.0),
        "n_collective_ops": char.collectives.n_ops,
        "by_kind": dict(char.collectives.by_kind),
        "collective_count_analytic": decode_collective_count(cfg),
        "tp": engine.tp,
    }


def verify_step_character(engine, n_tokens: int) -> extract.StepCharacter:
    """Compile the speculative engine's multi-token verification step
    (jnp reference backend) at its current shapes and characterize it."""
    if engine._kv is None:
        raise ValueError("engine has no live pool; submit work or reset()")
    cfg, kv, e = engine.cfg, engine._kv, engine.ecfg
    ps, T = e.page_size, n_tokens

    def step(p, pools, bt, toks, pos, act):
        return decode_step_verify_paged(p, cfg, pools, bt, toks, pos, act,
                                        page_size=ps, backend="jnp")

    B = e.num_slots
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (engine.params, kv.pools,
         jnp.zeros((B, kv.blocks_per_slot), jnp.int32),
         jnp.zeros((B, T), jnp.int32), jnp.zeros((B,), jnp.int32),
         jnp.zeros((B,), bool)))
    compiled = jax.jit(step).lower(*abstract).compile()
    return extract.characterize(compiled)


def crosscheck_verify(engine, requests: Optional[List] = None,
                      n_tokens: Optional[int] = None) -> Dict:
    """Ledger <-> HLO cross-check for ONE speculative verification step
    (the draft/verify phase split of the decode cross-check above).

    The analytic side is exactly what RooflineLedger.add_verify_step
    charges each request: T scored tokens per weight pass, one shared page
    walk.  ``engine`` is a serve.spec.SpecEngine (or any engine, with
    ``n_tokens`` given explicitly)."""
    cfg = engine.cfg
    if n_tokens is None:
        n_tokens = engine.scfg.k + 1
    T = n_tokens
    if requests is None:
        requests = engine._sched.decode_requests()
    if not requests:
        raise ValueError("no decoding requests to cross-check")
    contexts = [r.context_len for r in requests]
    n_active = len(contexts)
    line = kv_line_bytes(cfg)

    analytic_flops = sum(decode_token_flops(cfg, L + t)
                         for L in contexts for t in range(T))
    analytic_bytes = sum(
        params_bytes_active(cfg) / n_active + (L + 2 * T - 1) * line
        + 2 * state_bytes(cfg) for L in contexts)

    char = extract.character_as_dict(verify_step_character(engine, T))
    sub = substitute_paged_attention(char, contexts, line, n_q=T)
    hlo = sub or char
    return {
        "analytic_flops": analytic_flops,
        "analytic_bytes": analytic_bytes,
        "hlo_flops": hlo["flops_dev"],
        "hlo_bytes": hlo["hbm_bytes_dev"],
        "hlo_bytes_raw": char["hbm_bytes_dev"],
        "scope_bytes_raw": (char.get("scopes", {})
                            .get("paged_attention", {}).get("bytes", 0.0)),
        "flops_ratio": analytic_flops / max(hlo["flops_dev"], 1.0),
        "bytes_ratio": analytic_bytes / max(hlo["hbm_bytes_dev"], 1.0),
        "substituted": sub is not None,
        "contexts": contexts,
        "n_tokens": T,
    }


def step_cost_analysis(engine) -> Dict[str, float]:
    """Flops + bytes-accessed of the REAL fused decode+sample step, from
    the compiled module's own cost model.

    Unlike :func:`decode_step_character` (which compiles the decode body
    alone with the jnp reference backend for HLO parsing), this lowers
    ``engine._decode_fn`` — the exact program whose fenced wall the phase
    ledger records — so the time budget's compute/HBM rows divide bytes
    the step actually moves, sampling tail included."""
    if engine._kv is None:
        raise ValueError("engine has no live pool; submit work or reset()")
    e, kv = engine.ecfg, engine._kv
    import numpy as np
    bt = kv.block_tables_for(list(range(e.num_slots)))
    args = (engine.params, kv.pools, bt,
            jnp.asarray(np.zeros((e.num_slots, 1), np.int32)),
            jnp.asarray(np.zeros((e.num_slots,), np.int32)),
            jnp.asarray(np.ones((e.num_slots,), bool)),
            jnp.asarray(engine._key_data), jnp.asarray(engine._steps),
            jnp.asarray(engine._temps), jnp.asarray(engine._top_ks),
            jnp.asarray(engine._top_ps))
    ca = engine._decode_fn.lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def _kernel_grid_vmem_walk(cfg, context_len: int, page_size: int,
                           n_q: int = 1, pipeline: str = "off") -> float:
    """Independent re-derivation of one slot's paged-attention VMEM
    traffic by walking the Pallas grids in kernels/paged_attention.py
    literally: for every grid step, sum the ``in_specs`` block bytes the
    BlockSpec index maps stream in, the fp32 scratch carries the kernel
    reads AND rewrites, and the output block written at the flush step —
    plus the step's appended KV line crossing VMEM on its way to the
    pools.  ``pipeline="double"`` walks the two-slab manual-DMA grids
    instead: the block loop lives inside one (slot[, kv_head]) program,
    so the query slab enters VMEM once per program rather than once per
    block step (streamed pages / carries / out are the same walk).  GQA
    decode over unquantized pools at ``pipeline="off"`` walks the
    live-page kernel: one program per slot fetches the query once and
    loops over chunks of C pages of every head, one carry update per
    chunk.  The closed-form pricing (kernels.paged_decode_vmem_bytes)
    must agree with this walk; drift means someone changed the kernel's
    block geometry without repricing the ledger."""
    from repro.kernels import quantize as kvq
    from repro.kernels.paged_attention import (_check_pipeline, live_blocks,
                                               live_walk_chunk_pages)
    _check_pipeline(pipeline)
    isize = jnp.dtype(cfg.dtype).itemsize
    kv_isize = kvq.store_itemsize(cfg.kv_dtype, cfg.dtype)
    s = 4 if kvq.is_quantized(cfg.kv_dtype) else 0
    nb = live_blocks(context_len, page_size, n_q)
    q_steps = nb if pipeline == "off" else 1
    total = 0.0
    for unit, reps in cfg.segments():
        for b in unit:
            if b.mixer == "attn":
                KV, G, hd = (cfg.n_kv_heads,
                             cfg.n_heads // cfg.n_kv_heads, cfg.hd)
                rows = G * n_q
                # quantized pools stream (page, hd) k/v slabs at the
                # storage itemsize plus a (page,) f32 scale slab each
                kv_line = hd * kv_isize + s
                stream = nb * 2 * page_size * kv_line     # k + v (+scale)
                carry = 2 * rows * (hd + 2) * 4           # m/l/acc r+w
                if n_q == 1 and pipeline == "off" and not s:
                    # grid (B,): one query fetch, one carry update a chunk
                    chunk = live_walk_chunk_pages(page_size, KV, hd,
                                                  kv_isize)
                    chunks = -(-nb // chunk)
                    walk = KV * (rows * hd * isize + stream
                                 + chunks * carry + rows * hd * isize)
                else:
                    walk = KV * (q_steps * rows * hd * isize  # q block(s)
                                 + stream + nb * carry
                                 + rows * hd * isize)         # out flush
                walk += n_q * 2 * KV * kv_line            # appended line
            elif b.mixer == "mla":
                H, r, dr = (cfg.n_heads, cfg.kv_lora_rank,
                            cfg.rope_head_dim)
                rows = H * n_q
                kv_line = (r + dr) * kv_isize + 2 * s     # c + rope scales
                per_step = (page_size * kv_line           # c + r slabs
                            + 2 * rows * (r + 2) * 4)     # m/l/acc r+w
                walk = (q_steps * rows * (r + dr) * isize  # ql + qr blocks
                        + nb * per_step
                        + rows * r * isize)               # out flush
                walk += n_q * kv_line                     # appended line
            else:
                continue
            total += reps * walk
    return total


def crosscheck_vmem(engine, requests: Optional[List] = None,
                    n_q: int = 1, pipeline: Optional[str] = None) -> Dict:
    """Ledger <-> kernel-geometry cross-check for the VMEM level.

    The VMEM row of the hierarchy has no PMU to read on this stack, so
    the check is pricing-vs-artifact: the scheduler's closed-form
    ``attn_kernel_vmem_bytes`` against an independent walk of the actual
    Pallas BlockSpec grids (:func:`_kernel_grid_vmem_walk`), both priced
    for the kernel variant the engine actually runs (``pipeline``
    defaults to the engine's configured page streaming mode).  A ratio
    off 1.0 means the ledger's VMEM bytes no longer describe the kernel
    that ships."""
    cfg, ps = engine.cfg, engine.ecfg.page_size
    if pipeline is None:
        pipeline = getattr(engine.ecfg, "pipeline", "off")
    if requests is None:
        requests = engine._sched.decode_requests()
    if not requests:
        raise ValueError("no decoding requests to cross-check")
    contexts = [r.context_len for r in requests]
    analytic = sum(attn_kernel_vmem_bytes(cfg, L, ps, n_q=n_q,
                                          pipeline=pipeline)
                   for L in contexts)
    walked = sum(_kernel_grid_vmem_walk(cfg, L, ps, n_q=n_q,
                                        pipeline=pipeline)
                 for L in contexts)
    return {
        "analytic_vmem_bytes": analytic,
        "kernel_walk_bytes": walked,
        "vmem_ratio": analytic / max(walked, 1.0),
        "pipeline": pipeline,
        "contexts": contexts,
    }


def crosscheck_host(engine, n_blocks: Optional[int] = None) -> Dict:
    """Ledger <-> HLO cross-check for the HOST level (swap DMAs).

    The swap phase charges ``slot_swap_bytes`` per preemption round-trip.
    This compiles the same gather-and-pack program ``PagedKVCache
    .swap_out`` runs (per-page gathers of every cache leaf, bitcast +
    concatenated into the ONE flat device->host buffer) abstractly at the
    engine's live pool shapes and compares the compiled output footprint
    (extract.MemoryFootprint.output_bytes — the bytes that cross the
    link) against the pricing."""
    if engine._kv is None:
        raise ValueError("engine has no live pool; submit work or reset()")
    cfg, kv, e = engine.cfg, engine._kv, engine.ecfg
    if n_blocks is None:
        live = [kv.slot_pages(s) for s in range(e.num_slots)
                if s in kv._meta]
        n_blocks = max(live) if live else kv.pages_needed(kv.max_len)
    n_blocks = max(int(n_blocks), 1)

    def pack(pools, phys, slot):
        dev = []
        for seg_pool, seg_flag in zip(pools, kv._paged):
            def gather(pool, paged):
                if paged:
                    return pool[:, phys]
                return jax.lax.dynamic_slice_in_dim(pool, slot, 1, axis=1)
            dev.append(jax.tree.map(gather, seg_pool, seg_flag))
        flat = [jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)
                for x in jax.tree.leaves(dev)]
        return jnp.concatenate(flat)

    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), kv.pools)
    compiled = jax.jit(pack, static_argnums=(2,)).lower(
        abstract, jax.ShapeDtypeStruct((n_blocks,), jnp.int32), 0).compile()
    foot = extract.MemoryFootprint.from_compiled(compiled)
    analytic = slot_swap_bytes(cfg, n_blocks, e.page_size)
    return {
        "analytic_swap_bytes": analytic,
        "hlo_output_bytes": float(foot.output_bytes),
        "host_ratio": analytic / max(float(foot.output_bytes), 1.0),
        "n_blocks": n_blocks,
    }


def overlapped_levels(ecfg) -> List[str]:
    """Memory levels an engine config claims to overlap: ``vmem`` when
    the paged kernels double-buffer their page walk (EngineConfig
    .pipeline != "off"), ``ici`` when the decode collectives run as ring
    matmuls under the epilogue compute (EngineConfig.overlap != "none")."""
    out = []
    if getattr(ecfg, "pipeline", "off") != "off":
        out.append("vmem")
    if getattr(ecfg, "overlap", "none") != "none":
        out.append("ici")
    return out


def crosscheck_overlap(engine_off, engine_on, prompts, gen, *,
                       windows: int = 3, wall_tol: float = 0.25,
                       term_tol: float = 1e-6, betas=None) -> Dict:
    """Measured <-> budget cross-check for the OVERLAP extension of the
    time-based roofline (core.roofline.model.overlapped_budget).

    Drives the SAME fenced steady-state decode window (the
    ``run_hierarchy`` protocol: prefill outside, ``reset_phases``, pure
    saturated decode steps, ``windows`` interleaved repetitions, min
    per-step wall) on two engines that differ ONLY in their overlap
    configuration — ``engine_off`` serial (pipeline="off",
    overlap="none"), ``engine_on`` with page streaming double-buffered
    and/or ring collectives on.  Asserts

    * byte-identical greedy tokens — overlap is a schedule change, not a
      numerics change;
    * for every overlapped level the ledger's time term did not GROW
      (the double-buffered kernel's q-slab term genuinely shrinks; the
      ring's wire term stays fixed) beyond ``term_tol``;
    * the overlapped wall does not regress past ``wall_off * (1 +
      wall_tol)`` — the overlapped bound must hold where the serial sum
      may not.

    The measured wall delta is attributed back as an inferred per-level
    overlap fraction ``ov_l = clamp((wall_off - wall_on) / t_l, 0, 1)``
    — the fraction of that level's serial term the measured delta is
    consistent with hiding."""
    from repro.core.roofline.microbench import run_microbench
    from repro.core.roofline.model import overlapped_budget, time_attribution

    def steady(e):
        for p in prompts:
            e.submit(p % e.cfg.vocab_size, gen)
        e.step()                      # prefill all slots + first tokens
        e.reset_phases()              # timed window: pure decode steps
        done = e.run()
        ph = e.phases["decode"]
        return ph.wall_s / max(ph.steps, 1), ph, done

    steady(engine_off)                # compile warm-up, both engines
    steady(engine_on)
    walls_off, walls_on = [], []
    ph_off = ph_on = done_off = done_on = None
    for _ in range(windows):          # interleaved: noise hits both sides
        w0, ph_off, done_off = steady(engine_off)
        w1, ph_on, done_on = steady(engine_on)
        walls_off.append(w0)
        walls_on.append(w1)
    wall_off, wall_on = min(walls_off), min(walls_on)

    toks_off = [list(r.generated) for r in
                sorted(done_off, key=lambda r: r.request_id)]
    toks_on = [list(r.generated) for r in
               sorted(done_on, key=lambda r: r.request_id)]
    if toks_off != toks_on:
        raise RuntimeError(
            "overlap changed greedy outputs: the overlapped engine must "
            f"be byte-identical to the serial one ({toks_on} vs "
            f"{toks_off})")

    if betas is None:
        betas = run_microbench(quick=True).level_betas()
    # per-STEP terms, so they compare 1:1 with the per-step walls
    att_off = {k: v / max(ph_off.steps, 1)
               for k, v in time_attribution(ph_off, betas).items()}
    att_on = {k: v / max(ph_on.steps, 1)
              for k, v in time_attribution(ph_on, betas).items()}
    levels = overlapped_levels(engine_on.ecfg)
    for lvl in levels:
        if att_on[lvl] > att_off[lvl] * (1.0 + term_tol):
            raise RuntimeError(
                f"overlap grew the {lvl} time term: "
                f"{att_on[lvl]:.3e}s on vs {att_off[lvl]:.3e}s off — the "
                "overlapped kernel/collective moves MORE bytes than the "
                "serial one it replaces")
    if wall_on > wall_off * (1.0 + wall_tol):
        raise RuntimeError(
            f"overlapped steady-state wall regressed: {wall_on * 1e6:.0f}"
            f"us/step vs serial {wall_off * 1e6:.0f}us/step exceeds "
            f"+{wall_tol:.0%} (raw per-window walls: "
            f"on={['%.0fus' % (w * 1e6) for w in walls_on]}, "
            f"off={['%.0fus' % (w * 1e6) for w in walls_off]})")

    delta = wall_off - wall_on            # per-step, like the terms
    inferred = {}
    for lvl in levels:
        t = att_off[lvl]
        inferred[lvl] = min(max(delta / t, 0.0), 1.0) if t > 0 else 0.0
    return {
        "wall_off_s": wall_off, "wall_on_s": wall_on,
        "walls_off_s": walls_off, "walls_on_s": walls_on,
        "levels": levels,
        "terms_off": att_off, "terms_on": att_on,
        "inferred_overlap": inferred,
        "serial_budget_s": sum(att_off.values()),
        "overlapped_budget_s": overlapped_budget(att_on, inferred),
        "generated": toks_on,
    }
