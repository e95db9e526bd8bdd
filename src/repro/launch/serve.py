"""Serving launcher: continuous-batching generation with the paged engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 32 --new-tokens 32 --slots 2

Speculative decoding (draft/verify; serve/spec.py):

    ... --spec ngram --spec-k 4              # weight-free prompt lookup
    ... --spec draft --draft-arch qwen3-0.6b # small-model drafting
    ... --spec draft --spec-k-adaptive       # EWMA-adapted draft length

Block-pool memory management (serve/block_pool.py): pages are allocated
on demand as contexts grow, ``--prefix-cache`` dedups shared prompt
prefixes via content-hash page aliasing (+ copy-on-write on divergence),
and an undersized pool (``--num-pages``) exercises LRU preemption with
``--preempt swap`` (host round-trip) or ``--preempt recompute``:

    ... --prefix-cache --num-pages 24 --watermark 0.1 --preempt swap

Each run prints measured tokens/s plus the per-request decode roofline
ledger (arithmetic intensity, bound class, roofline ceiling); speculative
runs add acceptance rate, tokens-per-weight-pass, and the predicted
speedup from the memory-bound model.  Archs without a paged decode path
(enc-dec, VLM) fall back to the static whole-batch engine.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, smoke
from repro.core.roofline.hardware import (HOST_CPU_FALLBACK, TPU_V5E,
                                          chip_for_device_kind)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.parallel.mesh import make_host_mesh
from repro.serve import (EngineConfig, GenerateConfig, SpecConfig,
                         init_sharded_params, make_engine, parse_mesh,
                         supports_paging, supports_spec, tp_sharding_error)
from repro.serve.crosscheck import capacity_report
from repro.serve.spec import speculative_summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS), default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass (0 or >= 1 = off)")
    ap.add_argument("--spec", choices=["off", "ngram", "draft"],
                    default="off",
                    help="speculative decoding proposer (serve/spec.py)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify round")
    ap.add_argument("--spec-k-adaptive", action="store_true",
                    help="EWMA acceptance tracking shrinks/grows the "
                         "drafted length within the fixed verify shape")
    ap.add_argument("--draft-arch", default="qwen3-0.6b",
                    help="draft model arch for --spec draft (shrunk with "
                         "--smoke like the target)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hash prefix sharing + copy-on-write "
                         "(serve/block_pool.py)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="block-pool size incl. trash page (0 = fully "
                         "backed; smaller exercises preemption)")
    ap.add_argument("--watermark", type=float, default=0.0,
                    help="admission slack as a fraction of pool pages")
    ap.add_argument("--preempt", choices=["swap", "recompute"],
                    default="swap",
                    help="pool-dry preemption: swap pages to host or "
                         "drop + recompute on resume")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (0 = one per request)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--chip", choices=["host", "tpu_v5e"], default="tpu_v5e",
                    help="roofline peaks off-TPU; on a TPU the peaks of "
                         "the device JAX reports are used")
    ap.add_argument("--backend", choices=["auto", "pallas", "jnp"],
                    default=None,
                    help="paged-attention kernel backend (kernels/ops.py "
                         "registry; default = registry 'auto')")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8", "fp8_e4m3"],
                    default=None,
                    help="KV-page storage dtype (kernels/quantize.py): "
                         "quantized pools store int8/fp8 values with "
                         "per-page-line f32 scales, shrinking the decode "
                         "page walk ~2x (default = model config, bf16)")
    ap.add_argument("--mesh", default="1,1",
                    help="device mesh 'dp,tp' for tensor-parallel decode "
                         "(serve/shard.py; needs dp*tp visible devices — "
                         "on CPU force them with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--pipeline", choices=["off", "double"], default="off",
                    help="paged-kernel page streaming: 'double' "
                         "double-buffers the Pallas page walk (prefetch "
                         "page b+1 while computing page b; "
                         "kernels/paged_attention.py)")
    ap.add_argument("--overlap", choices=["none", "ring"], default="none",
                    help="decode collective overlap: 'ring' replaces the "
                         "blocking row-parallel psum epilogues with ring "
                         "collective matmuls (parallel/collectives.py; "
                         "tp > 1 meshes only)")
    ap.add_argument("--router", action="store_true",
                    help="serve through the multi-replica front door "
                         "(serve/router.py): dp replica engines behind "
                         "ledger-predicted load balancing.  Implied by "
                         "--mesh dp,tp with dp > 1")
    ap.add_argument("--roles", choices=["mixed", "disagg"], default="mixed",
                    help="replica roles for --router: 'mixed' serves each "
                         "request end to end, 'disagg' splits the fleet "
                         "into prefill and decode replicas with KV-page "
                         "migration between them (serve/cluster.py)")
    ap.add_argument("--link", choices=["dcn", "ici"], default="dcn",
                    help="wire level the migration snapshots are priced "
                         "on (the 'migration' roofline term)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable telemetry (repro.obs) and write the "
                         "Chrome trace-event timeline here — load it in "
                         "chrome://tracing or ui.perfetto.dev")
    ap.add_argument("--metrics-snapshot", default=None, metavar="OUT.prom",
                    help="enable telemetry and write a Prometheus "
                         "text-exposition metrics snapshot here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    mesh_shape = parse_mesh(args.mesh)
    if mesh_shape[1] > 1:
        err = tp_sharding_error(cfg, mesh_shape[1])
        if err:
            raise SystemExit(f"--mesh {args.mesh}: {err}")
    if mesh_shape[0] == 1 and mesh_shape[1] > 1:
        # weights born in their shards: a model larger than one chip
        # never lands whole on device 0
        params = init_sharded_params(cfg, jax.random.key(0),
                                     make_host_mesh(data=1,
                                                    model=mesh_shape[1]))
    else:
        params = init_params(cfg, jax.random.key(0))
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        chip = chip_for_device_kind(dev.device_kind)
    else:
        chip = TPU_V5E if args.chip == "tpu_v5e" else HOST_CPU_FALLBACK
    slots = args.slots or args.batch
    ecfg = EngineConfig(
        num_slots=slots, page_size=args.page_size,
        max_len=args.prompt_len + args.new_tokens,
        prefill_chunk=args.prefill_chunk, chip=chip,
        kernel_backend=args.backend,
        prefix_cache=args.prefix_cache,
        num_pages=args.num_pages or None,
        watermark=args.watermark, preempt_mode=args.preempt,
        pipeline=args.pipeline, overlap=args.overlap,
        kv_dtype=args.kv_dtype,
        telemetry=bool(args.trace or args.metrics_snapshot))
    scfg = None
    if args.spec != "off":
        if not supports_spec(cfg):
            raise SystemExit(f"{cfg.name}: --spec needs attention/MLA "
                             "mixers throughout")
        if args.spec == "draft":
            dcfg = get_config(args.draft_arch)
            if args.smoke:
                dcfg = smoke(dcfg)
            scfg = SpecConfig(k=args.spec_k, proposer="draft",
                              draft_cfg=dcfg,
                              draft_params=init_params(
                                  dcfg, jax.random.key(4)),
                              adaptive=args.spec_k_adaptive)
        else:
            scfg = SpecConfig(k=args.spec_k, proposer="ngram",
                              adaptive=args.spec_k_adaptive)
    if args.router or mesh_shape[0] > 1:
        return _run_router(args, cfg, params, ecfg, scfg, mesh_shape, chip)
    engine = make_engine(cfg, params, ecfg, scfg, mesh_shape=mesh_shape)

    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    gen = GenerateConfig(max_new_tokens=args.new_tokens,
                         temperature=args.temperature, top_k=args.top_k,
                         top_p=args.top_p)

    if not supports_paging(cfg):
        kwargs = {}
        if cfg.is_encoder_decoder:
            kwargs["enc_embeds"] = jax.random.normal(
                jax.random.key(2),
                (args.batch, cfg.n_audio_frames, cfg.d_model),
                jnp.float32).astype(cfg.dtype)
        if cfg.n_image_tokens:
            kwargs["img_embeds"] = jax.random.normal(
                jax.random.key(3),
                (args.batch, cfg.n_image_tokens, cfg.d_model),
                jnp.float32).astype(cfg.dtype)
        t0 = time.perf_counter()
        out = engine.generate(prompts, gen, rng=jax.random.key(7), **kwargs)
        dt = time.perf_counter() - t0
        toks = out["tokens"]
        n_new = toks.shape[1] - args.prompt_len
        print(f"[serve/static] {args.batch} seqs x {n_new} new tokens in "
              f"{dt:.2f}s ({args.batch * n_new / dt:.1f} tok/s)")
        _export_telemetry(args, getattr(engine, "obs", None), engine)
        print("[serve] first sequence:",
              toks[0, args.prompt_len:].tolist())
        return

    prompts_np = np.asarray(prompts)
    for b in range(args.batch):
        engine.submit(prompts_np[b], gen, rng=jax.random.fold_in(
            jax.random.key(7), b))
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    n_new = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)} requests, {n_new} new tokens in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s) over {slots} slots "
          f"({engine.decode_steps} decode steps)")
    for r in sorted(done, key=lambda r: r.request_id)[:4]:
        t = engine.roofline_terms(r)
        lat = r.latency_stats()
        print(f"[serve]   req {r.request_id}: {len(r.generated)} tokens "
              f"({r.finish_reason}), AI={t.arithmetic_intensity:.2f} "
              f"{t.bound_class()}, mean_batch={r.ledger.mean_batch:.1f}, "
              f"ttft={lat['ttft_s'] * 1e3:.1f}ms "
              f"itl_p50={lat['itl_p50_s'] * 1e3:.2f}ms "
              f"p95={lat['itl_p95_s'] * 1e3:.2f}ms")
    if mesh_shape[1] > 1:
        # which roof binds decode at this TP width (serve/shard.py):
        # the communication-roofline table over the finished requests
        from repro.core.roofline.report import (COMM_HEADER,
                                                comm_terms_row, text_table)
        rows = [comm_terms_row(f"req {r.request_id}",
                               engine.roofline_terms(r))
                for r in sorted(done, key=lambda r: r.request_id)[:4]]
        print("[serve/mesh] communication roofline "
              f"(tp={mesh_shape[1]}):")
        print(text_table(rows, COMM_HEADER))
    cap = capacity_report(engine)
    print(f"[serve/capacity] pages peak={cap['pages_peak']}"
          f"/{cap['pages_total']} ({cap['page_bytes']} B/page), "
          f"deduped={cap['pages_deduped']} cow={cap['cow_copies']} "
          f"preemptions={cap['preemptions']}, effective batch "
          f"{cap['effective_batch']} vs capacity-implied max "
          f"{cap['capacity_max_batch']} on {chip.name}")
    if args.spec != "off":
        s = speculative_summary(cfg, done, args.spec_k,
                                args.prompt_len + args.new_tokens // 2,
                                draft_cfg=scfg.draft_cfg)
        print(f"[serve/spec] proposer={args.spec} k={args.spec_k} "
              f"acceptance={s['acceptance_rate']:.2f} "
              f"tokens/pass={s['tokens_per_pass']:.2f} "
              f"(predicted {s['predicted_tokens_per_pass']:.2f}), "
              f"predicted memory-bound speedup "
              f"x{s['predicted_speedup']:.2f}")
    _export_telemetry(args, engine.obs, engine)
    first = min(done, key=lambda r: r.request_id)
    print("[serve] first sequence:", first.generated[:16])


def _export_telemetry(args, obs, source):
    """Post-run telemetry export: harvest the source (Engine or Cluster)
    into the registry, write the requested artifacts, and print the
    windowed roofline-attainment table."""
    if obs is None:
        return
    obs.harvest(source)
    if args.trace:
        obs.export_trace(args.trace)
        print(f"[serve/obs] trace written to {args.trace} "
              f"({len(obs.tracer.events)} events) — load in "
              "chrome://tracing or ui.perfetto.dev")
    if args.metrics_snapshot:
        obs.snapshot(args.metrics_snapshot)
        print(f"[serve/obs] metrics snapshot written to "
              f"{args.metrics_snapshot}")
    if obs.attainment.windows:
        from repro.core.roofline.report import (ATTAINMENT_HEADER,
                                                attainment_rows,
                                                text_table)
        print("[serve/obs] roofline attainment windows:")
        print(text_table(attainment_rows(obs.attainment.windows),
                         ATTAINMENT_HEADER))


def _run_router(args, cfg, params, ecfg, scfg, mesh_shape, chip):
    """The multi-replica tier: Cluster + Router over dp replica engines,
    with the TTFT decomposition, migration ledger and fleet capacity
    report alongside the usual throughput numbers."""
    from repro.serve import Cluster, RoleConfig, Router

    if not supports_paging(cfg):
        raise SystemExit(f"{cfg.name}: --router needs the paged decode "
                         "path (decoder-only archs)")
    dp = max(mesh_shape[0], 2 if args.roles == "disagg" else 1)
    if args.roles == "disagg":
        roles = RoleConfig.disaggregated(max(dp // 2, 1), dp - max(dp // 2, 1),
                                         link=args.link)
    else:
        roles = RoleConfig.mixed(dp, link=args.link)
    cluster = Cluster(cfg, params, ecfg, scfg,
                      mesh_shape=(dp, mesh_shape[1]), roles=roles)
    router = Router(cluster)
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab_size))
    gen = GenerateConfig(max_new_tokens=args.new_tokens,
                         temperature=args.temperature, top_k=args.top_k,
                         top_p=args.top_p)
    reqs = [router.submit(prompts[b], gen,
                          rng=jax.random.fold_in(jax.random.key(7), b))
            for b in range(args.batch)]
    t0 = time.perf_counter()
    done = router.run()
    dt = time.perf_counter() - t0
    n_new = sum(len(r.generated) for r in done)
    where = "colocated" if cluster.colocated else "sub-meshes"
    print(f"[serve/router] {len(done)} requests, {n_new} new tokens in "
          f"{dt:.2f}s ({n_new / dt:.1f} tok/s) over dp={dp} "
          f"tp={mesh_shape[1]} replicas ({where}, roles "
          f"{','.join(roles.roles)})")
    for r in sorted(done, key=lambda r: r.request_id)[:4]:
        bd = r.ttft_breakdown()
        print(f"[serve/router]   req {r.request_id}: "
              f"{len(r.generated)} tokens ({r.finish_reason}), "
              f"ttft={r.ttft * 1e3:.1f}ms = queue "
              f"{bd['queue_wait_s'] * 1e3:.1f} + prefill "
              f"{bd['prefill_s'] * 1e3:.1f} + first-decode "
              f"{bd['first_decode_s'] * 1e3:.1f}, "
              f"migrations={r.ledger.migrations}")
    stats = router.stats()
    print(f"[serve/router] migrations={router.migrations} "
          f"({stats['migration_bytes'] / 1e3:.1f} kB packed KV over "
          f"{roles.link}), ttft p50={stats['ttft_p50_s'] * 1e3:.1f}ms "
          f"p95={stats['ttft_p95_s'] * 1e3:.1f}ms")
    if router.migrations:
        from repro.core.roofline.report import (MIGRATION_HEADER,
                                                migration_row, text_table)
        t = cluster.roofline_terms()
        print(f"[serve/router] migration roofline on {chip.name}:")
        print(text_table([migration_row("fleet decode", t)],
                         MIGRATION_HEADER))
    cap = capacity_report(cluster)
    per = ", ".join(
        f"r{r['replica']}({r['role']}) {r['pages_peak']}pk"
        f"/{r['pages_in_use']}use" if r["live"] else
        f"r{r['replica']}({r['role']}) idle" for r in cap["replicas"])
    print(f"[serve/capacity] fleet pages peak={cap['pages_peak']}"
          f"/{cap['pages_total']}, per-replica [{per}], cluster B_max="
          f"{cap['capacity_max_batch']} on {chip.name}")
    _export_telemetry(args, cluster.obs, cluster)
    first = min(done, key=lambda r: r.request_id)
    print("[serve] first sequence:", first.generated[:16])


if __name__ == "__main__":
    main()
