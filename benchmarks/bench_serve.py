"""Continuous-batching serve benchmark: measured tokens/s against the
memory-bound roofline ceiling, with an optional speculative-decoding pass.

Decode is the most memory-bound workload in the system: every generated
token re-reads the active weights plus the request's KV line, so the
per-token arithmetic intensity sits far left of the ridge point and the
attainable ceiling is ``beta * I`` (paper eq. 1).  This benchmark drives
the paged continuous-batching engine end to end and reports, per run:

* measured decode throughput (tokens/s) and per-request latency (mean
  TTFT, pooled inter-token p50/p95),
* the analytic bytes/token -> the memory-bound ceiling tokens/s for the
  target chip,
* the roofline fraction (measured / ceiling) on the *host* roofline
  (microbench-calibrated), and the per-request bound class / arithmetic
  intensity from the engine's roofline ledger,
* with ``--spec``: measured acceptance rate, tokens per weight pass, the
  ledger arithmetic intensity against the one-token-per-pass baseline,
  and the predicted memory-bound speedup (serve.spec.spec_speedup_model).

``--shared-prefix`` switches to the block-pool capacity workload: every
request shares one long system prompt and adds a short unique tail.  With
``--prefix-cache`` the pool's content-hash index aliases the shared pages
(copy-on-write guards divergence), so N requests admit into a pool sized
for ~1 copy of the prefix; the run reports peak pages, pages
deduplicated, copy-on-write copies, and preemption count next to
tokens/s.

``--smoke`` (the CI run) benches the baseline engine, an n-gram
speculative pass over self-repetitive prompts (asserting the speculative
ledger intensity is strictly above the baseline's), AND a shared-prefix
pair — prefix cache off vs on — asserting the cached run peaks at fewer
pages while emitting byte-identical greedy tokens: the memory-capacity
claim the block pool exists to cash in.

    PYTHONPATH=src python -m benchmarks.bench_serve --arch qwen3-0.6b \
        --requests 8 --slots 4 --new-tokens 16
    PYTHONPATH=src python -m benchmarks.bench_serve --spec ngram
    PYTHONPATH=src python -m benchmarks.bench_serve --shared-prefix \
        --prefix-cache
    PYTHONPATH=src python -m benchmarks.bench_serve --smoke   # CI-sized
    PYTHONPATH=src python -m benchmarks.run --only serve --smoke
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config, smoke
from repro.core.roofline.hardware import HOST_CPU_FALLBACK, TPU_V5E
from repro.models import init_params
from repro.serve import (EngineConfig, GenerateConfig, SpecConfig,
                         make_engine, parse_mesh, tp_sharding_error)
from repro.serve.crosscheck import capacity_report, crosscheck_collectives
from repro.serve.scheduler import decode_token_bytes
from repro.serve.spec import speculative_summary

from .common import emit


def _prompts(cfg, requests: int, prompt_len: int, repetitive: bool,
             shared_prefix: bool = False):
    """Random prompts; self-repetitive ones (a short motif tiled to
    length) — the prompt-lookup proposer's honest demo workload; or
    shared-prefix ones (one long system prompt + short unique tails) —
    the prefix-dedup capacity workload."""
    rng = jax.random.key(1)
    shared = np.asarray(jax.random.randint(
        jax.random.key(2), (prompt_len,), 0, cfg.vocab_size), np.int32)
    out = []
    for i in range(requests):
        if shared_prefix:
            tail = np.asarray(jax.random.randint(
                jax.random.fold_in(rng, i), (max(prompt_len // 4, 2),), 0,
                cfg.vocab_size), np.int32)
            p = np.concatenate([shared, tail])
        elif repetitive:
            motif = np.asarray(jax.random.randint(
                jax.random.fold_in(rng, i), (max(prompt_len // 4, 2),), 0,
                cfg.vocab_size))
            p = np.tile(motif, prompt_len // motif.shape[0] + 1)[:prompt_len]
        else:
            p = np.asarray(jax.random.randint(
                jax.random.fold_in(rng, i), (prompt_len,), 0,
                cfg.vocab_size))
        out.append(p.astype(np.int32))
    return out


def run_bench(arch: str, *, requests: int, slots: int, page_size: int,
              prompt_len: int, new_tokens: int, prefill_chunk: int,
              chip_name: str, backend: str = None, spec: str = "none",
              spec_k: int = 4, draft_arch: str = "qwen3-0.6b",
              spec_k_adaptive: bool = False, shared_prefix: bool = False,
              prefix_cache: bool = False, num_pages: int = 0,
              watermark: float = 0.0, preempt: str = "swap",
              warmup: bool = True, mesh=(1, 1), pipeline: str = "off",
              overlap: str = "none", kv_dtype: str = None,
              telemetry: bool = False) -> dict:
    cfg = smoke(get_config(arch))
    params = init_params(cfg, jax.random.key(0))
    chip = TPU_V5E if chip_name == "tpu_v5e" else HOST_CPU_FALLBACK
    # shared-prefix prompts carry a unique tail past the shared system
    # prompt, so the context ceiling must cover prompt + tail + new tokens
    tail = max(prompt_len // 4, 2) if shared_prefix else 0
    ecfg = EngineConfig(num_slots=slots, page_size=page_size,
                        max_len=prompt_len + tail + new_tokens,
                        prefill_chunk=prefill_chunk, chip=chip,
                        kernel_backend=backend, prefix_cache=prefix_cache,
                        num_pages=num_pages or None, watermark=watermark,
                        preempt_mode=preempt, pipeline=pipeline,
                        overlap=overlap, kv_dtype=kv_dtype,
                        telemetry=telemetry)
    scfg = None
    if spec != "none":
        if spec == "draft":
            dcfg = smoke(get_config(draft_arch))
            scfg = SpecConfig(k=spec_k, proposer="draft", draft_cfg=dcfg,
                              draft_params=init_params(dcfg,
                                                       jax.random.key(4)),
                              adaptive=spec_k_adaptive)
        else:
            scfg = SpecConfig(k=spec_k, proposer="ngram",
                              adaptive=spec_k_adaptive)
    engine = make_engine(cfg, params, ecfg, scfg, mesh_shape=mesh)

    prompts = _prompts(cfg, requests, prompt_len, repetitive=spec != "none",
                       shared_prefix=shared_prefix)
    gen = GenerateConfig(max_new_tokens=new_tokens)
    if warmup:
        # warm the decode/prefill compile caches with one throwaway pass
        # (skipped for capacity runs: pool stats must reflect one pass)
        for p in prompts:
            engine.submit(p, gen)
        engine.run()
    for p in prompts:
        engine.submit(p, gen)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0

    n_tokens = sum(r.ledger.decode_tokens + 1 for r in done)
    tps = n_tokens / dt
    mean_batch = float(np.mean([r.ledger.mean_batch for r in done]))
    # the engine's cfg carries any EngineConfig.kv_dtype override, so the
    # ceiling prices the quantized KV line when one is active
    bytes_tok = decode_token_bytes(getattr(engine, "cfg", cfg),
                                   prompt_len + new_tokens // 2,
                                   max(int(round(mean_batch)), 1))
    ceiling_tps = chip.hbm_bw / bytes_tok
    ledgers = [engine.roofline_terms(r) for r in done]
    ai = float(np.mean([t.arithmetic_intensity for t in ledgers]))
    bound = ledgers[0].bound_class()
    frac = tps / ceiling_tps
    ttft = float(np.mean([r.ttft for r in done]))
    gaps = np.concatenate(
        [np.diff(np.asarray(r.token_times))
         for r in done if len(r.token_times) > 1] or [np.zeros((0,))])
    itl_p50 = float(np.percentile(gaps, 50)) if gaps.size else float("nan")
    itl_p95 = float(np.percentile(gaps, 95)) if gaps.size else float("nan")
    cap = capacity_report(engine)
    tp = mesh[1]
    ici_dev = float(np.mean([t.ici_wire_bytes_dev for t in ledgers]))
    out = {"tp": tp, "ici_bytes_dev": ici_dev,
           "binding_roof": ledgers[0].binding_roof,
           "collective_crosscheck": (crosscheck_collectives(engine)
                                     if tp > 1 else None),
           "wall_s": dt,
           "tokens_per_s": tps, "ceiling_tokens_per_s": ceiling_tps,
           "roofline_fraction": frac, "arithmetic_intensity": ai,
           "bound_class": bound, "requests": len(done),
           "ttft_s": ttft, "itl_p50_s": itl_p50, "itl_p95_s": itl_p95,
           "pages_peak": cap["pages_peak"],
           "pages_deduped": cap["pages_deduped"],
           "cow_copies": cap["cow_copies"],
           "preemptions": cap["preemptions"],
           "capacity_max_batch": cap["capacity_max_batch"],
           "generated": [list(r.generated) for r in
                         sorted(done, key=lambda r: r.request_id)],
           "engine": engine, "done": done}
    derived = (f"tok/s={tps:.1f};ceiling={ceiling_tps:.0f};"
               f"frac={frac:.4f};AI={ai:.2f};{bound};"
               f"mean_batch={mean_batch:.2f};ttft_ms={ttft * 1e3:.1f};"
               f"itl_p50_ms={itl_p50 * 1e3:.2f};"
               f"itl_p95_ms={itl_p95 * 1e3:.2f}")
    name = f"serve_{arch}_b{slots}"
    if kv_dtype:
        name += f"_{kv_dtype}"
    if tp > 1:
        name += f"_tp{tp}"
        derived += (f";tp={tp};ici_B={ici_dev:.0f};"
                    f"I_ici={ledgers[0].ici_intensity:.1f};"
                    f"binds={out['binding_roof']}")
    if shared_prefix:
        name += "_shared" + ("_cached" if prefix_cache else "")
        derived += (f";pages_peak={cap['pages_peak']};"
                    f"deduped={cap['pages_deduped']};"
                    f"cow={cap['cow_copies']};"
                    f"preempt={cap['preemptions']}")
    if spec != "none":
        out.update(speculative_summary(cfg, done, spec_k,
                                       prompt_len + new_tokens // 2,
                                       draft_cfg=scfg.draft_cfg))
        name = (f"serve_{arch}_b{slots}"
                + (f"_tp{tp}" if tp > 1 else "")
                + f"_spec_{spec}{spec_k}")
        derived += (f";accept={out['acceptance_rate']:.2f};"
                    f"tok_per_pass={out['tokens_per_pass']:.2f};"
                    f"pred_speedup={out['predicted_speedup']:.2f}")
    emit(name, dt / max(n_tokens, 1) * 1e6, derived)
    return out


def run_hierarchy(arch: str, *, page_size: int = 8, new_tokens: int = 24,
                  prompt_len: int = 6, windows: int = 3, retries: int = 2,
                  ratio_tol: float = 0.15, residual_tol: float = 0.25,
                  ) -> dict:
    """The ``--hierarchy`` leg: drive one steady-state decode workload,
    decompose its measured step time against microbench-calibrated
    per-level betas, and assert the hierarchical ledger holds water.

    Protocol (every term measured, nothing fitted):

    * *steady window* — submit ``slots`` requests, one step() prefills
      them all and commits the first tokens, reset_phases(), then run():
      the timed window holds only saturated decode steps.
    * *dispatch* — the no-kernel twin engine (paper §2.4: same op graph,
      kernel work floored) driven through the SAME steady windows; its
      per-step fenced wall is the framework floor.
    * *compute / HBM rows* — the REAL compiled step's own cost model
      (crosscheck.step_cost_analysis) divided by a sustained-matmul
      probe at the decode operating shape and the microbench triad beta.
    * *noise* — real and no-kernel windows interleave ``windows`` times;
      the minimum per-step wall of each side is used (OS noise is
      strictly additive; min is the standard latency estimator).  When
      the residual check misses anyway (a noisy shared container can
      inflate every window of one side), the TIMED part re-measures up
      to ``retries`` more times with one extra window each, printing the
      per-window raw walls of the rejected attempt; the analytic ratio
      checks never retry — they are deterministic.

    Asserts (a) every cross-checkable level's ledger/artifact ratio is
    within ``ratio_tol`` (HBM + flops vs compiled HLO, VMEM vs the
    Pallas BlockSpec walk, host vs the compiled swap-pack footprint) and
    (b) the time-attribution residual — the fraction of measured step
    wall the budget fails to explain — is within ``residual_tol``."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from repro.core.roofline.microbench import run_microbench
    from repro.serve.crosscheck import (crosscheck_decode, crosscheck_host,
                                        crosscheck_vmem, step_cost_analysis)
    from repro.serve.engine import Engine

    cfg = smoke(get_config(arch))
    params = init_params(cfg, jax.random.key(0))
    slots = 2
    ecfg = EngineConfig(num_slots=slots, page_size=page_size,
                        max_len=prompt_len + new_tokens + page_size)
    eng = Engine(cfg, params, ecfg)
    nk_cfg = eng._no_kernel_cfg()
    nk = Engine(nk_cfg, init_params(nk_cfg, jax.random.key(0)), ecfg)
    prompts = _prompts(cfg, slots, prompt_len, repetitive=False)
    gen = GenerateConfig(max_new_tokens=new_tokens)

    def steady(e, ps):
        done = []
        for p in ps:
            e.submit(p % e.cfg.vocab_size, gen)
        e.step()                      # prefill all slots + first tokens
        e.reset_phases()              # timed window: pure decode steps
        done = e.run()
        ph = e.phases["decode"]
        return ph.wall_s / max(ph.steps, 1), ph, done

    steady(eng, prompts)              # compile warm-up, both engines
    steady(nk, prompts)

    mb = run_microbench(quick=True)
    betas = mb.level_betas()
    # sustained-matmul probe at the decode operating shape: the average
    # rate of 16 independent (slots, d) @ (d, d) dots in ONE jit — what
    # this platform actually achieves on the step's own projections,
    # amortized over a chain exactly like the compiled layer stack
    m, d = slots, cfg.d_model
    x = jnp.zeros((m, d), jnp.float32)
    w = jnp.zeros((d, d), jnp.float32)
    n_dots = 16
    probe = jax.jit(lambda x, w: [x @ (w + i) for i in range(n_dots)])
    jax.block_until_ready(probe(x, w))
    samples = []
    for _ in range(30):
        t0 = time.perf_counter()
        jax.block_until_ready(probe(x, w))
        samples.append(time.perf_counter() - t0)
    pi_sust = n_dots * 2 * m * d * d / float(np.median(samples))
    betas = _dc.replace(betas, pi=pi_sust, source=betas.source + "+sustained")

    cost = step_cost_analysis(eng)    # the REAL fused step's own counters

    def timed_windows(n):
        walls, disps, vmem_steps, done = [], [], [], None
        for _ in range(n):            # interleaved: noise hits both sides
            rw, rph, done = steady(eng, prompts)
            dw, _, _ = steady(nk, prompts)
            walls.append(rw)
            disps.append(dw)
            vmem_steps.append(rph.vmem / max(rph.steps, 1))
        return walls, disps, vmem_steps, done

    t_comp = cost["flops"] / pi_sust
    t_hbm = cost["bytes"] / betas.hbm
    for attempt in range(retries + 1):
        walls, disps, vmem_steps, done = timed_windows(windows + attempt)
        wall, disp = min(walls), min(disps)
        t_vmem = vmem_steps[0] / betas.vmem
        explained = disp + t_comp + t_hbm + t_vmem
        residual = (wall - explained) / wall
        if abs(residual) <= residual_tol or attempt == retries:
            break
        print(f"[bench_serve/hierarchy] residual {residual:+.1%} outside "
              f"+-{residual_tol:.0%} on attempt {attempt + 1}; raw "
              f"per-window walls us: real="
              f"{['%.0f' % (w * 1e6) for w in walls]} nokernel="
              f"{['%.0f' % (w * 1e6) for w in disps]}; re-measuring with "
              f"{windows + attempt + 1} windows")

    cd = crosscheck_decode(eng, requests=done)
    cv = crosscheck_vmem(eng, requests=done)
    ch = crosscheck_host(eng)
    ratios = {"hbm": cd["bytes_ratio"], "flops": cd["flops_ratio"],
              "vmem": cv["vmem_ratio"], "host": ch["host_ratio"]}

    eng._dispatch_s = disp            # the report's dispatch row
    print(eng.hierarchy_report(betas=betas))
    print(f"[bench_serve/hierarchy] wall/step {wall * 1e6:.0f}us = "
          f"dispatch {disp * 1e6:.0f} + compute {t_comp * 1e6:.0f} + "
          f"hbm {t_hbm * 1e6:.0f} + vmem {t_vmem * 1e6:.0f} us "
          f"(residual {residual:+.1%}); crosscheck ratios " +
          " ".join(f"{k}={v:.3f}" for k, v in ratios.items()))
    emit(f"serve_hierarchy_{arch}", wall * 1e6,
         f"residual={residual:+.3f};" +
         ";".join(f"{k}_ratio={v:.3f}" for k, v in ratios.items()))

    for k, v in ratios.items():
        if abs(v - 1.0) > ratio_tol:
            raise RuntimeError(
                f"hierarchy crosscheck: {k} ledger/artifact ratio {v:.3f} "
                f"is outside 1 +- {ratio_tol}")
    if abs(residual) > residual_tol:
        raise RuntimeError(
            f"time-attribution residual {residual:+.1%} exceeds "
            f"+-{residual_tol:.0%} after {retries + 1} attempts: the "
            f"per-level budget does not explain the measured step wall "
            f"({wall * 1e6:.0f}us vs {explained * 1e6:.0f}us explained; "
            f"raw per-window walls us: real="
            f"{['%.0f' % (w * 1e6) for w in walls]} nokernel="
            f"{['%.0f' % (w * 1e6) for w in disps]})")
    return {"wall_s": wall, "dispatch_s": disp, "compute_s": t_comp,
            "hbm_s": t_hbm, "vmem_s": t_vmem, "residual": residual,
            "ratios": ratios, "pi_sustained": pi_sust,
            "betas_source": betas.source}


def run_mesh_compare(args, mesh, kwargs) -> None:
    """The --mesh leg (CI: forced-8-device smoke): run the single-device
    baseline and the tensor-parallel engine over the same prompts, then
    assert the sharding seam holds — byte-identical greedy outputs, a
    ledger that charges nonzero collective bytes, and ledger/HLO
    agreement on those bytes within 15% (the acceptance bar of the
    communication roofline; serve/crosscheck.crosscheck_collectives).
    The full workload surface forwards — spec / shared-prefix /
    prefix-cache / pool-pressure flags shape both legs identically."""
    kwargs = dict(kwargs, spec=args.spec,
                  shared_prefix=args.shared_prefix,
                  prefix_cache=args.prefix_cache,
                  num_pages=args.num_pages, watermark=args.watermark,
                  preempt=args.preempt, warmup=not args.shared_prefix,
                  pipeline=args.pipeline, overlap=args.overlap)
    base = run_bench(args.arch, mesh=(1, 1), **kwargs)
    if mesh[1] <= 1:
        # a 1x1 "mesh" IS the baseline (ShardedEngine wraps nothing):
        # there is no second engine to compare and no wire to crosscheck
        if base["ici_bytes_dev"] != 0:
            raise RuntimeError("1x1 ledger charged collective bytes")
        print("[bench_serve/mesh] tp=1: nothing sharded — the 1x1 mesh "
              "is the single-device engine byte-for-byte")
        return
    shrd = run_bench(args.arch, mesh=mesh, **kwargs)
    cc = shrd["collective_crosscheck"]
    print(f"[bench_serve/mesh] tp={mesh[1]}: "
          f"{shrd['tokens_per_s']:.1f} tok/s, "
          f"ici_bytes/dev={shrd['ici_bytes_dev']:.0f}, "
          f"binding roof={shrd['binding_roof']}, collective crosscheck "
          f"analytic={cc['analytic_ici_bytes']:.0f}B vs "
          f"hlo={cc['hlo_ici_bytes']:.0f}B "
          f"(ratio {cc['ici_ratio']:.3f}, {cc['by_kind']})")
    if shrd["generated"] != base["generated"]:
        raise RuntimeError(
            f"sharded greedy outputs diverged from single-device at "
            f"mesh {mesh}: {shrd['generated']} vs {base['generated']}")
    if not shrd["ici_bytes_dev"] > 0:
        raise RuntimeError("sharded ledger charged no collective bytes")
    if not 1 / 1.15 <= cc["ici_ratio"] <= 1.15:
        raise RuntimeError(
            "ledger collective bytes disagree with the HLO crosscheck "
            f"beyond 15%: ratio {cc['ici_ratio']:.3f}")
    if base["ici_bytes_dev"] != 0:
        raise RuntimeError("single-device ledger charged collective bytes")


def _run_router_bench(args, dp: int, tp: int, roles, kwargs,
                      telemetry: bool = False) -> dict:
    """One router-driven pass over the standard smoke prompts: build a
    Cluster + Router at the given roles, serve everything, and return
    outputs + migration/TTFT accounting in baseline-comparable form."""
    from repro.serve import Cluster, Router

    cfg = smoke(get_config(args.arch))
    params = init_params(cfg, jax.random.key(0))
    chip = TPU_V5E if kwargs["chip_name"] == "tpu_v5e" else HOST_CPU_FALLBACK
    ecfg = EngineConfig(num_slots=kwargs["slots"],
                        page_size=kwargs["page_size"],
                        max_len=kwargs["prompt_len"] + kwargs["new_tokens"],
                        prefill_chunk=kwargs["prefill_chunk"], chip=chip,
                        kernel_backend=kwargs["backend"],
                        prefix_cache=args.prefix_cache,
                        num_pages=args.num_pages or None,
                        watermark=args.watermark,
                        preempt_mode=args.preempt,
                        telemetry=telemetry)
    cluster = Cluster(cfg, params, ecfg, mesh_shape=(dp, tp), roles=roles)
    router = Router(cluster)
    prompts = _prompts(cfg, kwargs["requests"], kwargs["prompt_len"],
                       repetitive=False)
    gen = GenerateConfig(max_new_tokens=kwargs["new_tokens"])
    reqs = [router.submit(p, gen) for p in prompts]
    t0 = time.perf_counter()
    done = router.run()
    dt = time.perf_counter() - t0
    n_tokens = sum(len(r.generated) for r in done)
    led = cluster.aggregate_ledger()
    cap = capacity_report(cluster)
    out = {
        "generated": [list(r.generated) for r in
                      sorted(done, key=lambda r: r.request_id)],
        "requests": reqs, "done": done, "cluster": cluster,
        "router": router, "ledger": led, "cfg": cfg, "ecfg": ecfg,
        "tokens_per_s": n_tokens / dt,
        "migrations": led.migrations,
        "migration_bytes": led.migration_bytes,
        "migration_pages": led.migration_pages,
        "pages_peak": cap["pages_peak"],
        "capacity_max_batch": cap["capacity_max_batch"],
    }
    tag = "disagg" if "prefill" in roles.roles else "mixed"
    emit(f"serve_router_{args.arch}_dp{dp}_{tag}",
         dt / max(n_tokens, 1) * 1e6,
         f"tok/s={out['tokens_per_s']:.1f};migrations={led.migrations};"
         f"mig_kB={led.migration_bytes / 1e3:.1f};"
         f"colocated={int(cluster.colocated)}")
    return out


def run_router_compare(args, mesh, kwargs) -> None:
    """The --router leg (CI: one-device colocated AND forced-8-device
    dp=2): serve the same prompts through (a) the single engine, (b) a
    mixed-role dp-replica cluster, (c) a disaggregated prefill/decode
    cluster with KV-page migration — asserting the serving tier's
    acceptance bars:

    * greedy outputs byte-identical across all three paths,
    * the disaggregated run migrates every request and its ledger
      charges nonzero wire bytes on the RoleConfig link,
    * analytic migration bytes (scheduler.slot_swap_bytes applied to the
      migrated pages) within 15% of the measured packed-snapshot sizes,
    * TTFT telescopes exactly into queue + prefill + first-decode,
    * the roofline can NAME migration: a synthetic migration-heavy
      variant of the fleet terms binds on the "migration" roof."""
    import dataclasses as _dc

    from repro.serve import RoleConfig
    from repro.serve.scheduler import kv_line_bytes, state_bytes

    kw = dict(kwargs, warmup=False)
    base = run_bench(args.arch, mesh=(1, 1), **kw)
    dp = max(mesh[0], 2)
    mixed = _run_router_bench(args, dp, mesh[1], RoleConfig.mixed(dp),
                              kwargs)
    n_pf = max(dp // 2, 1)
    disagg = _run_router_bench(
        args, dp, mesh[1],
        RoleConfig.disaggregated(n_pf, dp - n_pf), kwargs)
    for tag, out in (("mixed", mixed), ("disagg", disagg)):
        if out["generated"] != base["generated"]:
            raise RuntimeError(
                f"router {tag} greedy outputs diverged from the single "
                f"engine: {out['generated']} vs {base['generated']}")
    if mixed["migrations"] != 0:
        raise RuntimeError("mixed-role cluster migrated on the happy "
                           f"path: {mixed['migrations']} moves")
    if not (disagg["migrations"] >= len(disagg["done"])
            and disagg["migration_bytes"] > 0):
        raise RuntimeError(
            "disaggregated run did not migrate every request: "
            f"{disagg['migrations']} moves, "
            f"{disagg['migration_bytes']:.0f}B")
    cfg = disagg["cfg"]
    analytic = (disagg["migration_pages"] * args.page_size
                * kv_line_bytes(cfg)
                + disagg["migrations"] * state_bytes(cfg))
    ratio = analytic / disagg["migration_bytes"]
    if not 1 / 1.15 <= ratio <= 1.15:
        raise RuntimeError(
            "analytic migration bytes disagree with the measured packed "
            f"snapshots beyond 15%: {analytic:.0f}B vs "
            f"{disagg['migration_bytes']:.0f}B (ratio {ratio:.3f})")
    for r in disagg["done"]:
        bd = r.ttft_breakdown()
        resid = abs(sum(bd.values()) - r.ttft)
        if not resid < 1e-6:
            raise RuntimeError(
                f"req {r.request_id}: TTFT breakdown does not telescope "
                f"(residual {resid:.2e}s): {bd} vs ttft {r.ttft:.6f}")
    t = disagg["cluster"].roofline_terms()
    if t.migration_bytes_dev <= 0 or "migration" not in t.roofs():
        raise RuntimeError("fleet terms carry no migration roof despite "
                           f"{disagg['migrations']} migrations")
    # synthetic migration-heavy workload: same fleet terms, snapshots
    # scaled until the wire can no longer hide behind HBM — the binding
    # roof must NAME migration (the disaggregation-cost early warning)
    heavy_bytes = (10.0 * t.flops_dev * t.chip.level_bw(t.migration_link)
                   / min(t.roofs().values()))
    heavy = _dc.replace(t, migration_bytes_dev=heavy_bytes,
                        dcn_wire_bytes_dev=(
                            t.dcn_wire_bytes_dev
                            - t.migration_bytes_dev + heavy_bytes))
    if heavy.binding_roof != "migration":
        raise RuntimeError(
            "synthetic migration-heavy terms bind on "
            f"{heavy.binding_roof!r}, not 'migration' "
            f"(roofs: {heavy.roofs()})")
    print(f"[bench_serve/router] dp={dp} tp={mesh[1]} "
          f"({'colocated' if disagg['cluster'].colocated else 'sub-mesh'}"
          f" replicas): mixed {mixed['tokens_per_s']:.1f} tok/s, disagg "
          f"{disagg['tokens_per_s']:.1f} tok/s, "
          f"{disagg['migrations']} migrations "
          f"({disagg['migration_bytes'] / 1e3:.1f} kB packed KV, analytic"
          f"/measured {ratio:.3f}); outputs byte-identical, TTFT "
          "telescopes, synthetic heavy workload binds on 'migration'")


def run_kv_dtype_compare(args, mesh, kwargs) -> None:
    """The ``--kv-dtype`` leg (CI: ``--smoke --kv-dtype int8``, 1-device
    and forced-8-device ``--mesh 1,2``): bf16 baseline vs quantized KV
    pool over the same prompts, asserting the tentpole acceptance bars of
    the quantized page walk:

    * the quantized run's ledger arithmetic intensity is strictly above
      the bf16 baseline's (decode is memory-bound, so shrinking the KV
      line is a direct AI multiplier: I' ~= I * line/line_q),
    * the Pallas engine's greedy outputs are byte-identical to the
      identically-quantized jnp oracle (kernels quantize/dequantize with
      the exact op sequence of the reference, so this is exact — no
      tolerance),
    * the analytic decode ledger agrees with the compiled-HLO byte count
      within 15% (serve.crosscheck.crosscheck_decode) at the quantized
      line size,
    * with tp > 1: the sharded quantized engine emits the same tokens
      and its ledger/HLO collective crosscheck holds within 15%."""
    from repro.serve.crosscheck import crosscheck_decode

    kw = dict(kwargs, warmup=False)
    base = run_bench(args.arch, mesh=(1, 1), **kw)
    quant = run_bench(args.arch, mesh=(1, 1), kv_dtype=args.kv_dtype,
                      **dict(kw, backend="pallas"))
    oracle = run_bench(args.arch, mesh=(1, 1), kv_dtype=args.kv_dtype,
                       **dict(kw, backend="jnp"))
    cd = crosscheck_decode(quant["engine"], requests=quant["done"])
    print(f"[bench_serve/kv_dtype] {args.kv_dtype}: "
          f"AI={quant['arithmetic_intensity']:.2f} vs bf16 "
          f"{base['arithmetic_intensity']:.2f}, ledger/HLO bytes ratio "
          f"{cd['bytes_ratio']:.3f}, B_max "
          f"{quant['capacity_max_batch']} vs {base['capacity_max_batch']}")
    if quant["generated"] != oracle["generated"]:
        raise RuntimeError(
            f"{args.kv_dtype} Pallas engine outputs diverged from the "
            f"identically-quantized jnp oracle: {quant['generated']} vs "
            f"{oracle['generated']}")
    if not quant["arithmetic_intensity"] > base["arithmetic_intensity"]:
        raise RuntimeError(
            f"quantized ledger intensity did not exceed the bf16 "
            f"baseline: {quant['arithmetic_intensity']} <= "
            f"{base['arithmetic_intensity']}")
    if abs(cd["bytes_ratio"] - 1.0) > 0.15:
        raise RuntimeError(
            "quantized decode ledger disagrees with the HLO byte count "
            f"beyond 15%: ratio {cd['bytes_ratio']:.3f}")
    if mesh[1] > 1:
        shrd = run_bench(args.arch, mesh=mesh, kv_dtype=args.kv_dtype,
                         **kw)
        cc = shrd["collective_crosscheck"]
        print(f"[bench_serve/kv_dtype] tp={mesh[1]}: collective "
              f"crosscheck ratio {cc['ici_ratio']:.3f}")
        if shrd["generated"] != quant["generated"]:
            raise RuntimeError(
                f"sharded {args.kv_dtype} outputs diverged from the "
                f"single-device quantized engine: {shrd['generated']} vs "
                f"{quant['generated']}")
        if not 1 / 1.15 <= cc["ici_ratio"] <= 1.15:
            raise RuntimeError(
                "sharded quantized ledger collective bytes disagree with "
                f"the HLO crosscheck beyond 15%: {cc['ici_ratio']:.3f}")


def run_overlap_compare(args, mesh) -> dict:
    """The ``--smoke --overlap``/``--pipeline`` leg (CI): serial engine
    vs overlapped twin at the same mesh, through the fenced steady-state
    protocol of serve.crosscheck.crosscheck_overlap.

    The serial side runs pipeline="off"/overlap="none"; the overlapped
    side runs whatever ``--pipeline``/``--overlap`` selected (bare
    ``--overlap`` means ring collectives, bare ``--pipeline`` the
    double-buffered page walk).  The crosscheck asserts byte-identical
    greedy output, no overlapped-level time-term growth, and an
    overlapped steady-state wall no worse than the serial wall within
    noise (``wall_tol``); the measured delta comes back attributed as an
    inferred per-level overlap fraction."""
    from repro.serve.crosscheck import crosscheck_overlap

    cfg = smoke(get_config(args.arch))
    params = init_params(cfg, jax.random.key(0))
    kw = dict(num_slots=args.slots, page_size=args.page_size,
              max_len=args.prompt_len + args.new_tokens + args.page_size,
              prefill_chunk=args.prefill_chunk,
              kernel_backend=args.backend)
    e_off = make_engine(cfg, params, EngineConfig(**kw), mesh_shape=mesh)
    e_on = make_engine(cfg, params,
                       EngineConfig(**kw, pipeline=args.pipeline,
                                    overlap=args.overlap), mesh_shape=mesh)
    prompts = _prompts(cfg, args.slots, args.prompt_len, repetitive=False)
    gen = GenerateConfig(max_new_tokens=args.new_tokens)
    res = crosscheck_overlap(e_off, e_on, prompts, gen)
    ov = ";".join(f"ov_{k}={v:.2f}" for k, v in
                  res["inferred_overlap"].items()) or "ov=none"
    print(f"[bench_serve/overlap] mesh {mesh} pipeline={args.pipeline} "
          f"overlap={args.overlap}: wall/step "
          f"{res['wall_on_s'] * 1e6:.0f}us (serial "
          f"{res['wall_off_s'] * 1e6:.0f}us), levels={res['levels']}, "
          f"{ov}, serial budget {res['serial_budget_s'] * 1e3:.2f}ms vs "
          f"overlapped bound {res['overlapped_budget_s'] * 1e3:.2f}ms; "
          "greedy outputs byte-identical")
    emit(f"serve_overlap_{args.arch}_tp{mesh[1]}",
         res["wall_on_s"] * 1e6,
         f"wall_off_us={res['wall_off_s'] * 1e6:.0f};"
         f"pipeline={args.pipeline};overlap={args.overlap};{ov}")
    return res


def run_trace_smoke(args, kwargs) -> dict:
    """The ``--smoke --trace`` leg (CI): telemetry's acceptance bars.

    * observation-only — the traced single engine and the traced
      disaggregated router emit greedy token streams byte-identical to
      their untraced twins,
    * cheap — the traced single-engine wall stays within 1.25x of the
      untraced wall (both sides re-measure up to ``retries`` times;
      container noise hits 8-token smoke walls hard),
    * loadable — the exported trace passes ``validate_trace`` (well-
      formed events, call-stack span nesting per track, named tracks,
      balanced async pairs, paired flow arrows) and contains prefill,
      decode and migration spans,
    * live roofline — the metrics snapshot names per-level attainment
      AND the binding roof (serve_roofline_attainment/_binding).

    Writes the trace JSON to ``args.trace`` and the Prometheus snapshot
    next to it (``.prom``)."""
    import os

    from repro.obs.trace import validate_trace
    from repro.serve import RoleConfig

    retries = 3
    kw = dict(kwargs, warmup=True)
    base = run_bench(args.arch, **kw)
    for attempt in range(retries):
        traced = run_bench(args.arch, telemetry=True, **kw)
        if traced["generated"] != base["generated"]:
            raise RuntimeError(
                "telemetry changed the single-engine greedy outputs: "
                f"{traced['generated']} vs {base['generated']}")
        ratio = traced["wall_s"] / base["wall_s"]
        if ratio <= 1.25:
            break
        if attempt < retries - 1:
            print(f"[bench_serve/trace] overhead ratio {ratio:.2f} > 1.25 "
                  f"on attempt {attempt + 1} (traced "
                  f"{traced['wall_s'] * 1e3:.1f}ms vs "
                  f"{base['wall_s'] * 1e3:.1f}ms); re-measuring both sides")
            base = run_bench(args.arch, **kw)
    if ratio > 1.25:
        raise RuntimeError(
            f"tracing is not observation-cheap: traced wall "
            f"{traced['wall_s'] * 1e3:.1f}ms is {ratio:.2f}x the untraced "
            f"{base['wall_s'] * 1e3:.1f}ms after {retries} attempts")

    # the disaggregated pair: byte-identity under migration, and the
    # exported fleet trace is the one CI archives + validates
    roles = RoleConfig.disaggregated(1, 1)
    plain = _run_router_bench(args, 2, 1, roles, kwargs)
    routed = _run_router_bench(args, 2, 1, roles, kwargs, telemetry=True)
    if routed["generated"] != plain["generated"]:
        raise RuntimeError(
            "telemetry changed the routed greedy outputs: "
            f"{routed['generated']} vs {plain['generated']}")
    obs = routed["cluster"].obs
    obs.harvest(routed["cluster"])
    trace_path = args.trace
    os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
    doc = obs.export_trace(trace_path)
    errors = validate_trace(doc)
    if errors:
        raise RuntimeError(
            f"exported trace fails validation ({len(errors)} errors): "
            + "; ".join(errors[:5]))
    names = {ev.get("name") for ev in doc["traceEvents"]}
    missing = {"engine.prefill", "engine.decode", "migrate_in"} - names
    if missing:
        raise RuntimeError(
            f"trace is missing required span names {sorted(missing)}; "
            f"has {sorted(names)}")
    snap_path = os.path.splitext(trace_path)[0] + ".prom"
    snap = obs.snapshot(snap_path)
    for needle in ("serve_roofline_attainment", "serve_roofline_binding",
                   "serve_migrations_total"):
        if needle not in snap:
            raise RuntimeError(
                f"metrics snapshot is missing {needle!r} "
                f"({snap_path})")
    n_events = len(doc["traceEvents"])
    print(f"[bench_serve/trace] overhead x{ratio:.2f} (bar 1.25), outputs "
          f"byte-identical traced vs untraced (engine + disagg router); "
          f"trace {trace_path} ({n_events} events, validator clean), "
          f"snapshot {snap_path}")
    emit(f"serve_trace_{args.arch}", traced["wall_s"] * 1e6,
         f"overhead_x={ratio:.2f};events={n_events};"
         f"migrations={routed['migrations']}")
    return {"overhead_ratio": ratio, "trace": doc, "snapshot": snap,
            "trace_path": trace_path, "snapshot_path": snap_path}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS), default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--chip", choices=["host", "tpu_v5e"], default="host")
    ap.add_argument("--backend", choices=["auto", "pallas", "jnp"],
                    default=None,
                    help="paged-attention kernel backend (registry default"
                         " when omitted)")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8", "fp8_e4m3"],
                    default=None,
                    help="KV-page storage dtype (kernels/quantize.py); "
                         "with --smoke runs the bf16-vs-quantized "
                         "comparison leg (run_kv_dtype_compare)")
    ap.add_argument("--pipeline", nargs="?", const="double", default="off",
                    choices=["off", "double"],
                    help="double-buffer the Pallas page walk (bare flag = "
                         "'double'); with --smoke runs the serial-vs-"
                         "overlapped comparison leg (run_overlap_compare)")
    ap.add_argument("--overlap", nargs="?", const="ring", default="none",
                    choices=["none", "ring"],
                    help="overlap decode collectives as ring matmuls "
                         "(bare flag = 'ring'; tp > 1 meshes); with "
                         "--smoke runs the serial-vs-overlapped "
                         "comparison leg (run_overlap_compare)")
    ap.add_argument("--spec", choices=["none", "ngram", "draft"],
                    default="none",
                    help="speculative decoding proposer (serve/spec.py)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify round")
    ap.add_argument("--spec-k-adaptive", action="store_true",
                    help="EWMA acceptance tracking adapts drafted length")
    ap.add_argument("--draft-arch", default="qwen3-0.6b",
                    help="draft model arch for --spec draft")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="capacity workload: requests share one long "
                         "system prompt + short unique tails")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hash prefix sharing + copy-on-write")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="block-pool size incl. trash page (0 = fully "
                         "backed; smaller exercises preemption)")
    ap.add_argument("--watermark", type=float, default=0.0,
                    help="admission slack as a fraction of pool pages")
    ap.add_argument("--preempt", choices=["swap", "recompute"],
                    default="swap")
    ap.add_argument("--router", action="store_true",
                    help="multi-replica serving leg (serve/router.py): "
                         "single engine vs mixed-role cluster vs "
                         "disaggregated prefill/decode cluster with "
                         "KV-page migration, asserting byte-identical "
                         "outputs, ledger-vs-measured migration bytes "
                         "within 15%, a telescoping TTFT breakdown, and "
                         "a nameable 'migration' binding roof.  dp comes "
                         "from --mesh (default 2, colocated on one "
                         "device)")
    ap.add_argument("--mesh", default=None,
                    help="device mesh 'dp,tp' (serve/shard.py): runs the "
                         "tensor-parallel engine AND the single-device "
                         "baseline, asserting byte-identical greedy "
                         "output + ledger/HLO collective agreement "
                         "(forced-CPU meshes need XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--trace", nargs="?", const="results/serve_trace.json",
                    default=None, metavar="OUT.json",
                    help="with --smoke: the telemetry leg "
                         "(run_trace_smoke) — byte-identical traced vs "
                         "untraced streams, <=1.25x overhead, a validated "
                         "Chrome trace with prefill/decode/migration "
                         "spans, and a Prometheus snapshot naming the "
                         "binding roof (written next to OUT.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized defaults: 4 requests, 2 slots, 8 new "
                         "tokens; baseline + ngram speculative pass + "
                         "shared-prefix capacity pair (explicit flags "
                         "still win); with --mesh, the sharded-vs-single "
                         "comparison replaces those legs")
    ap.add_argument("--hierarchy", action="store_true",
                    help="hierarchical + time-based roofline leg: steady "
                         "decode window decomposed against measured "
                         "per-level betas, asserting every level's "
                         "ledger/artifact crosscheck ratio within 15% "
                         "and a time-attribution residual within 25% "
                         "(replaces the other smoke legs)")
    ap.add_argument("--windows", type=int, default=3,
                    help="--hierarchy: interleaved timed windows per "
                         "measurement attempt")
    ap.add_argument("--retries", type=int, default=2,
                    help="--hierarchy: extra re-measurements (one more "
                         "window each) before the residual check fails; "
                         "rejected attempts print per-window raw walls")
    args = ap.parse_args(argv)
    if args.hierarchy:
        run_hierarchy(args.arch, windows=args.windows,
                      retries=args.retries)
        return
    sizes = (dict(requests=4, slots=2, page_size=4, prompt_len=8,
                  new_tokens=8) if args.smoke else
             dict(requests=8, slots=4, page_size=16, prompt_len=16,
                  new_tokens=16))
    for k, v in sizes.items():
        if getattr(args, k) is None:
            setattr(args, k, v)
    kwargs = dict(requests=args.requests, slots=args.slots,
                  page_size=args.page_size, prompt_len=args.prompt_len,
                  new_tokens=args.new_tokens,
                  prefill_chunk=args.prefill_chunk,
                  chip_name="tpu_v5e" if args.chip == "tpu_v5e" else "host",
                  backend=args.backend, spec_k=args.spec_k,
                  draft_arch=args.draft_arch,
                  spec_k_adaptive=args.spec_k_adaptive)
    if args.smoke and args.trace:
        run_trace_smoke(args, kwargs)
        return
    if args.smoke and args.kv_dtype:
        mesh = parse_mesh(args.mesh) if args.mesh else (1, 1)
        if mesh[1] > 1:
            cfg = smoke(get_config(args.arch))
            err = tp_sharding_error(cfg, mesh[1])
            if err:
                raise SystemExit(f"--mesh {args.mesh}: {err}")
        run_kv_dtype_compare(args, mesh, kwargs)
        return
    if args.smoke and (args.pipeline != "off" or args.overlap != "none"):
        mesh = parse_mesh(args.mesh) if args.mesh else (1, 1)
        if mesh[1] > 1:
            cfg = smoke(get_config(args.arch))
            err = tp_sharding_error(cfg, mesh[1])
            if err:
                raise SystemExit(f"--mesh {args.mesh}: {err}")
        run_overlap_compare(args, mesh)
        return
    if args.router:
        mesh = parse_mesh(args.mesh) if args.mesh else (2, 1)
        if mesh[1] > 1:
            cfg = smoke(get_config(args.arch))
            err = tp_sharding_error(cfg, mesh[1])
            if err:
                raise SystemExit(f"--mesh {args.mesh}: {err}")
        run_router_compare(args, mesh, kwargs)
        return
    if args.mesh is not None:
        mesh = parse_mesh(args.mesh)
        cfg = smoke(get_config(args.arch))
        err = tp_sharding_error(cfg, mesh[1])
        if err:
            raise SystemExit(f"--mesh {args.mesh}: {err}")
        run_mesh_compare(args, mesh, kwargs)
        return
    out = run_bench(args.arch, spec=args.spec,
                    shared_prefix=args.shared_prefix,
                    prefix_cache=args.prefix_cache,
                    num_pages=args.num_pages, watermark=args.watermark,
                    preempt=args.preempt, pipeline=args.pipeline,
                    overlap=args.overlap, kv_dtype=args.kv_dtype,
                    warmup=not args.shared_prefix, **kwargs)
    if args.shared_prefix:
        print(f"[bench_serve/capacity] pages_peak={out['pages_peak']} "
              f"deduped={out['pages_deduped']} cow={out['cow_copies']} "
              f"preemptions={out['preemptions']} "
              f"(capacity-implied max batch {out['capacity_max_batch']})")
    print(f"[bench_serve] {out['requests']} requests "
          f"{out['tokens_per_s']:.1f} tok/s "
          f"(memory-bound ceiling {out['ceiling_tokens_per_s']:.0f} tok/s, "
          f"roofline fraction {out['roofline_fraction']:.4f}), "
          f"AI={out['arithmetic_intensity']:.2f} {out['bound_class']}, "
          f"ttft={out['ttft_s'] * 1e3:.1f}ms "
          f"itl_p50={out['itl_p50_s'] * 1e3:.2f}ms "
          f"p95={out['itl_p95_s'] * 1e3:.2f}ms")
    if args.spec != "none":
        print(f"[bench_serve/spec] proposer={args.spec} k={args.spec_k} "
              f"acceptance={out['acceptance_rate']:.2f} "
              f"tokens/pass={out['tokens_per_pass']:.2f} "
              f"(model {out['predicted_tokens_per_pass']:.2f}), predicted "
              f"memory-bound speedup x{out['predicted_speedup']:.2f}")
    if args.smoke and args.spec == "none":
        # CI acceptance bar: the speculative pass must report acceptance
        # and a ledger intensity strictly above one-token-per-pass decode
        spec_out = run_bench(args.arch, spec="ngram", **kwargs)
        print(f"[bench_serve/spec] ngram k={args.spec_k} "
              f"acceptance={spec_out['acceptance_rate']:.2f} "
              f"tokens/pass={spec_out['tokens_per_pass']:.2f} "
              f"AI={spec_out['arithmetic_intensity']:.2f} "
              f"(baseline {out['arithmetic_intensity']:.2f}), predicted "
              f"memory-bound speedup x{spec_out['predicted_speedup']:.2f}")
        if not (spec_out["arithmetic_intensity"]
                > out["arithmetic_intensity"]):
            raise RuntimeError(
                "speculative ledger intensity did not exceed the one-token"
                f"-per-pass baseline: {spec_out['arithmetic_intensity']} "
                f"<= {out['arithmetic_intensity']}")
        # capacity acceptance bar: the shared-prefix workload with prefix
        # sharing on must peak at FEWER pool pages than the unshared
        # baseline while emitting byte-identical greedy tokens
        sp = {k: v for k, v in kwargs.items() if k not in
              ("spec_k", "draft_arch", "spec_k_adaptive")}
        base_sp = run_bench(args.arch, shared_prefix=True,
                            prefix_cache=False, warmup=False, **sp)
        dedup_sp = run_bench(args.arch, shared_prefix=True,
                             prefix_cache=True, warmup=False, **sp)
        print(f"[bench_serve/capacity] shared-prefix pages_peak "
              f"{dedup_sp['pages_peak']} (cached) vs "
              f"{base_sp['pages_peak']} (unshared), "
              f"deduped={dedup_sp['pages_deduped']} "
              f"cow={dedup_sp['cow_copies']}")
        if dedup_sp["generated"] != base_sp["generated"]:
            raise RuntimeError(
                "prefix sharing changed greedy outputs: shared-prefix "
                "cached run must be byte-identical to the unshared run")
        if not dedup_sp["pages_peak"] < base_sp["pages_peak"]:
            raise RuntimeError(
                "prefix sharing did not reduce peak pool pages: "
                f"{dedup_sp['pages_peak']} >= {base_sp['pages_peak']}")
        if not dedup_sp["pages_deduped"] > 0:
            raise RuntimeError("prefix cache recorded no dedup hits on "
                               "the shared-prefix workload")


if __name__ == "__main__":
    main()
