"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 50 --batch 4 --seq 128

Pre-flight: the step is lowered, compiled and roofline-characterized
*before* the first batch (the paper's analysis as a built-in feature) —
you see the predicted bound and per-scope breakdown, then training starts.
Device mesh: uses every visible device as (data, model=1) by default.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import ALL_ARCHS, get_config, smoke
from repro.core.analysis import analyze_compiled
from repro.core.roofline import scope_for_mesh
from repro.core.roofline.hardware import HOST_CPU_FALLBACK
from repro.launch import specs as specs_mod
from repro.models.common import ShapeCell, model_flops
from repro.parallel.mesh import make_host_mesh
from repro.parallel.sharding import sharding_context
from repro.train import (CheckpointManager, LoopConfig, OptConfig,
                         SyntheticLMData, TrainConfig, TrainLoop,
                         make_initial_state, make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS), default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--data", type=int, default=0,
                    help="data-parallel ways (0 = all devices)")
    ap.add_argument("--grad-accum", type=int, default=1)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    schedule = "wsd" if args.arch == "minicpm-2b" else "cosine"
    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                      total_steps=args.steps, schedule=schedule),
        grad_accum=args.grad_accum)
    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                          log_every=max(args.steps // 20, 1), train=tcfg)

    n_data = args.data or len(jax.devices())
    mesh = make_host_mesh(data=n_data, model=1)
    with sharding_context(mesh):
        # -- pre-flight roofline (the paper's feature) ---------------------
        cell = ShapeCell("preflight", args.seq, args.batch, "train")
        spec_args, in_sh, out_sh = specs_mod.train_specs(cfg, cell, mesh)
        step = make_train_step(cfg, tcfg)
        with jax.set_mesh(mesh):
            compiled = jax.jit(step, in_shardings=in_sh,
                               out_shardings=out_sh,
                               donate_argnums=(0,)).lower(*spec_args).compile()
        report = analyze_compiled(
            compiled, mesh, label=f"{cfg.name} train preflight",
            chip=HOST_CPU_FALLBACK, dtype="float32",
            model_flops=model_flops(cfg, args.seq, args.batch, "train"))
        print(report.render())

        data = SyntheticLMData(cfg, args.batch, args.seq)
        loop = TrainLoop(
            cfg, loop_cfg, data,
            CheckpointManager(f"{args.ckpt_dir}/{cfg.name}", keep=2),
            make_initial_state(cfg),
            step_fn=lambda s, b: compiled(s, b))
        out = loop.run()
    print(f"[train] finished at step {out['step']}; history:")
    for h in loop.history[-10:]:
        print(f"  step {h['step']:>5}  loss {h['loss']:.4f}  dt {h['dt']*1e3:.0f}ms")
    if loop.watchdog.events:
        print(f"[train] straggler events: {len(loop.watchdog.events)}")


if __name__ == "__main__":
    main()
