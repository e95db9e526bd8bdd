import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The XLA_FLAGS line above must execute before any jax import — jax locks the
device count at first init.  Results land in results/dryrun/*.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, get_config
from repro.core.analysis import analyze_compiled
from repro.core.roofline import multipod_scope, pod_scope
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.launch import specs as specs_mod
from repro.models import decode_step, loss_fn, prefill
from repro.models.common import SHAPES, applicable_shapes
from repro.parallel.sharding import sharding_context
from repro.train.step import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# §Perf hillclimb variants: named config/train-step tweaks applied on top of
# the paper-faithful baseline.  Each is one hypothesis in EXPERIMENTS.md.
import dataclasses as _dc


def _apply_variant(cfg, tcfg, variant: str):
    for piece in variant.split("+"):
        if piece in ("", "baseline"):
            continue
        elif piece == "absorb":
            cfg = _dc.replace(cfg, mla_absorb=True)
        elif piece == "tp_oproj":
            cfg = _dc.replace(cfg, tp_attn_inner=True)
        elif piece == "remat_dots":
            cfg = _dc.replace(cfg, remat="dots")
        elif piece == "remat_none":
            cfg = _dc.replace(cfg, remat="none")
        elif piece == "compress":
            tcfg = _dc.replace(tcfg, compress_pod_grads=True)
        elif piece.startswith("chunk"):
            cfg = _dc.replace(cfg, attn_chunk=int(piece[len("chunk"):]))
        elif piece == "localmoe":
            cfg = _dc.replace(cfg, moe_dispatch="local")
        elif piece.startswith("cf"):
            cfg = _dc.replace(cfg, capacity_factor=float(piece[2:]))
        else:
            raise ValueError(f"unknown variant piece {piece!r}")
    return cfg, tcfg


def _result_path(arch: str, shape: str, mesh_label: str,
                 variant: str = "baseline") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_label}{suffix}.json")


def run_cell(arch: str, shape: str, mesh, *, verbose: bool = True,
             force: bool = False, variant: str = "baseline"):
    """Lower+compile one cell; returns the analysis dict."""
    label = f"{arch}/{shape}/{mesh_name(mesh)}/{variant}"
    path = _result_path(arch, shape, mesh_name(mesh), variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    cell = SHAPES[shape]
    if shape == "long_500k" and not cfg.subquadratic:
        result = {"label": label, "status": "skipped",
                  "reason": "quadratic full attention; see DESIGN.md §5"}
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        if verbose:
            print(f"[dryrun] {label}: SKIPPED (quadratic attention)")
        return result

    t0 = time.time()
    try:
        cfg, tcfg = _apply_variant(cfg, TrainConfig(), variant)
        with sharding_context(mesh):
            if cell.kind == "train":
                args, in_sh, out_sh = specs_mod.train_specs(cfg, cell, mesh)
                step = make_train_step(cfg, tcfg)
                fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(0,))
            elif cell.kind == "prefill":
                args, in_sh, _ = specs_mod.prefill_specs(cfg, cell, mesh)
                if cfg.is_encoder_decoder:
                    fn = jax.jit(lambda p, t, e: prefill(p, cfg, t, enc_embeds=e),
                                 in_shardings=in_sh)
                elif cfg.n_image_tokens:
                    fn = jax.jit(lambda p, t, i: prefill(p, cfg, t, img_embeds=i),
                                 in_shardings=in_sh)
                else:
                    fn = jax.jit(lambda p, t: prefill(p, cfg, t),
                                 in_shardings=in_sh)
            else:  # decode
                args, in_sh, _ = specs_mod.decode_specs(cfg, cell, mesh)
                fn = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t, pos),
                             in_shardings=in_sh, donate_argnums=(1,))
            with jax.set_mesh(mesh):
                lowered = fn.lower(*args)
                compiled = lowered.compile()
        compile_s = time.time() - t0
        # archive the partitioned module: re-analysis never needs recompile
        import gzip
        with gzip.open(path.replace(".json", ".hlo.gz"), "wt") as zf:
            zf.write(compiled.as_text())
        scope = (multipod_scope() if mesh_name(mesh) == "multipod"
                 else pod_scope())
        report = analyze_compiled(
            compiled, mesh, label=label, scope=scope, dtype=cfg.dtype,
            model_flops=specs_mod.cell_flops(cfg, cell),
            compile_seconds=compile_s)
        ma = compiled.memory_analysis()
        result = report.as_dict()
        result["status"] = "ok"
        result["arch"], result["shape"] = arch, shape
        result["variant"] = variant
        if verbose:
            print(f"[dryrun] {label}: compiled in {compile_s:.1f}s")
            print(report.render())
            print(f"  memory_analysis: {ma}")
            sys.stdout.flush()
    except Exception as e:  # a failing cell is a bug — record it loudly
        result = {"label": label, "status": "error", "arch": arch,
                  "shape": shape, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
        if verbose:
            print(f"[dryrun] {label}: FAILED — {type(e).__name__}: {e}")
            sys.stdout.flush()
    with open(path, "w") as f:
        json.dump(result, f, indent=2, default=float)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompile even if a cached result exists")
    ap.add_argument("--variant", default="baseline",
                    help="'+'-joined perf levers, e.g. tp_oproj+remat_dots")
    args = ap.parse_args()

    archs = list(ALL_ARCHS) if (args.all or args.arch is None) else [args.arch]
    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append(make_production_mesh(multi_pod=False))
    if args.mesh in ("multipod", "both"):
        meshes.append(make_production_mesh(multi_pod=True))

    failures = 0
    for mesh in meshes:
        for arch in archs:
            shapes = ([args.shape] if args.shape
                      else list(SHAPES))
            for shape in shapes:
                res = run_cell(arch, shape, mesh, force=args.force,
                               variant=args.variant)
                if res.get("status") == "error":
                    failures += 1
    if failures:
        print(f"[dryrun] {failures} cell(s) FAILED")
        sys.exit(1)
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
