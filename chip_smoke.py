#!/usr/bin/env python3
"""Smoke run of the serving path on TPU, in one process.

    python chip_smoke.py              # one chip: qwen3-0.6b at published widths
    python chip_smoke.py --chips 4    # four chips: qwen3-14b at tp=4, and a
                                      # router over four one-chip replicas

One chip: random qwen3-0.6b weights from ``--seed``, served through
``make_engine`` / ``Engine.submit`` / ``Engine.run`` with the Pallas
paged-attention kernels compiled by Mosaic.  Eight requests (prompts of
64-512 tokens, one of them through chunked prefill) each return 32 tokens,
and the first decode step's logits agree with the same step on the jnp
reference kernels.

Four chips: qwen3-14b with its weights created directly in their tp=4
shards (no device holds the whole model), its first sharded decode step
compared with the jnp kernels under the same sharding; then a router over
four one-chip qwen3-0.6b replicas whose greedy tokens must equal a single
engine's, token for token.

Exits non-zero unless JAX's first device is a TPU.  The last line of
standard output is the JSON result; timings printed above it are smoke
figures (compilation included where stated), not benchmarks.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.roofline.hardware import chip_for_device_kind  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import decode_step_paged, init_params, param_bytes  # noqa: E402
from repro.parallel.mesh import make_host_mesh  # noqa: E402
from repro.serve import (Cluster, EngineConfig, GenerateConfig,  # noqa: E402
                         RoleConfig, Router, init_sharded_params,
                         make_engine)

PAGE = 16
# bf16 keeps 8 mantissa bits: the Pallas walk (f32 scores and softmax) and
# the jnp reference (bf16 einsums) round at different points in every
# layer.  A wrong page layout or mask moves the logits by O(1) relative.
LOGITS_REL_TOL = 5e-2

# seconds XLA (and Mosaic, for the kernels) spent compiling, summed over
# every program this process compiled
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_event(event: str, duration_s: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration_s


def _prompts(seed: int, lengths, vocab: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n, dtype=np.int32) for n in lengths]


class _DecodeSpy:
    """Records the arguments of an engine's first jitted decode step, so
    the check can replay exactly that step on both kernel backends."""

    def __init__(self, engine):
        self.fn, self.first = engine._decode_fn, None
        engine._decode_fn = self

    def __call__(self, *args):
        if self.first is None:
            self.first = args
        return self.fn(*args)


def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _check_logits(name: str, pallas, ref, active) -> None:
    pallas, ref = np.asarray(pallas)[active], np.asarray(ref)[active]
    if not (np.isfinite(pallas).all() and np.isfinite(ref).all()):
        raise SystemExit(f"{name}: non-finite first-step logits")
    err = _rel_l2(pallas, ref)
    print(f"[{name}] first decode step logits {pallas.shape}: pallas vs jnp "
          f"rel-L2 {err:.3e} (bound {LOGITS_REL_TOL}), max |diff| "
          f"{float(np.abs(pallas - ref).max()):.4f}, argmax agree "
          f"{int((pallas.argmax(-1) == ref.argmax(-1)).sum())}/{len(ref)}")
    if err > LOGITS_REL_TOL:
        raise SystemExit(f"{name}: pallas logits differ from the jnp "
                         f"reference by {err:.3e} > {LOGITS_REL_TOL}")


def _check_done(name: str, done, n_requests: int, new_tokens: int,
                vocab: int) -> None:
    if len(done) != n_requests:
        raise SystemExit(f"{name}: {len(done)} of {n_requests} finished")
    for r in done:
        toks = np.asarray(r.generated)
        if (len(toks) != new_tokens or r.finish_reason != "length"
                or toks.min() < 0 or toks.max() >= vocab):
            raise SystemExit(f"{name}: request {r.request_id} returned "
                             f"{len(toks)} tokens ({r.finish_reason})")


def _serve(engine, prompts, gen):
    reqs = [engine.submit(p, gen) for p in prompts]
    spy = _DecodeSpy(engine)
    t0 = time.perf_counter()
    done = engine.run()
    return reqs, done, time.perf_counter() - t0, spy.first


def _device_bytes(label: str):
    used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
    print(f"[{label}] bytes_in_use per device: {used}")
    return used


def run_one_chip(cfg, seed: int, chip, backend: str = "pallas",
                 lengths=(64, 96, 128, 160, 192, 224, 256, 512),
                 new_tokens: int = 32, prefill_chunk: int = 256):
    """qwen3-0.6b (or any paged config) on one device through the engine:
    returns the tokens each request generated."""
    name = f"{cfg.name} 1-chip"
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.key(seed))
    jax.block_until_ready(params)
    print(f"[{name}] random weights from seed {seed}: "
          f"{param_bytes(cfg) / 1e9:.3f} GB in "
          f"{time.perf_counter() - t0:.2f}s")
    ecfg = EngineConfig(num_slots=8, page_size=PAGE,
                        max_len=max(lengths) + new_tokens,
                        prefill_chunk=prefill_chunk, chip=chip,
                        kernel_backend=backend)
    resolved = ops.resolve("paged_attention", backend)
    print(f"[{name}] paged_attention resolves to "
          f"{resolved.func.__module__}.{resolved.func.__name__} "
          f"interpret={resolved.keywords['interpret']}")
    engine = make_engine(cfg, params, ecfg)
    prompts = _prompts(seed, lengths, cfg.vocab_size)
    chunked = [len(p) for p in prompts if len(p) > prefill_chunk]
    gen = GenerateConfig(max_new_tokens=new_tokens)
    compile0 = _compile_s[0]
    reqs, done, wall, first = _serve(engine, prompts, gen)
    _check_done(name, done, len(prompts), new_tokens, cfg.vocab_size)
    n_tok = sum(len(r.generated) for r in done)
    print(f"[{name}] cold pass: {len(done)} requests (prompts {list(lengths)}"
          f", {len(chunked)} through chunked prefill of {prefill_chunk}), "
          f"{n_tok} tokens in {wall:.3f}s wall, of which compile "
          f"{_compile_s[0] - compile0:.3f}s (set-up)")
    _, warm_done, warm_wall, _ = _serve(engine, prompts, gen)
    _check_done(name, warm_done, len(prompts), new_tokens, cfg.vocab_size)
    same = ([r.generated for r in sorted(done, key=lambda r: r.request_id)]
            == [r.generated for r in sorted(warm_done,
                                            key=lambda r: r.request_id)])
    print(f"[{name}] warm pass: {n_tok} tokens in {warm_wall:.3f}s wall "
          f"({n_tok / warm_wall:.1f} tok/s smoke figure, not a benchmark); "
          f"tokens equal to the cold pass: {same}")

    p, pools, bt, tok, pos, act = first[:6]

    def logits_on(kernel_backend):
        def step(p_, pools_, *rest):
            return decode_step_paged(p_, cfg, pools_, *rest, page_size=PAGE,
                                     backend=kernel_backend)[0]
        return jax.jit(step)(p, pools, bt, tok, pos, act)

    _check_logits(name, logits_on(backend), logits_on("jnp"),
                  np.asarray(act))
    return [list(r.generated) for r in reqs]


def run_tp(cfg, seed: int, chip, tp: int, backend: str = "pallas",
           lengths=(64, 96, 112, 128), new_tokens: int = 8):
    """A tensor-parallel engine over ``tp`` devices with weights born in
    their shards; its first sharded decode step on the Pallas kernels is
    compared with the jnp kernels under the same sharding."""
    name = f"{cfg.name} tp={tp}"
    mesh = make_host_mesh(data=1, model=tp)
    t0 = time.perf_counter()
    params = init_sharded_params(cfg, jax.random.key(seed), mesh)
    jax.block_until_ready(params)
    whole = param_bytes(cfg)
    print(f"[{name}] random sharded weights from seed {seed}: "
          f"{whole / 1e9:.3f} GB in all, in "
          f"{time.perf_counter() - t0:.2f}s")
    if jax.default_backend() == "tpu":
        used = _device_bytes(name)
        if max(used) >= whole:
            raise SystemExit(f"{name}: a device holds {max(used)} bytes, "
                             f"the whole model is {whole}")
    ecfg = EngineConfig(num_slots=len(lengths), page_size=PAGE,
                        max_len=max(lengths) + new_tokens, chip=chip,
                        kernel_backend=backend)
    engine = make_engine(cfg, params, ecfg, mesh_shape=(1, tp))
    prompts = _prompts(seed, lengths, cfg.vocab_size)
    gen = GenerateConfig(max_new_tokens=new_tokens)
    compile0 = _compile_s[0]
    _, done, wall, first = _serve(engine, prompts, gen)
    _check_done(name, done, len(prompts), new_tokens, cfg.vocab_size)
    print(f"[{name}] {len(done)} requests, "
          f"{sum(len(r.generated) for r in done)} tokens in {wall:.3f}s "
          f"wall, of which compile {_compile_s[0] - compile0:.3f}s")

    p, pools, bt, tok, pos, act = first[:6]
    rep = P()

    def logits_on(kernel_backend):
        def body(p_, pools_, *rest):
            return decode_step_paged(p_, engine.cfg_local, pools_, *rest,
                                     page_size=PAGE,
                                     backend=kernel_backend)[0]
        return jax.jit(jax.shard_map(
            body, mesh=engine.mesh,
            in_specs=(engine._param_specs, engine._pool_specs) + (rep,) * 4,
            out_specs=rep, check_vma=False))(p, pools, bt, tok, pos, act)

    _check_logits(name, logits_on(backend), logits_on("jnp"),
                  np.asarray(act))
    if jax.default_backend() == "tpu":
        _device_bytes(name)


def run_router(cfg, seed: int, chip, dp: int, backend: str = "pallas",
               lengths=(72, 80, 88, 96, 104, 112, 120, 128),
               new_tokens: int = 16):
    """A router over ``dp`` one-device replicas must emit the greedy tokens
    a single engine emits for the same requests, token for token."""
    name = f"{cfg.name} router dp={dp}"
    params = init_params(cfg, jax.random.key(seed))
    ecfg = EngineConfig(num_slots=4, page_size=PAGE,
                        max_len=max(lengths) + new_tokens, chip=chip,
                        kernel_backend=backend)
    prompts = _prompts(seed, lengths, cfg.vocab_size)
    gen = GenerateConfig(max_new_tokens=new_tokens)
    single = make_engine(cfg, params, ecfg)
    want = [list(r.generated) for r in _serve(single, prompts, gen)[0]]
    del single
    cluster = Cluster(cfg, params, ecfg, mesh_shape=(dp, 1),
                      roles=RoleConfig.mixed(dp))
    router = Router(cluster)
    reqs = [router.submit(p, gen) for p in prompts]
    t0 = time.perf_counter()
    done = router.run()
    wall = time.perf_counter() - t0
    _check_done(name, done, len(prompts), new_tokens, cfg.vocab_size)
    got = [list(r.generated) for r in reqs]
    busy = sum(1 for e in cluster.replicas
               if e._sched is not None and e._sched.finished)
    print(f"[{name}] {len(done)} requests over {busy} busy replicas "
          f"({'colocated' if cluster.colocated else 'one device each'}), "
          f"{wall:.3f}s wall incl. compile; tokens equal to one engine: "
          f"{got == want}")
    if got != want:
        raise SystemExit(f"{name}: router tokens differ from one engine")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    chip = chip_for_device_kind(dev.device_kind)
    print(f"[chip_smoke] device_kind={dev.device_kind!r} -> peaks "
          f"{chip.name}; {len(jax.devices())} devices; jax "
          f"{jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(get_config("qwen3-0.6b"), args.seed, chip)
        stats = dev.memory_stats()
        print(f"[chip_smoke] peak_bytes_in_use={stats['peak_bytes_in_use']}"
              f" of bytes_limit={stats.get('bytes_limit')}")
    else:
        run_tp(get_config("qwen3-14b"), args.seed, chip, tp=4)
        gc.collect()
        run_router(get_config("qwen3-0.6b"), args.seed, chip, dp=4)
        print("[chip_smoke] peak_bytes_in_use per device: "
              f"{[d.memory_stats()['peak_bytes_in_use'] for d in jax.devices()]}")
    print(f"[chip_smoke] total {time.perf_counter() - t0:.1f}s, compile "
          f"{_compile_s[0]:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
