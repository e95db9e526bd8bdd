"""The tensor-parallel path of the harness on four virtual CPU devices.

JAX fixes its device count when it starts, so ``test_tp.py`` runs this
file in a process of its own:

    python bench/tests/tp4_child.py

For each case it prints one JSON line: ``weights`` compares the seed's
weights born in their tp=4 shards with the one-device draw; ``sound``
runs a tiny backlog cell at tp=4 (Pallas kernels interpreted); the fault
cases run it with the timed path broken underneath, and ``control`` holds
the float8 control to the same check.
"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.tests.conftest import tiny_config  # noqa: E402

TP = 4
SEED = 2**31 + 303
MIX = {"arrival": "backlog", "depth": 6, "distinct_prompt_lengths": 6,
       "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 16, "max": 128},
       "output_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 32}}
END_TO_END = [{"name": "output_tok_s", "unit": "tokens/s"},
              {"name": "setup_s", "unit": "s"}]


def config(**sizes):
    """qwen3-tiny, untied (the head is vocabulary-sharded, as 14b's), at
    tp=4: 2 query heads, 1 KV head and a quarter of d_ff per device."""
    cfg = tiny_config(tied=False, **sizes)
    cfg["serving"] = dict(cfg["serving"], tp=TP, num_slots=6, num_pages=80)
    return cfg


def weights():
    from repro.parallel.mesh import make_host_mesh
    cfg = config()
    adapter, _ = harness.model_modules(cfg)
    mcfg = adapter.model_config(cfg)
    whole = adapter.make_params(cfg, mcfg, SEED)
    mesh = make_host_mesh(data=1, model=TP)
    shards = adapter.make_params(cfg, mcfg, SEED, mesh)
    same, split = [], []
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(shards)):
        same.append(bool(np.array_equal(np.asarray(a), np.asarray(b))
                         and a.dtype == b.dtype))
        held = [s.data.size for s in b.addressable_shards]
        split.append([b.size, sorted(held)])
    return {"same": same, "split": split}


def _wrap_decode(transform):
    def hook(engine):
        inner = engine._decode_fn

        def step(*args):
            return transform(args, inner(*args))
        engine._decode_fn = step
    return hook


FAULTS = {
    "state_unchanged": lambda args, out: (out[0], args[1]),
    "token_altered": lambda args, out: ((out[0] + 1) % 512, out[1]),
}


def _half_batch_left_out(engine):
    inner = engine._decode_fn

    def step(*args):
        args = list(args)
        odd = (jnp.arange(args[3].shape[0]) % 2 == 1)
        args[3] = jnp.where(odd[:, None], 0, args[3])       # tokens
        args[4] = jnp.where(odd, 0, args[4])                # positions
        return inner(*args)
    engine._decode_fn = step


def _exchange_left_out(engine):
    """The decode step re-traced with every row-parallel psum gone: each
    chip keeps its own partial sums."""
    from repro.parallel import collectives
    collectives.row_parallel_psum = lambda x, axis: x
    engine._apply_mesh()


def run(case):
    from repro.parallel import collectives
    cfg = config()
    hook, control = None, False
    seen = {"waiting": [], "pool_shards": None}
    if case in FAULTS:
        hook = _wrap_decode(FAULTS[case])
    elif case == "half_batch":
        hook = _half_batch_left_out
    elif case == "exchange_left_out":
        hook = _exchange_left_out
    elif case == "control":
        cfg = config(hidden_size=256, intermediate_size=512,
                     num_hidden_layers=4, vocab_size=2048)
        control = True
    elif case == "sound":
        def hook(engine):
            inner = engine.step

            def step():
                seen["waiting"].append(len(engine._sched.waiting))
                out = inner()
                seen["pool_shards"] = [
                    [list(x.shape), sorted({tuple(s.data.shape)
                                            for s in x.addressable_shards})]
                    for x in jax.tree.leaves(engine._kv.pools)]
                return out
            engine.step = step
    cell = harness.Cell("tiny-tp4.backlog", TP, cfg, dict(MIX), END_TO_END,
                        [])
    psum = collectives.row_parallel_psum
    try:
        res = harness.run_cell(cell, SEED, 4.0, False, time.perf_counter(),
                               engine_hook=hook, control=control)
    finally:
        collectives.row_parallel_psum = psum
    res.update(seen)
    return res


def main():
    cases = sys.argv[1:] or ["weights", "sound", "state_unchanged",
                             "token_altered", "half_batch",
                             "exchange_left_out", "control"]
    for case in cases:
        out = weights() if case == "weights" else run(case)
        print(json.dumps({"case": case, **out}), flush=True)


if __name__ == "__main__":
    main()
