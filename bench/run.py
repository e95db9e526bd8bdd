#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration with weights drawn from ``--seed``, warms
up every shape its traffic uses (set-up, timed as ``setup_s``), measures
``--seconds`` seconds of traffic, checks what the window served against
the float32 reference, and prints one JSON line last on standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.

Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # libtpu logs under /tmp unless told otherwise; keep every write in
    # the checkout
    os.environ["TPU_LOG_DIR"] = str(ROOT / ".bench_cache" / "tpu_logs")
    from bench import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    harness.use_compile_cache()
    harness.log(f"{args.workload} seed {args.seed}: {len(devices)} x "
                f"{devices[0].device_kind!r}, jax {jax.__version__}, "
                f"compile cache {harness.CACHE_DIR}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
