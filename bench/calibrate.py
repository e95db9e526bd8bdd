#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds a b c ...

For each seed, in one process: a run of the cell as ``bench/run.py`` makes
it (the same set-up, window and check, at the cell's own sizes and load),
then the float8 control, held to the same check in the served tokens'
place.  Prints one JSON line per seed and, last, the lower reading (the
widest gap any sound run gave), the upper reading (the smallest widest gap
of the control) and whether the check failed the control on every seed.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    # libtpu logs under /tmp unless told otherwise; keep every write in
    # the checkout
    os.environ["TPU_LOG_DIR"] = str(ROOT / ".bench_cache" / "tpu_logs")
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    program, control, control_failed = [], [], []
    t0 = T_PROCESS
    for seed in args.seeds:
        cell = harness.load_cell(args.workload)
        res = harness.run_cell(cell, seed, args.seconds, False, t0,
                               control=True)
        t0 = time.perf_counter()
        gap = res["checks"]["max_logit_gap"]["value"]
        program.append(gap)
        control.append(res["control"]["checks"]["max_logit_gap"]["value"])
        control_failed.append(res["control"]["correct"] is False)
        print(json.dumps({"seed": seed, "program": gap,
                          "control": control[-1],
                          "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": max(program), "upper": min(control),
                      "control_failed_every_seed": all(control_failed)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
