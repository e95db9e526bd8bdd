#!/usr/bin/env python3
"""The rate sweep a Poisson cell's fixed rate is chosen from, on the chip.

    python bench/sweep.py --workload <cell> --seconds <s> \
        --rates 1.5 2.0 2.5 --seeds a b c

In one process, for each rate and each seed: a run of the cell as
``bench/run.py`` makes it, with the mix's ``rate_per_s`` replaced by the
rate.  Prints one JSON line per run (its end-to-end metrics and the
requests due) and, last, the median of each metric over the seeds at each
rate.  The highest rate the cell sustains is the knee; the cell's mix
takes about four fifths of it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    os.environ["TPU_LOG_DIR"] = str(ROOT / ".bench_cache" / "tpu_logs")
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    medians = {}
    t0 = T_PROCESS
    for rate in args.rates:
        rows = []
        for seed in args.seeds:
            cell = harness.load_cell(args.workload)
            cell.mix = dict(cell.mix, rate_per_s=rate)
            res = harness.run_cell(cell, seed, args.seconds, False, t0)
            t0 = time.perf_counter()
            row = {k: v["value"] for k, v in res["metrics"].items()}
            rows.append(row)
            print(json.dumps({"rate": rate, "seed": seed,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "metrics": row}), flush=True)
        medians[rate] = {k: statistics.median(r[k] for r in rows)
                         for k in rows[0]}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "medians": medians}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
