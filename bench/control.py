"""Readings for a cell's correctness limit, in one process over many seeds.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed: one run of the cell (set-up, a window at the cell's own
load) whose check puts the control in the program's place: the
reference recomputed with every intermediate tensor rounded to
``float8_e4m3fn``, the precision below the configuration's bfloat16,
scored by the gap of the token it puts first.  ``correct`` must come
out false.  One JSON line per seed: ``correct``, the numbers compared
(``checks``) and the readings of the program's served tokens and of the
control's picks on the same sample (widest gap, its 99th percentile,
mean, share of positions off the reference's best).  The benchmark's
own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

CONTROL = "float8_e4m3fn"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    for key in [k for k in os.environ if k.startswith("SME_")]:
        del os.environ[key]
    bench = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(bench), "src"))
    from harness import run_cell
    from spec import load_cell

    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       control=CONTROL)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"], "checks": out["checks"],
            "readings": out["readings"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        }), flush=True)


if __name__ == "__main__":
    main()
