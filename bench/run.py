"""Serving benchmark of the SME engine on the chips JAX finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` from the root of a checkout.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` (with
``--trace 1`` also ``busy_s``, ``window_s`` and a ``breakdown``) and,
last, ``checks``: each number compared with its limit.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # the program's defaults hold: no SME_* override reaches it
    for key in [k for k in os.environ if k.startswith("SME_")]:
        del os.environ[key]
    bench = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(bench), "src"))
    import repro  # noqa: F401  (the program under test; absent: exit 1)
    from harness import run_cell
    from spec import load_cell

    out = run_cell(load_cell(args.workload), args.seed, args.seconds,
                   bool(args.trace), T_START)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
