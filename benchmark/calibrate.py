"""Read the numbers that ``correct`` compares, on several seeds in one process,
with the control beside them (and, with ``--rates``, the sweep that finds a
serving cell's knee): the reference computed in fp8, put in the
program's place. The limits in the cell files (``workloads/``) were set from this script's
output on the chip (PERF.md gives the readings); the benchmark's own runs never
run the control.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmark.run import run_cell

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="fp8", help="'' runs none")
    ap.add_argument("--rates", default="", help="sweep: one run per rate and seed")
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run_cell(args.workload, seed, args.seconds, False,
                           control=args.control or None, rate_per_s=rate)
            print("calibrate:", json.dumps({"seed": seed, "rate_per_s": rate, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
