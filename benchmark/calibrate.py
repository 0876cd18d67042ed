"""Read the numbers that ``correct`` compares, on several seeds in one process,
with the control beside them (and, with ``--rates``, the sweep that finds a
serving cell's knee): the reference computed in fp8, put in the
program's place. The limits in the cell files (``workloads/``) were set from this script's
output on the chip (PERF.md gives the readings); the benchmark's own runs never
run the control.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

``--longest`` runs no cell and takes none of the flags of a run: it makes the
cell's weights from the first seed and sends one made-up request of the mix's
longest prompt and the answer that fills ``max_total_tokens`` through the
check, so that its ``check: memory`` line says what the longest request a run
can finish needs beside the weights alone (in a run the window's peak, which
cannot be reset, hides it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _longest(workload: str, seed: int) -> None:
    """The check over one made-up request of ``max_total_tokens`` beside the
    cell's weights and nothing else: no engine, no pool, no window."""
    import numpy as np

    from benchmark.lib import device, models, serve_cell, spec

    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    cell = spec.load_cell(workload)
    device.require_chips(cell.chips)
    setup_compilation_cache()
    config, mix = cell.config, cell.traffic
    params = models.make_weights(config, seed)
    n_p = int(mix["prompt"]["max"])
    n_g = int(mix.get("max_total_tokens", n_p + int(mix["output"]["max"]))) - n_p
    rng = np.random.default_rng(0x10E6)
    made_up = {"prompt": rng.integers(0, config["vocab_size"], n_p).tolist(),
               "generated": rng.integers(0, config["vocab_size"], n_g).tolist()}
    print(f"calibrate: {workload}: one made-up request of {n_p} + {n_g} tokens through the check, beside"
          f" {device.memory_stats()['bytes_in_use']} bytes of weights", flush=True)
    worst = max(serve_cell.served_gaps(params, config, [made_up]))
    stats = device.memory_stats()
    print(f"calibrate: {workload}: widest gap {worst:.4g} (random tokens: large); the process's peak"
          f" {stats['peak_bytes_in_use']} of {stats['bytes_limit']} bytes", flush=True)


def main() -> int:
    from benchmark.run import run_cell

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--control", default="fp8", help="'' runs none")
    ap.add_argument("--rates", default="", help="sweep: one run per rate and seed")
    ap.add_argument("--longest", action="store_true", help="no run: one made-up request of max_total_tokens through the check")
    args = ap.parse_args()
    if args.longest:
        if args.seconds is not None or args.rates:
            ap.error("--longest runs no cell: it takes neither --seconds nor --rates")
        _longest(args.workload, int(args.seeds.split(",")[0]))
        return 0
    if args.seconds is None:
        ap.error("--seconds is required")
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run_cell(args.workload, seed, args.seconds, False,
                           control=args.control or None, rate_per_s=rate)
            print("calibrate:", json.dumps({"seed": seed, "rate_per_s": rate, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
