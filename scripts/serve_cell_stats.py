"""One run of a serving cell as ``benchmark/run.py`` makes it, with the engine's own
counters printed as the window's engine stops: ``preemptions``, ``kv_bytes_per_token``,
``steps_overlapped``, ``tokens_discarded`` (PR 30), blocks in use, the prefix cache's hits and
evictions, and the running counts of the prompts' chunks (PR 40: ``chunk_steps`` of
``chunk_width``, ``prefill_tokens``, ``prefill_padded_tokens``). The harness hands its readers
neither ``engine.stats()`` nor the requests (PERF.md 7.2 (c)); this is how PR 27 read
the preemptions of ``kimi-vl-a3b-serve-backlog``. With ``--trace 1`` it also prints what
``benchmark/lib/program_runs.py`` reads of the traced 4 s: the runs linked by ``run_id``,
the offset's bounds, a fetch's wait in its two parts (a trace of an engine from before
PR 40 also the exposed turn after a round in its parts). Not a tool of the benchmark.

    chiprun -- python3 scripts/serve_cell_stats.py --workload <cell> --seed <n> --seconds 45 [--trace 1]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    from benchmark.run import run_cell
    from torchx_tpu.serve import engine as eng

    stop = eng.ServeEngine.stop

    def stop_and_tell(self, *a, **kw):  # noqa: ANN001, ANN002, ANN003, ANN202
        s = self.stats()
        keep = ("preemptions", "steps_overlapped", "tokens_discarded", "kv_bytes_per_token", "kv_blocks_used",
                "kv_blocks_free", "requests_done", "steps", "prefix_cache", "chunk_steps", "chunk_width",
                "prefill_tokens", "prefill_padded_tokens", "state_bytes_per_slot", "state_bytes", "prefix_cache_off",
                "kv_blocks_window", "kv_blocks_pooled", "window_blocks_released", "pooled_blocks_promoted", "cache_rows_held",
                "cache_tokens_held")  # fmt: skip
        print("engine stats at stop:", json.dumps({k: s[k] for k in keep if k in s}), flush=True)
        return stop(self, *a, **kw)

    eng.ServeEngine.stop = stop_and_tell
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    if args.trace:
        from benchmark.lib import program_runs, spec, trace

        try:  # the harness's trace is still where it wrote it
            reading = program_runs.read(trace.find_xplane(os.path.join(spec.scratch_dir(spec.load_cell(args.workload)), "trace")))
        except FileNotFoundError:
            reading = None
        print("program runs of the traced window:", json.dumps(program_runs.account(reading) if reading else None), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
