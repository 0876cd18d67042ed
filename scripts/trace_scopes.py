"""Device time of a cell's last traced run by program and by scope, as JSON.

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 45 --trace 1; python3 scripts/trace_scopes.py <cell>

Reads the ``.xplane.pb`` the harness left under ``.bench_scratch/<cell>/trace`` with the
benchmark's own reader (``benchmark/lib/scopes.py``): for every compiled program of the
trace its runs, its median run and the milliseconds a run spends under each device scope
(nested scopes each count their operations), under no scope, and in the ten operations
that took longest. For PERF.md's "where the time goes"; no run of the benchmark calls it.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmark.lib import scopes, spec, trace
    from torchx_tpu.obs import hot

    path = trace.find_xplane(os.path.join(spec.REPO_ROOT, ".bench_scratch", sys.argv[1], "trace"))
    planes = scopes.read_planes(path)
    runs: dict[str, list[float]] = {}
    for p in planes:
        for name, s, e, _ in p["modules"]:
            runs.setdefault(trace.module_name(name), []).append(e - s)
    out = {}
    for module, times in sorted(runs.items(), key=lambda kv: -sum(kv[1])):
        ops = scopes.program_ops(planes, module)
        if not ops or sum(times) < 0.01:
            continue
        b = scopes.breakdown(ops, hot.DEVICE_SCOPES)
        per_run = 1e3 / len(times)
        by_op: dict[str, float] = {}
        for d, _, short in ops:
            by_op[short] = by_op.get(short, 0.0) + d
        out[module] = {
            "runs": len(times), "median_ms": statistics.median(times) * 1e3, "total_s": sum(times),
            "ms_a_run_by_scope": {k: round(v * per_run, 3) for k, v in b["by_scope"].items() if v > 0},
            "ms_a_run_unscoped": {k: round(v * per_run, 3) for k, v in list(b["unscoped"].items())[:6]},
            "ms_a_run_top_ops": {k: round(v * per_run, 3) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]},
        }  # fmt: skip
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
