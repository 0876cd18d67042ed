"""Device time of a cell's last traced run by program and by scope, as JSON.

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 45 --trace 1; python3 scripts/trace_scopes.py <cell | file.xplane.pb> [--ops REGEX]

Reads the ``.xplane.pb`` the harness left under ``.bench_scratch/<cell>/trace`` with the
benchmark's own reader (``benchmark/lib/scopes.py``): for every compiled program of the
trace its runs, its median run and the milliseconds a run spends under each device scope
(nested scopes each count their operations), under no scope, and in the ten operations
that took longest; and for each of the program's outermost loops (a training step's layers
forward, its loss, its layers backward: the ``while`` events no other ``while`` holds, in the
order they run) the milliseconds a run spends in it, by scope and in its fourteen longest
operations. With ``--ops REGEX`` every operation of a program whose instruction name the expression
finds is listed too, ms a run beside its scope path (``--ops .`` lists them all: the names are those of
``compiled.as_text()``, which ``scripts/rehearse_train_step.py`` prints the moves of). For PERF.md's
"where the time goes"; no run of the benchmark calls it.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def top_ops(ops: list[tuple[float, list[str], str]], n: int, per_run: float) -> dict[str, float]:
    """The ``n`` instructions of ``ops`` that took longest, ms a run."""
    by_op: dict[str, float] = {}
    for d, _, short in ops:
        by_op[short] = by_op.get(short, 0.0) + d
    return {k: round(v * per_run, 3) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:n]}


def named_ops(ops: list[tuple[float, list[str], str]], wanted: re.Pattern, per_run: float) -> dict[str, list]:
    """Every instruction of ``ops`` whose name ``wanted`` finds: ``[ms a run, scope path]``, by name."""
    found: dict[str, list] = {}
    for d, parts, short in ops:
        if wanted.search(short):
            entry = found.setdefault(short, [0.0, "/".join(parts)])
            entry[0] += d * per_run
    return {k: [round(ms, 4), path] for k, (ms, path) in sorted(found.items())}


def loops(planes: list[dict], module: str, device_scopes) -> list[dict]:  # noqa: ANN001
    """``module``'s outermost ``while`` loops in the order they run: name, ms a run, ms a run by scope and in the
    fourteen operations that took longest inside it."""
    from benchmark.lib import scopes, trace

    found: dict[str, dict] = {}
    runs = 0
    for p in planes:
        for name, s0, e0, _ in p["modules"]:
            if trace.module_name(name) != module:
                continue
            runs += 1
            inside = [(s, e, h.split(" = ")[0].lstrip("%"), path) for h, s, e, path in p["ops"] if s0 <= s < e0]
            whiles = sorted((s, e, n) for s, e, n, _ in inside if n.startswith("while"))
            outer = [w for w in whiles if not any(o[0] <= w[0] and w[1] <= o[1] and o is not w for o in whiles)]
            for ws, we, wn in outer:
                loop = found.setdefault(wn, {"at": ws - s0, "s": 0.0, "ops": []})
                loop["s"] += we - ws
                loop["ops"] += [(e - s, scopes.components(path), n) for s, e, n, path in inside if ws <= s < we and not trace.CONTAINER_OP.match(n)]
    per_run = 1e3 / max(runs, 1)
    return [
        {
            "loop": name, "ms_a_run": round(loop["s"] * per_run, 3),
            "ms_a_run_by_scope": {k: round(v * per_run, 3) for k, v in scopes.breakdown(loop["ops"], device_scopes)["by_scope"].items() if v > 0},
            "ms_a_run_top_ops": top_ops(loop["ops"], 14, per_run),
        }
        for name, loop in sorted(found.items(), key=lambda kv: kv[1]["at"])
    ]  # fmt: skip


def main() -> int:
    from benchmark.lib import scopes, spec, trace
    from torchx_tpu.obs import hot

    wanted = re.compile(sys.argv[sys.argv.index("--ops") + 1]) if "--ops" in sys.argv else None
    path = sys.argv[1] if sys.argv[1].endswith(".pb") else trace.find_xplane(os.path.join(spec.REPO_ROOT, ".bench_scratch", sys.argv[1], "trace"))
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
        out[module] = {
            "runs": len(times), "median_ms": statistics.median(times) * 1e3, "total_s": sum(times),
            "ms_a_run_by_scope": {k: round(v * per_run, 3) for k, v in b["by_scope"].items() if v > 0},
            "ms_a_run_unscoped": {k: round(v * per_run, 3) for k, v in list(b["unscoped"].items())[:6]},
            "ms_a_run_top_ops": top_ops(ops, 10, per_run),
            "loops": loops(planes, module, hot.DEVICE_SCOPES),
        }  # fmt: skip
        if wanted is not None:
            out[module]["ms_a_run_ops"] = named_ops(ops, wanted, per_run)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
