"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip: it loads the cell's files, sets up, warms
every program the window will use, measures for ``--seconds``, decides
``correct`` against ``benchmark/reference`` and prints one JSON object as its
last line. Without a TPU it exits non-zero and prints no result.
``--rehearse`` drives the same code on the CPU at the tiny widths of
``tests/fixtures`` and prints no metrics line, ever.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))


def load_reader(name: str, bench_dir: str):
    """The reader of per-layer metric ``name``: ``layer_metrics/<name>.py``."""
    path = os.path.join(_readers_dir(bench_dir), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _readers_dir(bench_dir: str) -> str:
    """``bench_dir``'s readers, or the benchmark's own where it has none (the
    test fixtures keep only cells)."""
    own = os.path.join(bench_dir, "layer_metrics")
    return own if os.path.isdir(own) else os.path.join(BENCH_DIR, "layer_metrics")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, bench_dir: str = BENCH_DIR,
             allow_cpu: bool = False, control: str | None = None, t_start: float | None = None,
             rate_per_s: float | None = None) -> dict:
    """Run one cell and return the result object of the last line.
    ``rate_per_s`` replaces the traffic file's rate: for the sweep that finds
    the knee when a cell is defined, never for a measured run."""
    import dataclasses

    from benchmark.lib import serve_cell, spec, train_cell

    cell = spec.load_cell(workload, bench_dir)
    if rate_per_s is not None:
        arrivals = dict(cell.traffic["arrivals"], rate_per_s=rate_per_s)
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, arrivals=arrivals))
    runner = {"train": train_cell, "serve": serve_cell}[cell.kind]
    print(f"benchmark: cell {cell.name} = {cell.config_name} x {cell.traffic_name},"
          f" {cell.chips} chip(s), seed {seed}, {seconds}s, trace {int(trace)}", flush=True)
    rec = runner.run(cell, seed, seconds, trace, t_start if t_start is not None else time.monotonic(),
                     allow_cpu=allow_cpu, control=control)
    rec["verdict"].print()
    metrics = {}
    if trace:
        names = cell.per_layer or sorted(
            f[:-3] for f in os.listdir(_readers_dir(bench_dir))
            if f.endswith(".py") and not f.startswith("_")
        )
        for name in names:
            reader = load_reader(name, bench_dir)
            value = reader.read(rec)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
    else:
        for name in cell.end_to_end or sorted(rec["end_to_end"]):
            if name in rec["end_to_end"]:
                value, unit = rec["end_to_end"][name]
                metrics[name] = {"value": float(value), "unit": unit}
    dev = dict(rec["device"], memory_peak_bytes=int(rec["memory_peak_bytes"]))
    out = {
        "correct": bool(rec["verdict"].correct),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    tr = rec.get("trace")
    if trace and tr:
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    return out


def rehearse() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.lib import spec

    fixtures = os.path.join(BENCH_DIR, "tests", "fixtures")
    for name in spec.list_cells(fixtures):
        for trace in (False, True):
            out = run_cell(name, 7, 2.0, trace, bench_dir=fixtures, allow_cpu=True)
            if not out["correct"] or not out["metrics"]:
                print(f"rehearsal FAILED in {name}: {out}")
                return 1
    print("rehearsal ok platform=cpu")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        return rehearse()
    if not args.workload or args.seconds is None:
        ap.error("--workload and --seconds are required")
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
