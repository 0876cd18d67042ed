"""What the profiler costs the serving loop, and what its Python tracer adds.

A one-off measurement (PR 24), not a tool of the benchmark: it drives a serving
cell's engine and traffic as ``benchmark/lib/serve_cell.py`` does and, in one
process and on one seed, reads the decode step's period

* with no profiler session (``engine.stats()`` deltas),
* under a session with ``ProfileOptions.python_tracer_level = 0``,
* under a session with the profiler's defaults (what ``benchmark/run.py
  --trace 1`` uses today),

with untraced stretches between them. For each traced stretch it prints the
device's idle share, the readings of ``benchmark/lib/host_spans.py`` and the
by-scope shares of ``benchmark/lib/scopes.py``. It also records a short
trace, Python tracer off: ``benchmark/tests/data/serve_spans.xplane.pb`` is
PR 24's (a few decode steps and one admission round), and
``benchmark/tests/data/serve_runs.xplane.pb`` PR 38's (the engine that keeps a
step in flight: a few decode turns and two rounds, each behind a step). Since
PR 40 an admission is no program: the short trace it waits for now holds two
steps that carried a chunk of a prompt and one that carried none.

    chiprun -- python3 scripts/serve_trace_tax.py --workload mixtral8x7b-serve-backlog --seed 7

Everything it writes goes under ``chiprun_out/trace_tax/<workload>/``. It needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="mixtral8x7b-serve-backlog")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--stretch", type=float, default=4.0, help="seconds of each traced stretch")
    ap.add_argument("--fixture-s", type=float, default=0.25, help="seconds of the short recorded trace")
    ap.add_argument("--bench-dir", default=None, help="another benchmark directory (the tests' fixtures)")
    ap.add_argument("--allow-cpu", action="store_true", help="rehearse on the CPU: no device numbers")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.lib import device, host_spans, models, program_runs, scopes, serve_cell, spec
    from benchmark.lib import trace as trace_lib
    from benchmark.lib import traffic as traffic_lib
    from torchx_tpu.obs import hot
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache
    from torchx_tpu.serve.engine import ServeEngine, ServeRequest

    out_dir = os.path.join(REPO, "chiprun_out", "trace_tax", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    print("env JAX_COMPILATION_CACHE_DIR =", os.environ.get("JAX_COMPILATION_CACHE_DIR"), flush=True)

    cell = spec.load_cell(args.workload, args.bench_dir or spec.BENCH_DIR)
    dev = device.require_chips(cell.chips, args.allow_cpu)
    setup_compilation_cache()
    config, mix, dep = cell.config, cell.traffic, cell.config["deployment"]
    cfg = models.program_config(config, max_seq=int(dep["max_seq"]))
    params = models.make_weights(config, args.seed)
    engine = ServeEngine(params, cfg, max_slots=int(dep["max_slots"]), block_size=int(dep["block_size"]),
                         max_prefill_batch=int(dep["max_prefill_batch"]))
    plan = traffic_lib.build_schedule(mix, args.seed, 45.0, config["vocab_size"])
    widths = traffic_lib.prefill_widths(plan, mix, engine.block_size)
    rows = sorted({1 << i for i in range(engine.max_prefill_batch.bit_length())
                   if 1 << i <= engine.max_prefill_batch})
    rng = np.random.default_rng([args.seed, 0x3A23])
    serve_cell._warm_up(engine, widths, rows, config["vocab_size"], rng)
    print(f"warmed rows {rows} x widths {widths}; device {dev}", flush=True)

    def submit(r):  # noqa: ANN001, ANN202
        r.request = engine.submit(ServeRequest(r.prompt, max_new_tokens=r.max_new_tokens))

    gen = traffic_lib.Generator(plan, submit)
    gen.start()
    time.sleep(float(mix["arrivals"]["ramp_s"]))

    def stretch(label: str, seconds: float, options=None, trace_dir=None) -> dict:  # noqa: ANN001
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        s0, t0 = engine.stats(), time.monotonic()
        time.sleep(seconds)
        s1, t1 = engine.stats(), time.monotonic()
        if trace_dir:
            jax.profiler.stop_trace()
        steps = s1["steps"] - s0["steps"]
        rec = {
            "label": label,
            "seconds": t1 - t0,
            "steps": steps,
            "stats_step_ms": (t1 - t0) / max(steps, 1) * 1e3,
            "tokens_per_s": (s1["tokens_out"] - s0["tokens_out"]) / (t1 - t0),
            "requests_done": s1["requests_done"] - s0["requests_done"],
            "queue_depth": s1["queue_depth"],
        }
        if trace_dir:
            rec.update(read_trace(trace_dir))
        print(json.dumps(rec), flush=True)
        return rec

    def read_trace(trace_dir: str) -> dict:
        path = trace_lib.find_xplane(trace_dir)
        tr = trace_lib.reduce_trace(trace_dir, cell.chips)
        if tr is None:  # a CPU rehearsal: no device plane
            return {"file_mb": os.path.getsize(path) / 1e6, "engine_spans": len(host_spans.engine_spans(path))}
        r = host_spans.read(path)
        out = {
            "file_mb": os.path.getsize(path) / 1e6,
            "idle_pct": 100.0 * (1 - tr["busy_s"] / tr["window_s"]),
            "window_s": tr["window_s"],
            "module_median_ms": {k: v * 1e3 for k, v in tr["module_median_s"].items()},
            "idle_gaps": tr["idle_gaps"][:4],
        }
        if r is None:
            return out
        decode = r.named(hot.SERVE_DECODE)
        admit = r.named(hot.SERVE_ADMIT)
        split = program_runs.idle_split(path)

        def children_ms(spans, parent):  # noqa: ANN001, ANN202
            return {c: 1e3 * sum(s.child_time(c) for s in spans) / max(len(spans), 1)
                    for c in hot.SERVE_SPAN_TREE[parent]}

        out.update({
            "decode_spans": len(decode),
            "admit_spans": len(admit),
            "chunk_spans": sum(int(s.attrs.get("chunk_tokens", 0)) > 0 for s in decode),
            "traced_step_ms": host_spans.traced_step_ms(r),
            "host_ms_per_step": host_spans.host_ms_per_step(r),
            "admit_host_ms": host_spans.admit_host_ms(r),
            "idle_by_class_pct": {k: 100.0 * v / split.window_s for k, v in split.by_class.items()} if split else None,
            "decode_children_ms": children_ms(decode, hot.SERVE_DECODE),
            "admit_children_ms": children_ms(admit, hot.SERVE_ADMIT),
            "coverage": {p: host_spans.coverage(r, p) for p in hot.SERVE_SPAN_TREE},
            "idle_span_s": sum(s.duration for s in r.named(hot.SERVE_IDLE)),
        })
        planes = scopes.read_planes(path)
        for module in tr["module_median_s"]:
            ops = scopes.program_ops(planes, module)
            if ops:
                b = scopes.breakdown(ops, hot.DEVICE_SCOPES)
                out[f"scopes.{module}"] = {
                    "scoped_share": b["scoped"] / b["total"],
                    "by_scope_share": {k: v / b["total"] for k, v in b["by_scope"].items() if v > 0},
                    "unscoped_share": {k: v / b["total"] for k, v in list(b["unscoped"].items())[:8]},
                }
        return out

    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0
    results = [stretch("untraced", 2 * args.stretch)]
    results.append(stretch("tracer_off", args.stretch, quiet, os.path.join(out_dir, "tracer_off")))
    results.append(stretch("untraced", args.stretch))
    results.append(stretch("tracer_default", args.stretch, None, os.path.join(out_dir, "tracer_default")))
    results.append(stretch("untraced", args.stretch))
    results.append(stretch("tracer_off", args.stretch, quiet, os.path.join(out_dir, "tracer_off_2")))
    results.append(stretch("untraced", args.stretch))

    # the short recorded trace: try until one holds two steps that carried a chunk and one that carried none
    fixture_dir = os.path.join(out_dir, "fixture")
    for attempt in range(24):
        rec = stretch(f"fixture.{attempt}", args.fixture_s, quiet, fixture_dir)
        if rec.get("chunk_spans", 0) >= 2 and rec.get("decode_spans", 0) >= rec.get("chunk_spans", 0) + 1:
            break
    results.append(rec)

    gen.stop()
    failed = engine.failed
    engine.stop()
    # of the long traces only the first quiet one comes back, if it is small
    for name in ("tracer_default", "tracer_off_2"):
        shutil.rmtree(os.path.join(out_dir, name))
    kept = os.path.join(out_dir, "tracer_off")
    if sum(os.path.getsize(os.path.join(r, f)) for r, _, fs in os.walk(kept) for f in fs) > 48e6:
        shutil.rmtree(kept)
    with open(os.path.join(out_dir, "results.json"), "w") as f:
        json.dump({"device": dev, "workload": args.workload, "seed": args.seed, "engine_failed": failed,
                   "results": results}, f, indent=1)
    print("engine failed:", failed)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
