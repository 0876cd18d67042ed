#!/usr/bin/env python
"""Serving benchmark: KV-cache decode throughput for the generation stack.

Two halves:

* raw decode (``bench_decode``): steady-state decode tokens/sec for
  llama3_1b, bf16 vs int8 weight-only, across batch sizes — decode at
  batch b is HBM-bandwidth-bound, so the ceiling is roughly
  ``b * HBM_BW / (param_bytes + kv_bytes_per_row * b)``.

* serving under load (``bench_poisson``, the ``--poisson`` mode): an
  OPEN-LOOP Poisson load generator drives the real serving stack —
  arrivals follow seeded exponential gaps and are submitted on schedule
  regardless of completions, so queueing delay is measured instead of
  hidden (a closed loop self-throttles when the server falls behind).
  The same deterministic workload trace (same seed → identical prompts,
  arrival times and sampling seeds) is replayed against both engines at
  equal ``--max-batch``:

    - ``continuous``: the :mod:`torchx_tpu.serve.engine` slot-array
      engine (admit-on-free-slot, paged KV, per-step batching)
    - ``coalesce``: the legacy batch-to-completion coalescing batcher

  reporting decode tokens/sec, TTFT/TPOT p50/p99, and goodput (the
  fraction of requests whose TTFT meets ``--slo-ttft-ms``). For the
  coalescing baseline all tokens arrive when the batch completes, so its
  TTFT *is* its total latency — that asymmetry is the point of the
  comparison. ``--out`` writes the paired result (plus the paged-KV
  :meth:`~torchx_tpu.serve.kv_pool.PoolPlan.occupancy_report`) as one
  JSON document (see BENCH_SERVE_r01.json).

* shared-prefix serving (``--shared-prefix``): every prompt opens with
  the same system prompt (+ an exponential long-prompt tail) under the
  same open-loop Poisson arrivals, replayed against two topologies at
  equal per-engine HBM: TWO unified continuous engines with the prefix
  cache off (round-robin) vs ONE prefill engine (radix prefix cache on)
  streaming KV to ONE decode engine. Reports prefix-hit rate, TTFT
  p50/p99, decode tokens/sec, and cached-block occupancy (see
  BENCH_SERVE_r02.json).

Every mode measures the device, so every mode needs one: without a TPU the
script exits non-zero before it builds anything, and a failed point is a
failed run.

Usage:
    python scripts/bench_serving.py [--steps 128] [--batches 1,4,8]
    python scripts/bench_serving.py --poisson [--rate 8] [--requests 48] \
        [--max-batch 4] [--out BENCH_SERVE_r01.json]
    python scripts/bench_serving.py --shared-prefix [--shared-len 48] \
        [--out BENCH_SERVE_r02.json]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

# run from a bare checkout: the repo root is not on sys.path when this file
# is executed as a script
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def bench_decode(params, cfg, batch: int, steps: int, prompt_len: int = 32):
    """-> steady-state decode tokens/sec for one (params, batch)."""
    from torchx_tpu.models import generate as gen

    total = prompt_len + steps
    prompt = jnp.ones((batch, prompt_len), jnp.int32)
    rng = jax.random.PRNGKey(0)

    # reuse the server's own cached jitted fns (prefill + chunked decode)
    prefill, decode_chunk = gen._stream_fns(cfg, total, 0.0, chunk=steps)
    cache, tok, rng2 = prefill(params, prompt, rng)
    # warm decode compile
    cache, tok, rng2, toks = decode_chunk(params, cache, tok, rng2, prompt_len)
    jax.block_until_ready(toks)
    # time with the carry CHAINED through reps: feeding each rep's cache/
    # tok into the next forces real execution (repeat-identical dispatches
    # can be elided/cached by remote-device transports — measured 960k
    # "tokens/sec" without this, 5x over the HBM roofline)
    t0 = time.monotonic()
    reps = 3
    for _ in range(reps):
        cache, tok, rng2, toks = decode_chunk(
            params, cache, tok, rng2, prompt_len
        )
    # device_get, not block_until_ready: remote transports can treat the
    # latter as a metadata-ready check; fetching a VALUE from the end of
    # the chained carry forces the whole timed chain to have executed
    jax.device_get(toks[:, -1])
    dt = (time.monotonic() - t0) / reps
    return batch * steps / dt


def _percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _p99_label(sorted_vals: list) -> str:
    """Honesty label for the nearest-rank p99: below ~100 samples the
    nearest-rank 99th percentile IS the sample maximum, so say so
    (``p99~max(n=40)``) instead of implying tail resolution the sample
    cannot provide. Emitted next to every p99 in the BENCH JSON."""
    n = len(sorted_vals)
    if n and round(0.99 * (n - 1)) >= n - 1:
        return f"p99~max(n={n})"
    return f"p99(n={n})"


def bench_server(
    cfg_name: str, int8: bool, steps: int, clients: int, rounds: int = 5
):
    """Aggregate tokens/sec + per-request latency percentiles through the
    REAL HTTP server under concurrent load: `clients` threads each POST
    one /v1/generate per round; the batcher coalesces them into shared
    device batches.

    Deterministic protocol (the round-4 bf16 row measured 280-490 tok/s
    run-to-run because arrival jitter split dispatch groups differently
    each time): a timed round only COUNTS when its `clients` requests
    coalesced into exactly one device batch — split rounds are discarded
    and retried (up to 5x per round), so every reported number measures
    the same work. `rounds` >= 3 timed rounds are aggregated with their
    relative spread; per-request `timing` fields from the server give
    p50/p99 end-to-end latency, queue wait, and per-token latency.
    """
    import threading
    import urllib.request

    from torchx_tpu.apps import generate_server

    # A huge coalescing window makes grouping deterministic BY
    # CONSTRUCTION at no timing cost: the batcher dispatches the moment
    # the max_batch-th (== clients-th) request arrives, so the window
    # only ever waits for stragglers — it never pads a full round.
    server = generate_server.serve(
        cfg_name, port=0, int8=int8, batch_window_ms=5000.0, max_batch=clients
    )
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        body = json.dumps(
            {"tokens": [[1] * 16], "max_new_tokens": steps}
        ).encode()

        def one(errors: list, timings: list) -> None:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/generate",
                    data=body,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=600) as r:
                    payload = json.loads(r.read())
                if "tokens" not in payload:
                    raise RuntimeError(f"bad response: {payload}")
                timings.append(payload.get("timing") or {})
            except Exception as e:  # noqa: BLE001 - collected, fails the run
                errors.append(e)

        def round_trip() -> tuple[float, list]:
            errors: list = []
            timings: list = []
            t0 = time.monotonic()
            threads = [
                threading.Thread(target=one, args=(errors, timings))
                for _ in range(clients)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            if errors:
                # a failed round must not masquerade as a throughput number
                raise RuntimeError(
                    f"{len(errors)} request(s) failed: {errors[0]}"
                )
            return time.monotonic() - t0, timings

        round_trip()  # warm: compiles the coalesced batch-`clients` shape
        svc = server.service
        rates: list = []
        all_timings: list = []
        discarded = 0
        for _ in range(rounds):
            for _attempt in range(5):
                batches_before = svc.batches
                dt, timings = round_trip()
                if svc.batches - batches_before == 1:
                    rates.append(clients * steps / dt)
                    all_timings.extend(timings)
                    break
                discarded += 1  # split group: not the measured protocol
            else:
                raise RuntimeError(
                    "could not coalesce a clean single-batch round in 5"
                    " attempts; raise batch_window_ms"
                )
        totals = sorted(t["total_ms"] for t in all_timings if "total_ms" in t)
        queues = sorted(t["queue_ms"] for t in all_timings if "queue_ms" in t)
        mean_rate = sum(rates) / len(rates)
        spread = (max(rates) - min(rates)) / mean_rate if mean_rate else 0.0
        return {
            "metric": f"server aggregate decode tokens/sec ({cfg_name},"
            f" {'int8' if int8 else 'bf16'}, {clients} concurrent clients)",
            "value": round(mean_rate, 1),
            "unit": "tokens/sec",
            "rounds": len(rates),
            "spread_pct": round(spread * 100, 1),
            "discarded_split_rounds": discarded,
            "latency_ms": {
                "p50_total": round(_percentile(totals, 0.50), 1),
                "p99_total": round(_percentile(totals, 0.99), 1),
                "p50_queue": round(_percentile(queues, 0.50), 1),
                "p99_queue": round(_percentile(queues, 0.99), 1),
                "p99_label": _p99_label(totals),
                "p50_per_token": round(
                    _percentile(totals, 0.50) / steps, 2
                ),
            },
            "batched_sequences": svc.batched_sequences,
        }
    finally:
        server.shutdown()
        server.server_close()
        server.service.close()


def bench_stream_ttft(cfg_name: str, int8: bool, steps: int, samples: int = 8):
    """Real time-to-first-token via the streaming endpoint (batch 1; the
    non-streaming batched path delivers all tokens at once, so its
    'TTFT' IS the total latency — this measures the latency-optimized
    path the server trades coalescing away for)."""
    import threading
    import urllib.request

    from torchx_tpu.apps import generate_server

    server = generate_server.serve(cfg_name, port=0, int8=int8)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        body = json.dumps(
            {
                "tokens": [[1] * 16],
                "max_new_tokens": steps,
                "stream": True,
                "stream_chunk": 1,
            }
        ).encode()

        def one() -> tuple[float, float]:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=body,
                headers={"Content-Type": "application/json"},
            )
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=600) as r:
                first = None
                for line in r:
                    if line.strip():
                        if first is None:
                            first = time.monotonic() - t0
                return first, time.monotonic() - t0

        one()  # warm compile
        ttfts, totals = [], []
        for _ in range(samples):
            first, total = one()
            ttfts.append(first * 1e3)
            totals.append(total * 1e3)
        ttfts.sort()
        totals.sort()
        return {
            "metric": f"stream TTFT ms ({cfg_name},"
            f" {'int8' if int8 else 'bf16'}, batch 1)",
            "p50_ttft_ms": round(_percentile(ttfts, 0.50), 1),
            "p99_ttft_ms": round(_percentile(ttfts, 0.99), 1),
            "p99_label": _p99_label(ttfts),
            "p50_per_token_ms": round(
                _percentile(totals, 0.50) / steps, 2
            ),
            "samples": samples,
        }
    finally:
        server.shutdown()
        server.server_close()
        server.service.close()


def make_workload(
    *,
    num_requests: int,
    rate_rps: float,
    max_new: int,
    prompt_lens: tuple[int, ...],
    seed: int,
    vocab: int,
) -> list[dict]:
    """Deterministic open-loop trace: one dict per request with its
    arrival offset (cumulative seeded exponential gaps — a Poisson
    process), prompt, and per-request sampling seed. Replaying the same
    seed against both engines makes the comparison apples-to-apples."""
    rng = random.Random(seed)
    trace = []
    t = 0.0
    for i in range(num_requests):
        t += rng.expovariate(rate_rps)
        plen = rng.choice(prompt_lens)
        trace.append(
            {
                "arrival_s": t,
                "prompt": [rng.randrange(1, vocab) for _ in range(plen)],
                "max_new": max_new,
                "seed": seed * 1000 + i,
            }
        )
    return trace


def make_shared_prefix_workload(
    *,
    num_requests: int,
    rate_rps: float,
    max_new: int,
    shared_len: int,
    mean_tail: int,
    max_tail: int,
    seed: int,
    vocab: int,
) -> list[dict]:
    """Deterministic shared-prefix trace: every prompt opens with the SAME
    ``shared_len``-token system prompt, followed by a per-request tail
    whose length is exponentially distributed (a long-prompt tail) —
    the workload shape that motivates prefix caching. Arrivals are the
    same seeded Poisson process :func:`make_workload` uses."""
    rng = random.Random(seed)
    shared = [rng.randrange(1, vocab) for _ in range(shared_len)]
    trace = []
    t = 0.0
    for i in range(num_requests):
        t += rng.expovariate(rate_rps)
        tail_len = min(max_tail, 1 + int(rng.expovariate(1.0 / mean_tail)))
        trace.append(
            {
                "arrival_s": t,
                "prompt": shared
                + [rng.randrange(1, vocab) for _ in range(tail_len)],
                "max_new": max_new,
                "seed": seed * 1000 + i,
            }
        )
    return trace


def bench_shared_prefix(
    cfg_name: str,
    mode: str,
    trace: list[dict],
    *,
    max_batch: int,
    slo_ttft_ms: float,
    block_size: int = 16,
    num_blocks: int | None = None,
    temperature: float = 0.7,
) -> dict:
    """Replay one shared-prefix trace against one serving topology at a
    fixed per-engine HBM budget (same ``max_batch`` / ``num_blocks``):

    * ``unified``: TWO unified continuous engines, prefix cache OFF,
      round-robin — the pre-disaggregation baseline at equal chip count;
    * ``disagg``: ONE prefill engine (radix prefix cache ON) streaming
      KV to ONE decode engine over an in-process transfer — same two
      chips, split by phase.

    -> scorecard: decode tokens/sec, TTFT p50/p99, prefix-hit rate, and
    cached-block occupancy."""
    from torchx_tpu.apps.generate_server import GenerateService
    from torchx_tpu.serve.kv_transfer import LocalTransfer

    services: list[GenerateService] = []
    try:
        if mode == "unified":
            services = [
                GenerateService(
                    cfg_name,
                    engine="continuous",
                    max_batch=max_batch,
                    block_size=block_size,
                    num_blocks=num_blocks,
                    enable_prefix_cache=False,
                )
                for _ in range(2)
            ]

            def submit(i: int, req: dict):
                return services[i % 2].generate_timed(
                    [req["prompt"]],
                    req["max_new"],
                    temperature=temperature,
                    seed=req["seed"],
                )

            cache_engine = None
        elif mode == "disagg":
            dec = GenerateService(
                cfg_name,
                engine="continuous",
                serve_role="decode",
                max_batch=max_batch,
                block_size=block_size,
                num_blocks=num_blocks,
            )
            pre = GenerateService(
                cfg_name,
                engine="continuous",
                serve_role="prefill",
                kv_transfer="local",
                max_batch=max_batch,
                block_size=block_size,
                num_blocks=num_blocks,
            )
            pre._transfer = LocalTransfer({"decode": dec.handle_kv_payload})
            services = [pre, dec]

            def submit(i: int, req: dict):
                return pre.generate_timed(
                    [req["prompt"]],
                    req["max_new"],
                    temperature=temperature,
                    seed=req["seed"],
                )

            cache_engine = pre._engine
        else:
            raise ValueError(f"unknown mode {mode!r}")

        # warm every observed prompt length twice outside the timed
        # window: the first pass compiles the cold bucket (and seeds the
        # shared prefix into the cache where enabled), the second
        # compiles the cached-suffix bucket the steady state runs in
        for plen in sorted({len(r["prompt"]) for r in trace}):
            warm = trace[0]["prompt"][:plen]
            for _ in range(2):
                for i in range(len(services) if mode == "unified" else 1):
                    submit(i, {
                        "prompt": warm,
                        "max_new": trace[0]["max_new"],
                        "seed": 0,
                    })
        hits0 = misses0 = 0
        if cache_engine is not None:
            st0 = cache_engine.stats()["prefix_cache"]
            hits0, misses0 = st0["hits"], st0["misses"]

        results: list[dict] = [None] * len(trace)  # type: ignore[list-item]

        def one(i: int, req: dict) -> None:
            try:
                seqs, timing = submit(i, req)
                results[i] = {
                    "ok": True,
                    "generated": len(seqs[0]) - len(req["prompt"]),
                    "done_at": time.monotonic(),
                    **timing,
                }
            except Exception as e:  # noqa: BLE001 - scored as a miss
                results[i] = {"ok": False, "error": str(e)[:200]}

        t0 = time.monotonic()
        workers = []
        for i, req in enumerate(trace):
            delay = t0 + req["arrival_s"] - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=one, args=(i, req), daemon=True)
            th.start()
            workers.append(th)
        for th in workers:
            th.join(timeout=600)
        done = [r for r in results if r and r.get("ok")]
        failed = len(trace) - len(done)
        if not done:
            raise RuntimeError(f"all {len(trace)} requests failed")
        duration = max(r["done_at"] for r in done) - t0
        total_tokens = sum(r["generated"] for r in done)
        ttfts = sorted(r["ttft_ms"] for r in done)
        good = sum(1 for r in done if r["ttft_ms"] <= slo_ttft_ms)
        out = {
            "mode": mode,
            "requests": len(trace),
            "failed": failed,
            "duration_s": round(duration, 2),
            "decode_tokens_per_sec": round(total_tokens / duration, 1),
            "ttft_ms": {
                "p50": round(_percentile(ttfts, 0.50), 1),
                "p99": round(_percentile(ttfts, 0.99), 1),
                "p99_label": _p99_label(ttfts),
            },
            "goodput": round(good / len(trace), 3),
            "slo_ttft_ms": slo_ttft_ms,
        }
        if cache_engine is not None:
            st = cache_engine.stats()
            pc = st["prefix_cache"]
            hits, misses = pc["hits"] - hits0, pc["misses"] - misses0
            out["prefix_cache"] = {
                "hit_rate": round(hits / max(1, hits + misses), 3),
                "hits": hits,
                "misses": misses,
                "token_hit_rate": pc["token_hit_rate"],
                "cached_blocks": pc["cached_blocks"],
                "cached_block_occupancy": round(
                    pc["cached_blocks"]
                    / max(1, st["kv_blocks_used"] + st["kv_blocks_free"]),
                    3,
                ),
                "evictions": pc["evictions"],
            }
        return out
    finally:
        for s in services:
            s.close()


def run_shared_prefix_comparison(args) -> dict:
    """Unified (2 engines, no cache) vs disaggregated+cache (prefill +
    decode) on one shared-prefix trace at equal per-engine HBM — the
    --shared-prefix mode, one JSON document (BENCH_SERVE_r02.json)."""
    from torchx_tpu.models import llama
    from torchx_tpu.serve.kv_pool import plan_pool

    platform = jax.devices()[0].platform
    cfg_name = args.config
    cfg = llama.CONFIGS[cfg_name]()
    max_new = min(args.steps, cfg.max_seq // 8)
    shared_len = min(args.shared_len, cfg.max_seq // 2)
    max_tail = max(4, cfg.max_seq - shared_len - max_new - 1)
    trace = make_shared_prefix_workload(
        num_requests=args.requests,
        rate_rps=args.rate,
        max_new=max_new,
        shared_len=shared_len,
        mean_tail=min(12, max_tail),
        max_tail=max_tail,
        seed=args.seed,
        vocab=cfg.vocab_size,
    )
    doc = {
        "bench": "shared-prefix serving: unified vs disaggregated+cache"
        " at equal per-engine HBM (2 engines each)",
        "config": cfg_name,
        "platform": platform,
        "workload": {
            "requests": args.requests,
            "rate_rps": args.rate,
            "max_new_tokens": max_new,
            "shared_prefix_len": shared_len,
            "prompt_lens": sorted({len(r["prompt"]) for r in trace}),
            "seed": args.seed,
            "max_batch": args.max_batch,
        },
        "modes": {},
    }
    for mode in ("unified", "disagg"):
        doc["modes"][mode] = bench_shared_prefix(
            cfg_name,
            mode,
            trace,
            max_batch=args.max_batch,
            slo_ttft_ms=args.slo_ttft_ms,
        )
        print(json.dumps(doc["modes"][mode]))
    uni, dis = doc["modes"]["unified"], doc["modes"]["disagg"]
    doc["comparison"] = {
        "p99_ttft_reduction": round(
            1 - dis["ttft_ms"]["p99"] / uni["ttft_ms"]["p99"], 3
        ),
        "decode_tokens_per_sec_ratio": round(
            dis["decode_tokens_per_sec"] / uni["decode_tokens_per_sec"], 2
        ),
        "prefix_hit_rate": dis["prefix_cache"]["hit_rate"],
        "goodput_delta": round(dis["goodput"] - uni["goodput"], 3),
    }
    # paged-vs-dense (the HBM half of the story), same as the r01 report,
    # plus what the cache held at steady state
    doc["kv_pool_occupancy"] = plan_pool(cfg).occupancy_report()
    print(json.dumps(doc["comparison"]))
    return doc


def bench_poisson(
    cfg_name: str,
    engine: str,
    trace: list[dict],
    *,
    max_batch: int,
    slo_ttft_ms: float,
    block_size: int = 16,
    batch_window_ms: float = 25.0,
    temperature: float = 0.7,
) -> dict:
    """Replay one workload trace open-loop against one engine; -> the
    serving scorecard (tokens/sec, TTFT/TPOT p50/p99, goodput)."""
    from torchx_tpu.apps.generate_server import GenerateService

    svc = GenerateService(
        cfg_name,
        engine=engine,
        max_batch=max_batch,
        batch_window_ms=batch_window_ms,
        block_size=block_size,
    )
    try:
        # warm every (prompt_len, max_new) compile outside the timed window
        for plen in sorted({len(r["prompt"]) for r in trace}):
            svc.generate(
                [list(range(1, plen + 1))],
                trace[0]["max_new"],
                temperature=temperature,
            )

        results: list[dict] = [None] * len(trace)  # type: ignore[list-item]

        def one(i: int, req: dict) -> None:
            try:
                seqs, timing = svc.generate_timed(
                    [req["prompt"]],
                    req["max_new"],
                    temperature=temperature,
                    seed=req["seed"],
                )
                results[i] = {
                    "ok": True,
                    "generated": len(seqs[0]) - len(req["prompt"]),
                    "done_at": time.monotonic(),
                    **timing,
                }
            except Exception as e:  # noqa: BLE001 - scored as a miss
                results[i] = {"ok": False, "error": str(e)[:200]}

        # open loop: submit on the trace's schedule, never waiting for
        # completions — if the server falls behind, the backlog (and the
        # latency it causes) is part of the measurement
        t0 = time.monotonic()
        workers = []
        for i, req in enumerate(trace):
            delay = t0 + req["arrival_s"] - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=one, args=(i, req), daemon=True)
            th.start()
            workers.append(th)
        for th in workers:
            th.join(timeout=600)
        done = [r for r in results if r and r.get("ok")]
        failed = len(trace) - len(done)
        if not done:
            raise RuntimeError(f"all {len(trace)} requests failed")
        duration = max(r["done_at"] for r in done) - t0
        total_tokens = sum(r["generated"] for r in done)
        ttfts = sorted(r["ttft_ms"] for r in done)
        # per-output-token latency after the first token; the coalescing
        # baseline delivers everything at once, so its per-token cost is
        # total/steps — there is no cheaper number to give it
        tpots = sorted(
            (r["total_ms"] - r["ttft_ms"]) / max(1, r["generated"] - 1)
            if r["total_ms"] > r["ttft_ms"]
            else r["total_ms"] / max(1, r["generated"])
            for r in done
        )
        good = sum(1 for r in done if r["ttft_ms"] <= slo_ttft_ms)
        return {
            "engine": engine,
            "requests": len(trace),
            "failed": failed,
            "duration_s": round(duration, 2),
            "decode_tokens_per_sec": round(total_tokens / duration, 1),
            "ttft_ms": {
                "p50": round(_percentile(ttfts, 0.50), 1),
                "p99": round(_percentile(ttfts, 0.99), 1),
                "p99_label": _p99_label(ttfts),
            },
            "tpot_ms": {
                "p50": round(_percentile(tpots, 0.50), 2),
                "p99": round(_percentile(tpots, 0.99), 2),
                "p99_label": _p99_label(tpots),
            },
            "goodput": round(good / len(trace), 3),
            "slo_ttft_ms": slo_ttft_ms,
        }
    finally:
        svc.close()


def run_poisson_comparison(args) -> dict:
    """Both engines, one trace, one JSON document (the --poisson mode)."""
    from torchx_tpu.models import llama
    from torchx_tpu.serve.kv_pool import plan_pool

    platform = jax.devices()[0].platform
    cfg_name = args.config
    cfg = llama.CONFIGS[cfg_name]()
    max_new = min(args.steps, cfg.max_seq // 4)
    prompt_lens = tuple(
        p for p in (4, 8, 12) if p + max_new <= cfg.max_seq
    ) or (4,)
    trace = make_workload(
        num_requests=args.requests,
        rate_rps=args.rate,
        max_new=max_new,
        prompt_lens=prompt_lens,
        seed=args.seed,
        vocab=cfg.vocab_size,
    )
    doc = {
        "bench": "serving under open-loop Poisson load",
        "config": cfg_name,
        "platform": platform,
        "workload": {
            "requests": args.requests,
            "rate_rps": args.rate,
            "max_new_tokens": max_new,
            "prompt_lens": list(prompt_lens),
            "seed": args.seed,
            "max_batch": args.max_batch,
        },
        "engines": {},
    }
    for engine in ("coalesce", "continuous"):
        doc["engines"][engine] = bench_poisson(
            cfg_name,
            engine,
            trace,
            max_batch=args.max_batch,
            slo_ttft_ms=args.slo_ttft_ms,
        )
        print(json.dumps(doc["engines"][engine]))
    cont, coal = doc["engines"]["continuous"], doc["engines"]["coalesce"]
    doc["comparison"] = {
        "decode_tokens_per_sec_speedup": round(
            cont["decode_tokens_per_sec"] / coal["decode_tokens_per_sec"], 2
        ),
        "p99_ttft_reduction": round(
            1 - cont["ttft_ms"]["p99"] / coal["ttft_ms"]["p99"], 3
        ),
        "goodput_delta": round(cont["goodput"] - coal["goodput"], 3),
    }
    # the paged-KV half of the story: concurrency at the same HBM budget
    doc["kv_pool_occupancy"] = plan_pool(cfg).occupancy_report()
    print(json.dumps(doc["comparison"]))
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--batches", default="1,4,8")
    ap.add_argument("--config", default="llama3_1b")
    ap.add_argument(
        "--server",
        action="store_true",
        help="also measure aggregate throughput through the HTTP server",
    )
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument(
        "--poisson",
        action="store_true",
        help="open-loop Poisson comparison: continuous engine vs"
        " coalescing baseline at equal --max-batch",
    )
    ap.add_argument(
        "--shared-prefix",
        action="store_true",
        help="shared-prefix comparison: unified continuous engines vs"
        " disaggregated prefill/decode with the radix prefix cache, at"
        " equal per-engine HBM",
    )
    ap.add_argument(
        "--shared-len",
        type=int,
        default=48,
        help="length of the common system prompt in the shared-prefix"
        " workload (tokens)",
    )
    ap.add_argument("--rate", type=float, default=8.0, help="arrivals/sec")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--slo-ttft-ms", type=float, default=1000.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write the comparison JSON here")
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(
            f"bench_serving: no TPU (jax found {platform!r}); a number from"
            " another backend is not a serving speed"
        )
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    setup_compilation_cache()

    if args.poisson or args.shared_prefix:
        doc = (
            run_shared_prefix_comparison(args)
            if args.shared_prefix
            else run_poisson_comparison(args)
        )
        if args.out:
            with open(args.out, "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
            print(f"wrote {args.out}")
        return

    from torchx_tpu.models import llama
    from torchx_tpu.ops import quant

    cfg_name = args.config
    cfg = llama.CONFIGS[cfg_name](max_seq=512, remat=False)
    # keep the decode window inside the config's declared context
    # (generate_stream enforces the same invariant)
    prompt_len = 32
    args.steps = min(args.steps, cfg.max_seq - prompt_len)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: x.astype(cfg.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        params,
    )
    qparams = quant.quantize_params(params)

    for batch in [int(b) for b in args.batches.split(",")]:
        for name, p in (("bf16", params), ("int8", qparams)):
            tps = bench_decode(p, cfg, batch, args.steps)
            print(
                json.dumps(
                    {
                        "metric": f"decode tokens/sec ({cfg_name}, {name},"
                        f" batch={batch}, {platform})",
                        "value": round(tps, 1),
                        "unit": "tokens/sec",
                        "per_row": round(tps / batch, 1),
                    }
                )
            )

    if args.server:
        for int8 in (False, True):
            print(
                json.dumps(
                    bench_server(cfg_name, int8, args.steps, args.clients)
                )
            )
            print(json.dumps(bench_stream_ttft(cfg_name, int8, args.steps)))


if __name__ == "__main__":
    main()
