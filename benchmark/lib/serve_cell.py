"""A serving cell: the program's ``ServeEngine`` under the cell's traffic.

Set-up makes the weights from the seed, builds the engine, and runs every
program the window can reach (prefill rows 1, 2, 4 at each width the plan
can produce, and the decode step) before the load starts. The window opens
``ramp_s`` after the load does, on occupied slots and a primed prefix cache.
Requests are timed from when they were due on the engine's own clock.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import check, counts, device, kinds, models, peaks, stats, traffic as traffic_lib
from . import trace as trace_lib
from .spec import Cell, scratch_dir

POLL_S = 0.05


def _warm_up(engine, widths: list[int], rows: list[int], vocab: int, rng) -> int:  # noqa: ANN001
    """Run every prefill program (``rows`` x ``widths``) and the decode step.
    ``submit`` only appends until the loop starts, so 4 + 2 prompts of one
    width queued beforehand are admitted as 4 rows and then 2; one prompt at
    a time on the idle engine gives the 1-row programs."""
    from torchx_tpu.serve.engine import ServeRequest

    def prompt(width: int) -> list[int]:
        return rng.integers(0, vocab, width).tolist()

    many = sum(r for r in rows if r > 1)
    queued = [
        engine.submit(ServeRequest(prompt(w), max_new_tokens=1))
        for w in widths
        for _ in range(many)
    ]
    engine.start()
    for req in queued:
        if not req.wait(timeout=1200) or req.error:
            raise RuntimeError(f"warm-up request failed: {req.error or 'timeout'}")
    for w in widths:  # one row, and the decode step
        req = engine.submit(ServeRequest(prompt(w), max_new_tokens=3))
        if not req.wait(timeout=1200) or req.error:
            raise RuntimeError(f"warm-up request failed: {req.error or 'timeout'}")
    return len(queued) + len(widths)


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    allow_cpu: bool = False,
    control: Optional[str] = None,
) -> dict:
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache
    from torchx_tpu.serve.engine import ServeEngine, ServeRequest

    dev = device.require_chips(cell.chips, allow_cpu)
    setup_compilation_cache()
    compiles = device.CompileCounter()
    config, mix, dep = cell.config, cell.traffic, cell.config["deployment"]
    vocab = config["vocab_size"]
    cfg = models.program_config(config, max_seq=int(dep["max_seq"]))
    params = models.make_weights(config, seed)
    engine = ServeEngine(
        params,
        cfg,
        max_slots=int(dep["max_slots"]),
        block_size=int(dep["block_size"]),
        max_prefill_batch=int(dep["max_prefill_batch"]),
    )
    plan = traffic_lib.build_schedule(mix, seed, seconds, vocab)
    widths = traffic_lib.prefill_widths(plan, mix, engine.block_size)
    rows = sorted({1 << i for i in range(engine.max_prefill_batch.bit_length())
                   if 1 << i <= engine.max_prefill_batch})
    rng = np.random.default_rng([int(seed), 0x3A23])
    n_warm = _warm_up(engine, widths, rows, vocab, rng)
    print(f"serve: warmed rows {rows} x widths {widths} + decode with {n_warm} requests", flush=True)

    def submit(r: traffic_lib.Planned) -> None:
        r.request = engine.submit(ServeRequest(r.prompt, max_new_tokens=r.max_new_tokens))

    gen = traffic_lib.Generator(plan, submit)
    profile_dir = os.path.join(scratch_dir(cell), "trace")
    shutil.rmtree(profile_dir, ignore_errors=True)
    polls: list[dict] = []
    try:
        t_open = gen.start() + float(mix["arrivals"]["ramp_s"])
        t_close = t_open + seconds
        time.sleep(max(0.0, t_open - time.monotonic()))
        t_open_real = time.monotonic()
        s_open = engine.stats()
        trace_from, trace_to = t_open + 0.4 * seconds, t_open + 0.4 * seconds + float(mix.get("trace_s", 4))
        tracing, to_trace = False, trace
        while (now := time.monotonic()) < t_close:
            if to_trace and now >= trace_from:
                jax.profiler.start_trace(profile_dir)
                tracing, to_trace = True, False
            elif tracing and now >= trace_to:
                jax.profiler.stop_trace()
                tracing = False
            s = engine.stats()
            live = [
                r.request for r in plan
                if r.request is not None and r.request.t_first and not r.request.done.is_set()
            ]
            polls.append({
                "t": now,
                "occupancy": s["occupancy"],
                "queue_depth": s["queue_depth"],
                "active": s["active_slots"],
                "tokens_held": sum(len(q.prompt) + len(q.generated) for q in live),
                "tracing": tracing,
            })
            time.sleep(POLL_S)
        t_close_real = time.monotonic()
        s_close = engine.stats()
        if tracing:
            jax.profiler.stop_trace()
        # the state of every request as the window closes; nothing is waited for
        snap = [_snapshot(r, t_close_real) for r in plan]
    finally:
        gen.stop()
    memory_peak = device.memory_peak_bytes(cell.chips)
    compiled_in_window = compiles.between(t_open_real, t_close_real)
    engine_failed = engine.failed
    engine.stop()
    tokens_total = engine.tokens_out
    del engine
    window_s = t_close_real - t_open_real

    run_rec = _metrics(cell, dev, snap, polls, s_open, s_close, t_open, t_close_real,
                       window_s, t_open_real - t_start, mix, seconds)
    run_rec["memory_peak_bytes"] = memory_peak
    run_rec["trace"] = trace_lib.reduce_trace(profile_dir, cell.chips) if trace else None
    if dev["platform"] == "tpu":
        run_rec["counters"]["peak_hbm_bytes_per_s"] = peaks.peak(dev["kind"], "hbm_bytes_per_s")
        c = run_rec["counters"]
        c["serve_mfu_pct"] = 100.0 * c["forward_flops"] / (
            window_s * cell.chips * peaks.peak(dev["kind"], "bf16_flops_per_s"))
        # the one per-layer metric an untraced run can read: printed, since its result line has no place for it
        print(f"serve: model.serve_mfu_pct {c['serve_mfu_pct']:.4f}"
              f" = {c['forward_flops']:.6g} forward FLOPs ({c['tokens']} tokens out at a mean context of"
              f" {c['decode_context_mean']:.1f}, {c['prefilled_tokens']} prompt tokens prefilled over"
              f" {c['prefill_keys_mean']:.1f} keys; the engine's rounds counted {c['engine_prefill_tokens']})"
              f" in {window_s:.3f}s", flush=True)

    verdict = check.Verdict()
    if compiled_in_window:
        verdict.flag(f"{compiled_in_window} compilations inside the window")
    if engine_failed:
        verdict.flag(f"engine failed: {engine_failed}")
    if mix["arrivals"]["process"] == "backlog" and s_close["queue_depth"] == 0:
        verdict.flag("the backlog ran empty before the window closed")
    counted = sum(len(r.request.generated) for r in plan if r.request is not None)
    print(f"serve: engine counted {tokens_total} tokens in all, requests hold"
          f" {counted} + warm-up", flush=True)
    t0 = time.monotonic()
    run_rec["control"] = _check_outputs(verdict, params, config, snap, cell.check, seed, control,
                                        t_open, seconds)
    print(f"check: reference ran in {time.monotonic() - t0:.1f}s", flush=True)
    run_rec["verdict"] = verdict
    return run_rec


def _snapshot(r: traffic_lib.Planned, now: float) -> dict:
    q = r.request
    return {
        "t_due": r.t_due,
        "late_s": (r.t_submit - r.t_due) if r.t_submit else None,
        "refused": r.refused,
        "prompt": r.prompt,
        "submitted": q is not None,
        "error": getattr(q, "error", None),
        "done": bool(q is not None and q.done.is_set()),
        "t_first": getattr(q, "t_first", 0.0),
        "t_done": getattr(q, "t_done", 0.0),
        "generated": list(q.generated) if q is not None else [],
    }


def _metrics(cell, dev, snap, polls, s_open, s_close, t_open, t_close, window_s,
             setup_s, mix, seconds) -> dict:  # noqa: ANN001
    due = [s for s in snap if t_open <= s["t_due"] < t_close]
    backlog = mix["arrivals"]["process"] == "backlog"
    if backlog:  # everything was due before the window; all of it is the window's work
        due = snap
    half = t_open + seconds / 2
    failed = 0
    ttft, tpot = [], []
    for s in due:
        bad = bool(s["refused"] or s["error"])
        if not bad and not backlog and not s["t_first"] and s["t_due"] < half:
            bad = True  # due in the first half and still no first token
        failed += bad
        if backlog:
            continue
        if s["t_first"]:
            ttft.append((s["t_first"] - s["t_due"]) * 1e3)
        elif bad:
            ttft.append(seconds * 1e3)  # a failed request counts as the window's length
        if s["done"] and not s["error"] and len(s["generated"]) > 1:
            tpot.append((s["t_done"] - s["t_first"]) / (len(s["generated"]) - 1) * 1e3)
    tokens = s_close["tokens_out"] - s_open["tokens_out"]
    steps = s_close["steps"] - s_open["steps"]
    late = [s["late_s"] for s in due if s["late_s"] is not None]
    e2e = {
        "serve_tokens_per_s": (tokens / window_s, "tokens/s"),
        "setup_s": (setup_s, "s"),
    }
    if ttft:
        e2e["ttft_p95_ms"] = (stats.percentile(ttft, 95), "ms")
        e2e["ttft_p50_ms"] = (stats.percentile(ttft, 50), "ms")
    if tpot:
        e2e["tpot_p95_ms"] = (stats.percentile(tpot, 95), "ms")
        e2e["tpot_p50_ms"] = (stats.percentile(tpot, 50), "ms")
    ttft_seen = [(s["t_first"] - s["t_due"]) * 1e3 for s in due if s["t_first"]]
    pc_open, pc_close = s_open.get("prefix_cache") or {}, s_close.get("prefix_cache") or {}
    traced_polls = [p for p in polls if p["tracing"]] or polls
    done_in_window = sum(1 for s in snap if s["done"] and not s["error"] and t_open <= s["t_done"] <= t_close)
    held, active = sum(p["tokens_held"] for p in polls), sum(p["active"] for p in polls)
    admitted = sorted((s for s in snap if s["t_first"]), key=lambda s: s["t_first"])
    cached = cached_prefix_lens([s["prompt"] for s in admitted], int(cell.config["deployment"]["block_size"]))
    forward = forward_flops(
        cell.config, tokens, held / active if active else 0.0,
        [(len(s["prompt"]), c) for s, c in zip(admitted, cached) if t_open <= s["t_first"] <= t_close])
    forward["engine_prefill_tokens"] = s_close.get("prefill_tokens", 0) - s_open.get("prefill_tokens", 0)
    print(
        f"serve: {len(due)} requests due, {failed} failed, {done_in_window} finished in the window;"
        f" samples ttft {len(ttft)} tpot {len(tpot)}; {tokens} tokens, {steps} steps in {window_s:.3f}s;"
        f" generator late max {max(late, default=0) * 1e3:.2f} ms;"
        f" queue depth open {s_open['queue_depth']} mid {polls[len(polls) // 2]['queue_depth']}"
        f" close {s_close['queue_depth']}",
        flush=True,
    )
    return {
        "cell": cell,
        "device": dev,
        "attempted": len(due),
        "failed": failed,
        "end_to_end": e2e,
        "counters": {
            "window_s": window_s,
            "steps": steps,
            "tokens": tokens,
            "occupancy_mean": sum(p["occupancy"] for p in polls) / len(polls),
            "queue_depth_mean": sum(p["queue_depth"] for p in polls) / len(polls),
            "prefix_hit_tokens": pc_close.get("hit_tokens", 0) - pc_open.get("hit_tokens", 0),
            "prefix_lookup_tokens": pc_close.get("lookup_tokens", 0) - pc_open.get("lookup_tokens", 0),
            **forward,
            "traced_active_mean": sum(p["active"] for p in traced_polls) / len(traced_polls),
            "traced_tokens_held_mean": sum(p["tokens_held"] for p in traced_polls) / len(traced_polls),
            "generator_late_max_s": max(late, default=0.0),
            "done_in_window": done_in_window,
            "ttft_ms": ttft_seen if not backlog else [],
        },
    }


def cached_prefix_lens(prompts: list[list[int]], block: int) -> list[int]:
    """For each of ``prompts``, in the order their first tokens came: the whole
    blocks at its head that a prompt ahead of it began with too, never all of
    it (a token is left to prefill). What a prefix cache of whole blocks that
    forgets nothing serves a prompt from, so what is left is the work the
    prompt needed; the harness's own reckoning from the tokens it sent, no
    counter of the engine's."""
    seen: set[int] = set()
    out = []
    for p in prompts:
        chain, h = [], 0
        for k in range(len(p) // block):
            h = hash((h, tuple(p[k * block : (k + 1) * block])))
            chain.append(h)
        n = next((i for i, h in enumerate(chain) if h not in seen), len(chain))
        out.append(min(n, (len(p) - 1) // block) * block)
        seen.update(chain)
    return out


def forward_flops(config: dict, decoded: int, context: float, prefills: list[tuple[int, int]]) -> dict:
    """The forward FLOPs the window needed, by the kind's count
    (``kinds/<kind>.py::forward_flops_per_token``) and from what the harness
    itself holds: every token the window put out the whole forward at
    ``context``, the mean tokens a slot held over the polls (a request's first
    token, which its prefill made, among them: one in some hundreds); every
    prompt token it prefilled the forward less the head. ``prefills`` is
    ``(prompt, cached)`` tokens of each request whose first token came inside
    the window: it prefilled ``prompt - cached`` positions, the first of which
    attends ``cached + 1`` keys and the last ``prompt``, request by request
    (:func:`cached_prefix_lens`; not the prefix cache's ``hit_tokens``, which
    count every waiting request the engine walks past in a round and again in
    the next: 1.4 times the tokens kept in ``kimi``'s cell, and a later PR may
    move how often it plans).
    -> the counters ``model.serve_mfu_pct`` reads, with what they were made of."""
    prefilled = sum(p - c for p, c in prefills)
    keys = sum((p - c) * (p + c + 1) / 2 for p, c in prefills) / prefilled if prefilled else 0.0
    flops = (decoded * counts.forward_flops_per_token(config, context)
             + prefilled * counts.forward_flops_per_token(config, keys, head=False))
    return {"forward_flops": flops, "decode_context_mean": context, "prefilled_tokens": prefilled,
            "prefill_keys_mean": keys}


def sample_finished(snap: list[dict], n: int, seed: int, t_from: float, t_to: float) -> tuple[list[dict], int]:
    """``n`` of the requests the window finished: the longest; then those that
    were decoding together at one instant of ``[t_from, t_to)`` drawn from the
    seed, each of which sat in a slot of its own, so that every slot in use
    at that instant is in the sample; then others drawn from the seed.
    -> (the sample, how many of it were decoding together)"""
    done = [s for s in snap if s["done"] and not s["error"] and s["generated"]]
    if not done:
        return [], 0
    longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"]) + len(done[i]["generated"]))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    at = t_from + float(rng.random()) * (t_to - t_from)
    order = [i for i in rng.permutation(len(done)).tolist() if i != longest]
    together = [i for i in order if done[i]["t_first"] <= at < done[i]["t_done"]]
    others = [i for i in order if i not in together]
    picked = ([longest] + together + others)[:n]
    return [done[i] for i in picked], len(set(picked) & set(together))


def _padded_tokens(s: dict):  # noqa: ANN202
    """``[1, padded]`` prompt + served tokens of a sampled request, padded to
    a multiple of 128: few distinct shapes for the reference's layers to compile."""
    seq = s["prompt"] + s["generated"]
    return jnp.asarray([seq + [0] * (-len(seq) % 128)], jnp.int32)


SLICE = 512  # served positions the head multiplies at once: 512 x 131,072 float32 logits are 256 MiB


def _compare_slice(ref, config: dict, quant: Optional[str]):  # noqa: ANN001, ANN202
    """The comparison of one slice of served positions, to jit: the kind's
    ``head`` over ``rows`` (the stream at those positions), reduced where the
    logits are made to each position's gap, so that no ``[positions, vocab]``
    array outlives the program. ``other`` is the served tokens or, with
    ``quant``, the control's stream at the same positions, whose first choice
    under the lower precision then stands for the served token."""

    def compare(rows, top, other):  # noqa: ANN001, ANN202
        lg = ref.head(rows[None], top, config, None)[0]
        served = other if quant is None else jnp.argmax(ref.head(other[None], top, config, quant)[0], axis=-1)
        return jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]

    return compare


def served_gaps(params, config: dict, sample: list[dict], quant: Optional[str] = None) -> list[float]:  # noqa: ANN001
    """For each served token of each sampled request: how far its logit lies
    below the best of the kind's reference at that position, from one pass over
    prompt + served tokens. The reference's layers give the stream ahead of the
    head; the head runs over the served positions alone, ``SLICE`` at a time
    through one compiled program (one compile a cell, not one a length), each
    slice reduced to its gaps under the jit: the ``[padded, vocab]`` logits
    never exist, and the check needs what the layer pass needs whatever a
    request's length. With ``quant`` (the control) the served tokens are
    replaced by the ones the lower precision puts first at each position."""
    ref = kinds.reference(config)
    top = {k: w for k, w in params.items() if not isinstance(w, dict)}  # what the head reads: no group of layers
    compare, compiled = jax.jit(_compare_slice(ref, config, quant)), None
    before = device.memory_stats()
    gaps = []
    for s in sample:
        toks, n_g = _padded_tokens(s), len(s["generated"])
        x = ref.stream(params, toks, config)[0]
        x_low = ref.stream(params, toks, config, quant)[0] if quant is not None else None
        at = len(s["prompt"]) - 1  # the stream at position at + i predicts served token i
        for i in range(0, n_g, SLICE):
            n = min(SLICE, n_g - i)
            # a short last slice repeats its last position; what it gives for those is dropped
            idx = jnp.asarray(np.minimum(np.arange(SLICE) + at + i, at + i + n - 1), jnp.int32)
            rows = jnp.take(x, idx, axis=0)
            if quant is None:
                other = jnp.asarray((s["generated"][i : i + n] + [0] * SLICE)[:SLICE], jnp.int32)
            else:
                other = jnp.take(x_low, idx, axis=0)
            if compiled is None:
                compiled = compare.lower(rows, top, other).compile()
            gaps.extend(np.asarray(compiled(rows, top, other))[:n].tolist())
        del x, x_low  # not held through the next request's layer pass
    _print_memory(before, compiled)
    return gaps


def _print_memory(before: dict, compiled) -> None:  # noqa: ANN001
    """One line from which the check's need is read off a log. The peak is the
    process's and cannot be reset: it shows the check's need only where that
    passed the window's, so the slice's own program is printed beside it.
    ``temporary`` counts device memory alone: a slice's logits of up to some
    64 MiB (a vocabulary of 32,768 and under) the compiler keeps in the chip's
    fast memory (layout ``S(1)`` in the compiled text) and they read 0."""
    after = device.memory_stats()
    m = compiled.memory_analysis() if compiled is not None else None
    program = "not compiled" if m is None else (
        f"temporary {m.temp_size_in_bytes} arguments {m.argument_size_in_bytes} output {m.output_size_in_bytes}")
    print(f"check: memory in use {before['bytes_in_use']} as the check starts, peak {before['peak_bytes_in_use']}"
          f" -> {after['peak_bytes_in_use']}, limit {after['bytes_limit']}; the slice of {SLICE} positions: {program}"
          " (bytes)", flush=True)


SHARES_OVER = (0.25, 0.5, 1.0, 2.0)  # printed for every run; a cell compares the one it names


def _numbers(gaps: list[float], tolerance: float) -> dict:
    return {
        "served_logit_gap_max": max(gaps),
        "served_logit_gap_mean": sum(gaps) / len(gaps),
        "served_gap_over_share": sum(g > tolerance for g in gaps) / len(gaps),
    }


def _shares(gaps: list[float]) -> str:
    return ", ".join(f"over {t:g}: {sum(g > t for g in gaps) / len(gaps):.5f}" for t in SHARES_OVER)


def _check_outputs(verdict, params, config, snap, limits, seed, control, t_open, seconds) -> Optional[dict]:  # noqa: ANN001
    """Compare the sample with the reference; with ``control`` also return the
    numbers the lower precision gives in the program's place. The numbers:
    the widest and the mean gap of a served token's logit below the
    reference's best, and the share of served tokens whose gap is over the
    cell's ``served_gap_tolerance``. A cell compares those it gives a limit."""
    sample, together = sample_finished(snap, int(limits["sample_requests"]), seed,
                                       t_open, t_open + seconds / 2)
    if not sample:
        verdict.flag("no finished request to compare")
        return None
    gaps = served_gaps(params, config, sample)
    tolerance = float(limits.get("served_gap_tolerance", 1.0))
    print(f"check: {len(sample)} requests ({together} of them decoding together, a slot each),"
          f" {len(gaps)} served tokens,"
          f" longest {max(len(s['prompt']) + len(s['generated']) for s in sample)} tokens,"
          f" {sum(g > 0 for g in gaps)} tokens differ from the reference's first choice", flush=True)
    for name, value in _numbers(gaps, tolerance).items():
        if f"{name}_limit" in limits:
            verdict.compare(name, value, limits[f"{name}_limit"])
        else:
            print(f"check: {name} = {value:.6g} (not compared in this cell)", flush=True)
    qs = [stats.percentile(gaps, q) for q in (50, 90, 99)]
    print(f"check: gap p50 {qs[0]:.4g} p90 {qs[1]:.4g} p99 {qs[2]:.4g}; share {_shares(gaps)}", flush=True)
    if control:
        low = served_gaps(params, config, sample, control)
        numbers = _numbers(low, tolerance)
        print(f"control[{control}]: gap p90 {stats.percentile(low, 90):.4g} p99 {stats.percentile(low, 99):.4g};"
              f" share {_shares(low)}", flush=True)
        print(f"control[{control}]: " + ", ".join(f"{k} = {v:.6g}" for k, v in numbers.items())
              + f", {sum(g > 0 for g in low)} of {len(low)} tokens differ", flush=True)
        return numbers
    return None
