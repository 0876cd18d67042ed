"""Benchmark: Llama training throughput on the available hardware.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

On a TPU chip this trains Llama-3.2-1B (bf16, remat, splash attention)
through the real input pipeline and reports tokens/sec/chip and MFU;
``vs_baseline`` is MFU relative to the 45%-MFU north-star from
BASELINE.json (the reference itself publishes no numbers — it is a
launcher; see BASELINE.md). Also reported: launch-to-first-step (process
start -> step-1 done), the other north-star metric.

It measures the device, so it needs one: without a TPU it exits non-zero,
and so does a failure in any leg. One process does everything — nothing
else may hold the chip while it runs. ``chip_smoke.py`` is the quick check
that the system starts on the chip at all.
"""

from __future__ import annotations

import json
import os
import sys
import time

CORPUS_PATH = "/tmp/tpx_bench_corpus.bin"
CORPUS_TOKENS = 16_000_000


def _ensure_corpus() -> str:
    """Deterministic random-token corpus for the TokenDataset pipeline
    (memmap + per-process shard + double-buffer prefetch — the REAL input
    path, exercised so the bench measures input overlap, not just math).
    Written once, reused across runs."""
    import numpy as np

    want_bytes = CORPUS_TOKENS * 4
    if (
        not os.path.exists(CORPUS_PATH)
        or os.path.getsize(CORPUS_PATH) != want_bytes
    ):
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 128256, size=CORPUS_TOKENS, dtype=np.uint32)
        toks.tofile(CORPUS_PATH)
    return CORPUS_PATH


def main() -> None:
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(
            f"bench: no TPU (jax found {platform!r}); a number from another"
            " backend is not a training speed"
        )

    # bench under a trace id so the trainer emits the launch.breakdown
    # span family + first-step heartbeat into the obs JSONL (inspect with
    # `tpx trace <id>` / the launch-stage histogram)
    from torchx_tpu import settings as tpx_settings
    from torchx_tpu.obs import trace as obs_trace

    os.environ.setdefault(tpx_settings.ENV_TPX_TRACE_ID, obs_trace.new_trace_id())

    from torchx_tpu.train.run import train
    from torchx_tpu.models import llama

    # 32 steps, log every 8: each log point is a block_until_ready
    # fence that breaks dispatch pipelining — logging every 4 steps
    # measured ~1.7pp of MFU lower than every 8 (r4, see
    # docs/performance.md)
    seq, steps, log_every = 2048, 32, 8
    # (remat_policy, batch, cfg overrides) in preference order; measured
    # on v5e-1: dots@2 with the splash kernel + 512/512 tiles (the
    # llama3_1b defaults) and whole-sequence CE chunking hits 52.4%
    # mean MFU on the REAL input pipeline; the smaller loss chunk is
    # the fallback when the [batch, seq, vocab] f32 chunk doesn't fit
    # (see docs/performance.md)
    # "auto" resolves per-launch via compiled.memory_analysis(): it
    # upgrades to dots_attn (no attention recompute in backward) when
    # the activation footprint fits HBM, and the trial compile IS the
    # winner's compile (persistent XLA cache), so launch latency pays
    # only for candidates that did NOT fit
    candidates = [
        ("auto", 2, {"loss_chunk": 2048}),
        ("dots", 2, {"loss_chunk": 2048}),
        ("dots", 2, {}),
        ("full", 8, {}),
        ("full", 4, {}),
        ("full", 2, {}),
        ("full", 1, {}),
    ]
    base_cfg = llama.llama3_1b

    # the REAL input pipeline (memmap TokenDataset + per-process sharding +
    # double-buffer prefetch), not synthetic device-resident data: measured
    # parity within 0.3pp of synthetic (r4), so the bench exercises it
    data_path = _ensure_corpus()

    from torchx_tpu.parallel.mesh import MeshConfig

    mesh_cfg = MeshConfig(dp=1, fsdp=-1, tp=1, sp=1)

    def _is_oom(e: Exception) -> bool:
        msg = str(e).lower()
        return any(
            s in msg
            for s in ("resource_exhausted", "out of memory", "hbm", "oom")
        )

    # the candidate that ran is named in the result (batch, remat_policy);
    # only a device OOM moves on to the next — any other failure is the
    # bench's failure
    metrics = None
    batch_used = None
    policy_used = None
    overrides_used: dict = {}
    for policy, batch, overrides in candidates:
        cfg = base_cfg(remat_policy=policy, **overrides)
        try:
            metrics = train(
                cfg,
                mesh_cfg,
                batch=batch,
                seq=seq,
                steps=steps,
                log_every=log_every,
                data_path=data_path,
            )
        except Exception as e:  # noqa: BLE001 - OOM -> next candidate
            if not _is_oom(e):
                raise
            print(f"{policy}@{batch} OOM, trying next", file=sys.stderr)
            continue
        batch_used, policy_used, overrides_used = batch, policy, overrides
        break
    if metrics is None:
        raise RuntimeError("all bench configurations OOMed")

    # secondary: AQT int8 training matmuls on the same config. Scope "ffn"
    # only: r05 measured whole-model int8 BELOW bf16 (12,562 vs 12,912
    # tok/s/chip) — at batch 2 the attention projections are skinny
    # matmuls where AQT's per-call quantize/dequantize (scale reduction +
    # rounding over the [b*s, d] activations) costs more than the int8
    # MXU gain; the FFN matmuls have the arithmetic intensity to win. If
    # int8 still loses, the JSON says so explicitly
    # (int8_slower_than_bf16) instead of leaving a silent regression.
    int8_metrics = None
    int8_scope = "ffn"
    # reuse the RESOLVED policy (post-"auto") so the secondary leg doesn't
    # re-run selection
    resolved_policy = metrics.get("remat_policy", policy_used)
    # re-anchor the leg's launch clock HERE: train()'s own t_call
    # fallback starts after this leg's cfg construction, so the
    # reported launch-to-first-step drifted low by the setup time
    # (and the pre-fastpath bench drifted high by process age)
    int8_anchor = time.monotonic()
    int8_cfg = base_cfg(
        remat_policy=resolved_policy,
        int8_matmuls=True,
        int8_scope=int8_scope,
        **overrides_used,
    )
    int8_metrics = train(
        int8_cfg,
        mesh_cfg,
        batch=batch_used,
        seq=seq,
        steps=steps,
        log_every=log_every,
        data_path=data_path,
        launch_anchor=int8_anchor,
    )

    # attribution leg: a short PROFILED rerun of the headline config. The
    # profiler fences every step (required for phase boundaries), which
    # perturbs throughput — so the headline number stays unprofiled and
    # the attribution comes from its own few steps.
    prof_metrics = train(
        base_cfg(remat_policy=resolved_policy, **overrides_used),
        mesh_cfg,
        batch=batch_used,
        seq=seq,
        steps=min(steps, 8),
        log_every=log_every,
        data_path=data_path,
        profile=True,
        launch_anchor=time.monotonic(),
    )
    prof_summary = prof_metrics["profile"]

    # overlap leg: the SAME short profiled config with bucketed gradient
    # sync and the fused Pallas kernels. Side-by-side with the baseline
    # attribution above, it shows what the step-time knobs buy: MFU,
    # measured overlap fraction, and the exposed grad-sync seconds. The
    # headline legs above stay unfenced and unbucketed.
    overlap_metrics = train(
        base_cfg(remat_policy=resolved_policy, **overrides_used),
        mesh_cfg,
        batch=batch_used,
        seq=seq,
        steps=min(steps, 8),
        log_every=log_every,
        data_path=data_path,
        profile=True,
        grad_bucket_mb="auto",
        kernels="pallas",
        launch_anchor=time.monotonic(),
    )
    overlap_summary = overlap_metrics["profile"]

    input_kind = "tokendataset"
    device = {k: metrics[k] for k in ("platform", "device_kind", "device_count")}
    result = {
        "metric": "llama training tokens/sec/chip (llama3_1b,"
        f" bf16, seq={seq}, batch={batch_used}, {input_kind}, {platform})",
        "value": round(metrics["tokens_per_sec_per_chip"], 1),
        "unit": "tokens/sec/chip",
        # north star: >=45% MFU (BASELINE.json); reference publishes no
        # numbers (control-plane launcher), so baseline = the MFU target
        "vs_baseline": round(metrics["mfu"] / 0.45, 3),
        "mfu": round(metrics["mfu"], 4),
        "launch_to_first_step_s": round(metrics["launch_to_first_step_s"], 1),
        "loss": round(metrics["loss"], 4),
        "devices": jax.device_count(),
        **device,
        "attention": metrics["attention"],
        "input": input_kind,
    }
    result["launch_breakdown"] = {
        k: round(v, 2) for k, v in metrics["launch_breakdown"].items()
    }
    # steady-state step-time split (data-wait vs compute) + the remat
    # policy the step actually ran with (post-"auto" resolution)
    result["remat_policy"] = metrics["remat_policy"]
    result["step_time_s"] = round(metrics["step_time_s"], 5)
    result["data_wait_s"] = round(metrics["data_wait_s"], 5)
    result["data_wait_frac"] = round(metrics["data_wait_frac"], 5)
    result["prefetch_depth"] = metrics["prefetch_depth"]
    # the profiled leg's attribution: per-phase seconds, MFU, and the
    # measured collective overlap — the numbers the MFU push tracks
    # across rounds (obs/profile.py; render with `tpx profile`)
    result["profile"] = {
        "steps": prof_summary.get("steps"),
        "mfu": round(float(prof_summary.get("mfu") or 0.0), 4),
        "data_wait_frac": round(
            float(prof_summary.get("data_wait_frac") or 0.0), 5
        ),
        "overlap_frac": (
            round(float(prof_summary["overlap_frac"]), 4)
            if prof_summary.get("overlap_frac") is not None
            else None
        ),
        "phase_seconds": {
            k: round(float(v), 5)
            for k, v in (prof_summary.get("phase_seconds") or {}).items()
        },
        "grad_sync_seconds": {
            k: round(float(v), 5)
            for k, v in (prof_summary.get("grad_sync_seconds") or {}).items()
        },
    }
    if "calibration" in prof_summary:
        result["profile"]["calibration"] = prof_summary["calibration"]["scales"]

    def _overlap_leg(summ: dict, met: dict) -> dict:
        grad_sync = summ.get("grad_sync_seconds") or {}
        return {
            "mfu": round(float(summ.get("mfu") or 0.0), 4),
            "overlap_frac": (
                round(float(summ["overlap_frac"]), 4)
                if summ.get("overlap_frac") is not None
                else None
            ),
            "comm_exposed_s": round(float(summ.get("comm_exposed_s") or 0.0), 5),
            "grad_sync_seconds": {
                k: round(float(v), 5) for k, v in sorted(grad_sync.items())
            },
            "grad_bucket_mb": met.get("grad_bucket_mb", 0),
            "grad_buckets": met.get("grad_buckets", 0),
            "kernels": met["kernels"],
            "attention": met["attention"],
        }

    # baseline (single fused sync, reference kernels) vs bucketed + fused
    # kernels, both from short profiled reruns of the headline config — the
    # side-by-side the MFU push tracks (the fused kernels legitimately
    # change rounding, so the two losses are not compared)
    result["overlap"] = {
        "baseline": _overlap_leg(prof_summary, prof_metrics),
        "bucketed": _overlap_leg(overlap_summary, overlap_metrics),
    }
    result["int8_mfu"] = round(int8_metrics["mfu"], 4)
    result["int8_tokens_per_sec_per_chip"] = round(
        int8_metrics["tokens_per_sec_per_chip"], 1
    )
    result["int8_scope"] = int8_scope
    # explicit regression gate: int8 must beat (or tie) bf16 on the
    # same config, else the JSON flags it rather than hiding it
    result["int8_slower_than_bf16"] = bool(
        int8_metrics["tokens_per_sec_per_chip"]
        < metrics["tokens_per_sec_per_chip"]
    )
    # the int8 leg's OWN launch latency (per-call reference), not the
    # cumulative process age the pre-fastpath bench reported
    result["int8_launch_to_first_step_s"] = round(
        int8_metrics["launch_to_first_step_s"], 1
    )
    # deep-preflight predictions next to the measured numbers, so the
    # static cost model's error is tracked across bench rounds (the
    # analyzer side of `tpx explain` — jax-free, pure arithmetic)
    from torchx_tpu.analyze import costmodel as _cm
    from torchx_tpu.analyze.plan import MODEL_SHAPES, ParallelPlan

    _plan = ParallelPlan(
        role="bench",
        model=MODEL_SHAPES["llama3_1b"],
        mesh_spec="fsdp=-1",
        sizes=mesh_cfg.resolve(jax.device_count()),
        batch=int(batch_used),
        seq=int(seq),
        remat_policy=str(result["remat_policy"]),
        devices=jax.device_count(),
        slices=1,
        chips_per_slice=jax.device_count(),
    )
    _fit = _cm.hbm_fit(_plan)
    result["explain_predictions"] = {
        "hbm_total_bytes": _fit.total_bytes,
        "hbm_components": dict(sorted(_fit.components.items())),
        "collective_bytes_per_step": {
            t.axis: t.bytes_per_step for t in _cm.collective_traffic(_plan)
        },
    }
    # the closed loop (`tpx tune`): fold THIS bench's prediction-vs-actual
    # step-time error into the persisted per-generation calibration table
    # (error strictly shrinks: EMA gain 0.5 halves the residual), then run
    # the static tune funnel so the JSON carries the prune report + the
    # winner artifact. Kill switch: TPX_BENCH_TUNE=0.
    if os.environ.get("TPX_BENCH_TUNE", "1").lower() not in ("0", "false"):
        from torchx_tpu.tune import rank as _rank
        from torchx_tpu.tune.calibrate import CalibrationTable, generation_key
        from torchx_tpu.tune.driver import run_tune
        from torchx_tpu.tune.space import bench_1b_space

        _gen = generation_key(metrics["device_kind"])
        _table = CalibrationTable.load_default()
        # predict with the PRE-update scales: the before/after
        # errors below then show this run's calibration gain
        _cost = _rank.predicted_step_cost(
            _plan,
            generation=_gen,
            calibration=_table.scales_for(_gen),
        )
        _obs = _table.observe(
            _gen,
            predicted_step_s=_cost.step_s,
            measured_step_s=float(metrics["step_time_s"]),
            predicted_collective_s=_cost.collective_s,
        )
        _table.save()
        result["tune_calibration"] = {
            "generation": _gen,
            "predicted_step_s": round(_cost.step_s, 6),
            "measured_step_s": round(float(metrics["step_time_s"]), 6),
            "err_before": round(_obs["step_time"]["err_before"], 4),
            "err_after": round(_obs["step_time"]["err_after"], 4),
            "scales": _obs["scales"],
        }
        _tuned = run_tune(
            bench_1b_space(),
            devices=jax.device_count(),
            generation=_gen,
            aot=False,  # bench time budget: static funnel only
            measure=False,  # the bench run above IS the measurement
        )
        result["tune_report"] = _tuned.report
        result["tune_artifact"] = _tuned.artifact_path
        if _tuned.winner is not None:
            result["tune_winner"] = _tuned.winner.candidate.to_dict()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
