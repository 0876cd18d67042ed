"""What a training job says about itself: the launch-to-first-step clock
and its ``launch.*`` spans, the ``job.first_step`` and ``step.window``
heartbeats with the liveness lease beside them, the step profiler, where
the largest parameter sits, and the device's peak FLOP/s for MFU.

None of it changes what a step computes; :mod:`torchx_tpu.train.run` calls
it around the stages. The trace-joining helpers are no-ops unless the
launcher injected ``TPX_TRACE_ID``.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from torchx_tpu.models import llama

_PROCESS_START = time.monotonic()

# The FIRST train() call in a process anchors launch-to-first-step to
# process start (the BASELINE north-star definition: import time counts);
# later calls in the same process (bench variant legs, sweeps) time only
# themselves — otherwise leg N reports the cumulative process age.
_FIRST_TRAIN_PENDING = True


def _launch_ref(t_call: float, launch_anchor: Optional[float]) -> float:
    """The clock a ``train()`` call's launch-to-first-step starts on.

    ``launch_anchor`` re-anchors it for in-process callers (the bench
    legs): without it, every leg after the first would either inherit
    process age or measure only its own call — the caller says explicitly
    which clock this run starts on."""
    global _FIRST_TRAIN_PENDING
    if launch_anchor is not None:
        launch_ref = launch_anchor
    else:
        launch_ref = _PROCESS_START if _FIRST_TRAIN_PENDING else t_call
    _FIRST_TRAIN_PENDING = False
    return launch_ref


# peak bf16 FLOPs/s per chip by generation (for MFU)
PEAK_FLOPS = {
    "tpu v2": 23e12,
    "tpu v3": 61.5e12,  # per chip (2 cores)
    "tpu v4": 275e12,
    "tpu v5": 197e12,  # v5e (v5 lite)
    "tpu v5p": 459e12,
    "tpu v6": 918e12,
    "cpu": 1e12,  # nominal, keeps MFU finite in simulation
}


def device_peak_flops() -> float:
    """Peak bf16 FLOP/s of one device. A TPU whose ``device_kind`` matches
    no row is an error — an MFU against a made-up peak is worse than none;
    the CPU keeps its nominal value so simulated runs stay finite."""
    d = jax.devices()[0]
    kind = d.device_kind.lower()
    for prefix, flops in sorted(PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return flops
    if d.platform == "tpu":
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {d.device_kind!r};"
            " add it to PEAK_FLOPS"
        )
    return PEAK_FLOPS["cpu"]


def _replica_id() -> int:
    """This process's global replica id in the gang — the launcher-injected
    ``TPX_REPLICA_ID`` when present (the id the gang monitor expects),
    falling back to the jax process index."""
    import os

    from torchx_tpu import settings

    raw = os.environ.get(settings.ENV_TPX_REPLICA_ID, "")
    try:
        return int(raw)
    except ValueError:
        return jax.process_index()


def _renew_liveness_lease(step: Optional[int]) -> None:
    """Best-effort per-replica liveness lease alongside each heartbeat, so
    the supervisor's gang monitor can tell 'this replica is alive' apart
    from 'the whole gang stopped' even if the shared trace stream stalls.
    Never lets lease I/O take down training."""
    try:
        from torchx_tpu.supervisor.gang import renew_lease

        # step is advisory; None (no step known yet) must not turn into a
        # swallowed TypeError that silently skips the first-step lease
        renew_lease(_replica_id(), step=-1 if step is None else int(step))
    except Exception:  # noqa: BLE001 - liveness is advisory
        pass


def _launch_span(name: str, **attrs: Any):
    """A ``launch.*`` breakdown span when running under tracing, else a
    no-op (same gating as apps/spmd_main: spans only exist when the
    launcher injected ``TPX_TRACE_ID``)."""
    import os
    from contextlib import nullcontext

    from torchx_tpu import settings

    if not os.environ.get(settings.ENV_TPX_TRACE_ID):
        return nullcontext()
    from torchx_tpu.obs import trace as obs_trace

    return obs_trace.span(name, **attrs)


def _report_first_step(
    first_step_s: float, resumed_step: int, breakdown: dict[str, float]
) -> None:
    """Join the launcher's trace with a ``job.first_step`` heartbeat and
    feed the launch-to-first-step histogram (the BASELINE.md north-star
    metric). No-op when this process was not launched under tracing."""
    import os

    from torchx_tpu import settings

    if not os.environ.get(settings.ENV_TPX_TRACE_ID):
        return
    from torchx_tpu.obs import metrics as obs_metrics
    from torchx_tpu.obs import trace as obs_trace

    obs_metrics.LAUNCH_TO_FIRST_STEP.observe(first_step_s)
    obs_trace.heartbeat(
        "job.first_step",
        launch_to_first_step_s=round(first_step_s, 3),
        resumed_step=resumed_step or None,
        replica=_replica_id(),
        **{f"stage_{k}_s": round(v, 3) for k, v in breakdown.items()},
    )
    _renew_liveness_lease(resumed_step)


def _step_heartbeat(**attrs: Any) -> None:
    """A ``step.window`` trace event per log window — the steady-state
    counterpart of the ``launch.*`` spans (same TPX_TRACE_ID gating)."""
    import os

    from torchx_tpu import settings

    if not os.environ.get(settings.ENV_TPX_TRACE_ID):
        return
    from torchx_tpu.obs import trace as obs_trace

    obs_trace.heartbeat("step.window", replica=_replica_id(), **attrs)
    _renew_liveness_lease(int(attrs.get("step", -1)))


def _profile_enabled(flag: bool) -> bool:
    """True when per-step phase profiling is on: the trainer's
    ``--profile`` flag or the launcher-injected ``TPX_PROFILE`` switch
    (so a submitted role enables it via env without editing args)."""
    if flag:
        return True
    import os

    from torchx_tpu import settings

    return os.environ.get(settings.ENV_TPX_PROFILE, "").lower() in (
        "1",
        "true",
        "yes",
        "on",
    )


def _make_profiler(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    batch: int,
    seq: int,
    tokens_per_step: int,
    flops_per_token: float,
    peak_flops: float,
) -> Optional[Any]:
    """Best-effort :class:`~torchx_tpu.obs.profile.StepProfiler` wired to
    this run's arithmetic.

    Mirrors the live config and mesh into the jax-free
    ``ModelShape``/``ParallelPlan`` IR so the attribution model's
    collective terms come from the same calibrated cost model as
    ``tpx explain``. Returns None when anything is off — profiling must
    never fail the job.
    """
    try:
        from torchx_tpu.analyze.plan import ModelShape, ParallelPlan
        from torchx_tpu.obs.profile import StepProfiler, attribution_model

        kind = getattr(jax.devices()[0], "device_kind", "cpu")
        shape = ModelShape(
            name="train",
            vocab_size=cfg.vocab_size,
            dim=cfg.dim,
            n_layers=cfg.n_layers,
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            ffn_dim=cfg.ffn_dim,
            max_seq=cfg.max_seq,
            dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
            tie_embeddings=cfg.tie_embeddings,
            loss_chunk=cfg.loss_chunk,
            n_experts=getattr(cfg, "n_experts", 0),
            top_k=getattr(cfg, "top_k", 0),
        )
        plan = ParallelPlan(
            role="train",
            model=shape,
            mesh_spec="",
            sizes={a: int(s) for a, s in mesh.shape.items()},
            batch=batch,
            seq=seq,
            devices=jax.device_count(),
            accelerator=kind,
        )
        return StepProfiler(
            attribution_model(
                flops_per_token=flops_per_token,
                tokens_per_step=tokens_per_step,
                peak_flops=peak_flops,
                param_count=shape.param_count(),
                plan=plan,
                generation=kind,
            )
        )
    except Exception as e:  # noqa: BLE001 - profiling is best-effort
        if jax.process_index() == 0:
            print(f"step profiler unavailable: {e}", flush=True)
        return None


def _shard_report(params: llama.Params) -> dict[str, Any]:
    """Where the largest parameter physically sits: how many distinct
    devices hold a shard, and each shard's share of the bytes. On an
    ``fsdp=4`` mesh this reads 4 devices x 0.25 — a tree that has only met
    one device would put everything on the first."""
    leaf = max(jax.tree.leaves(params), key=lambda x: x.nbytes)
    shards = leaf.addressable_shards
    return {
        "shape": list(leaf.shape),
        "devices": len({s.device.id for s in shards}),
        "shard_frac": max(s.data.nbytes for s in shards) / leaf.nbytes,
    }
