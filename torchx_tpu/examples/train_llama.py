"""Llama pretraining example/benchmark — the flagship launched job.

The analog of the reference's ``lightning`` example trainer
(torchx/examples/apps/lightning) re-imagined for TPU SPMD: a pjit-style
training step (AdamW, remat, bf16) over the 4-axis dp/fsdp/tp/sp mesh,
launched via::

    tpx run -s gke dist.spmd --tpu v5p-32 -m torchx_tpu.examples.train_llama -- \
        --config llama3_8b --mesh fsdp=-1 --batch 16 --seq 8192

Prints per-step tokens/sec and model FLOPs utilization (MFU); the
launch-to-first-step latency (the BASELINE.md north-star metric) is
reported as the time from process start to the end of step 1.
"""

from __future__ import annotations

import argparse
import contextvars
import dataclasses
import functools
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchx_tpu.models import llama
from torchx_tpu.obs import hot
from torchx_tpu.parallel.mesh import (
    BATCH_SPEC,
    MeshConfig,
    device_info,
    make_mesh,
)
from torchx_tpu.parallel.prefetch import Prefetcher, device_prefetch

_PROCESS_START = time.monotonic()

# The FIRST train() call in a process anchors launch-to-first-step to
# process start (the BASELINE north-star definition: import time counts);
# later calls in the same process (bench variant legs, sweeps) time only
# themselves — otherwise leg N reports the cumulative process age.
_FIRST_TRAIN_PENDING = True

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


def make_optimizer(
    lr: float = 3e-4, weight_decay: float = 0.1, warmup: int = 100
) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=lr,
        warmup_steps=warmup,
        decay_steps=100_000,
        end_value=lr * 0.1,
    )
    return optax.chain(
        _scoped(hot.GRAD_CLIP, optax.clip_by_global_norm(1.0)),
        _scoped(
            hot.OPTIMIZER,
            optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
        ),
    )


def _scoped(
    name: str, tx: optax.GradientTransformation
) -> optax.GradientTransformation:
    """``tx`` with its update's operations named ``name`` in the compiled
    step (the state keeps ``tx``'s own structure, so checkpoints still fit)."""

    def update(updates, state, params=None):  # noqa: ANN001
        with jax.named_scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


@dataclasses.dataclass
class TrainState:
    params: llama.Params
    opt_state: Any
    step: jnp.ndarray


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[]
)


def _model_fns(cfg: llama.LlamaConfig):
    """Dense vs MoE dispatch (see :func:`llama.model_fns`)."""
    return llama.model_fns(cfg)


def init_state(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    seed: int = 0,
) -> TrainState:
    """Initialize params *sharded* (jit with out_shardings so the full
    fp32 model never materializes on one device)."""
    init_fn, specs_fn = _model_fns(cfg)
    specs = specs_fn(cfg, pp=mesh.shape.get("pp", 1) > 1)
    out_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    @functools.partial(jax.jit, out_shardings=out_shardings)
    def _init(key):  # noqa: ANN001
        return init_fn(cfg, key)

    params = _init(jax.random.PRNGKey(seed))
    opt_state = jax.jit(
        optimizer.init,
        out_shardings=None,  # let XLA choose opt-state shardings from params
    )(params)
    state = TrainState(
        params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32)
    )
    return normalize_state_shardings(state, mesh)


def normalize_state_shardings(state: TrainState, mesh: Mesh) -> TrainState:
    """Re-place any leaf committed to a single device (XLA puts optimizer
    scalars there; orbax restores them there) as mesh-replicated, so every
    leaf of the state lives on one consistent device set."""
    replicated = NamedSharding(mesh, P())

    def fix(x):  # noqa: ANN001
        sharding = getattr(x, "sharding", None)
        if sharding is not None and len(sharding.device_set) < mesh.devices.size:
            return jax.device_put(x, replicated)
        return x

    return jax.tree.map(fix, state)


def make_train_step(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    state_shardings: Optional[Any] = None,
    grad_bucket_plan: Optional[Any] = None,
):
    """The jitted SPMD training step: grads + AdamW update, donated state.

    All mesh configs — including ring attention inside a pipeline stage
    (the pipeline manualizes pp and sp in one shard_map) — compile under
    the default Shardy partitioner; no GSPMD fallback remains.

    ``state_shardings`` (a TrainState of NamedShardings) pins the output
    state to the input's shardings. Without it the compiler may pick
    different shardings for the returned opt state than the donated input
    had — then feeding step N's state into step N+1 through an AOT
    executable trips the strict input-sharding check.

    ``grad_bucket_plan`` (a :class:`~torchx_tpu.parallel.overlap.BucketPlan`)
    buckets the gradient sync: value-identity barriers at bucket
    boundaries let XLA issue per-bucket reduces while backward is still
    running, instead of one fused post-backward collective. Gradients are
    bitwise identical to the unbucketed step."""

    def step(state: TrainState, batch: dict[str, jnp.ndarray]):
        (loss, aux), grads = jax.value_and_grad(llama.loss_and_aux, has_aux=True)(
            state.params, batch, cfg, mesh
        )
        if grad_bucket_plan is not None:
            from torchx_tpu.parallel import overlap

            grads, _ = overlap.bucketed_sync(
                grads,
                bucket_mb=max(1, grad_bucket_plan.bucket_bytes // (1024 * 1024)),
                mode="auto",
                plan=grad_bucket_plan,
            )
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        with jax.named_scope(hot.OPTIMIZER):
            params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1),
            loss,
            aux,  # raw MoE balancing aux (router health; 0 for dense)
        )

    out_shardings = None
    if state_shardings is not None:
        scalar = NamedSharding(mesh, P())
        out_shardings = (state_shardings, scalar, scalar)
    return jax.jit(step, donate_argnums=(0,), out_shardings=out_shardings)


def synthetic_batch(
    cfg: llama.LlamaConfig, mesh: Mesh, batch: int, seq: int, seed: int = 0
) -> dict[str, jnp.ndarray]:
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )
    return {"tokens": jax.device_put(tokens, NamedSharding(mesh, BATCH_SPEC))}


def parse_mesh_arg(spec: str) -> MeshConfig:
    """``dp=2,fsdp=-1,tp=4`` -> MeshConfig."""
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec

    return parse_mesh_spec(spec)


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


def _install_preempt_handler() -> tuple[Optional[threading.Event], Any]:
    """Arm a SIGTERM preemption-grace handler (main thread only).

    TPU preemptions deliver SIGTERM with a short notice window before the
    hard kill; the default handler would drop the process mid-step and
    waste everything since the last periodic checkpoint. Instead the
    handler just sets an event the train loop polls at each step — the
    loop then forces a final save, *waits for it to be durable*, and exits
    cleanly inside the window. Returns ``(event, restore)`` where
    ``restore()`` reinstates the previous handler; ``(None, noop)`` when
    the handler cannot be installed (non-main thread, e.g. under pytest
    workers or a nested launcher)."""
    import signal

    if threading.current_thread() is not threading.main_thread():
        return None, lambda: None
    evt = threading.Event()

    def _on_sigterm(signum, frame):  # noqa: ANN001
        evt.set()

    try:
        prev = signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # no signal support here
        return None, lambda: None

    def _restore() -> None:
        try:
            signal.signal(signal.SIGTERM, prev)
        except (ValueError, OSError):
            pass

    return evt, _restore


def train(
    cfg: llama.LlamaConfig,
    mesh_config: MeshConfig,
    batch: int,
    seq: int,
    steps: int,
    log_every: int = 1,
    lr: float = 3e-4,
    warmup: int = 100,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    data_path: Optional[str] = None,
    profile_dir: Optional[str] = None,
    prefetch: int = 2,
    profile: bool = False,
    grad_bucket_mb: Any = 0,
    kernels: str = "reference",
    launch_anchor: Optional[float] = None,
) -> dict[str, float]:
    global _FIRST_TRAIN_PENDING
    t_call = time.monotonic()
    # ``launch_anchor`` re-anchors launch-to-first-step for in-process
    # callers (the bench legs): without it, every leg after the first
    # would either inherit process age or measure only its own call —
    # the caller says explicitly which clock this run starts on.
    if launch_anchor is not None:
        launch_ref = launch_anchor
    else:
        launch_ref = _PROCESS_START if _FIRST_TRAIN_PENDING else t_call
    _FIRST_TRAIN_PENDING = False

    from torchx_tpu.obs import metrics as obs_metrics
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    breakdown: dict[str, float] = {}

    def _stage(stage: str, seconds: float) -> None:
        breakdown[stage] = seconds
        obs_metrics.LAUNCH_STAGE_SECONDS.observe(seconds, stage=stage)

    _stage("import", t_call - launch_ref)

    cfg = dataclasses.replace(cfg, max_seq=seq)

    kernels_used = "reference"
    if kernels and kernels != "reference":
        # "pallas" degrades to "reference" off-TPU (the Mosaic kernels
        # need real TPU cores); "interpret" runs the same kernels through
        # the Pallas interpreter anywhere (tests, CPU sim)
        from torchx_tpu.ops.fused import resolve_kernels

        kernels_used = resolve_kernels(kernels)
        cfg = dataclasses.replace(cfg, kernels=kernels_used)
        if kernels_used != kernels and jax.process_index() == 0:
            print(
                f"kernels: {kernels!r} unavailable on this backend;"
                " using reference ops",
                flush=True,
            )

    t0 = time.monotonic()
    with _launch_span("launch.backend_init"):
        setup_compilation_cache()  # relaunches compile in seconds, not minutes
        mesh = make_mesh(mesh_config)  # first device query: backend init
        device = device_info()
        n_devices = device["device_count"]
        peak = device_peak_flops() * n_devices
    _stage("backend_init", time.monotonic() - t0)

    optimizer = make_optimizer(lr=lr, warmup=warmup)

    if cfg.remat_policy == "auto":
        if cfg.remat:
            # resolve "auto" -> the cheapest-recompute policy whose
            # compiled step fits HBM (trial compiles land in the
            # persistent XLA cache, so the winner's real compile below is
            # a cache hit)
            from torchx_tpu.parallel.remat_auto import choose_remat_policy

            t0 = time.monotonic()
            with _launch_span("launch.remat_select"):
                policy, trials = choose_remat_policy(cfg, mesh, batch, seq)
            cfg = dataclasses.replace(cfg, remat_policy=policy)
            _stage("remat_select", time.monotonic() - t0)
            if jax.process_index() == 0:
                verdicts = ", ".join(
                    f"{t.policy}={'fits' if t.fits else 'no'}" for t in trials
                )
                print(f"remat auto -> {policy} ({verdicts})", flush=True)
        else:
            # remat disabled: the policy is never consulted, but "auto"
            # must not leak into traces/results as if it were concrete
            cfg = dataclasses.replace(cfg, remat_policy="full")
    # what the step actually does — "none" when remat is off entirely
    remat_policy_used = cfg.remat_policy if cfg.remat else "none"

    ckpt = None
    latest = None
    if ckpt_dir:
        from torchx_tpu.parallel.checkpoint import Checkpointer

        ckpt_every = ckpt_every or 100  # ckpt_dir alone must still checkpoint
        ckpt = Checkpointer(ckpt_dir, save_interval_steps=ckpt_every)
        latest = ckpt.latest_step()  # cheap step listing, no tensor IO
    resumed_step = latest or 0

    # -- overlapped bootstrap ----------------------------------------------
    # Corpus setup (memmap open + first host batch + its device transfer)
    # and the heavy checkpoint restore run on threads while the main thread
    # AOT-compiles the train step; both join before the first step. Spans
    # started on the threads keep their parent via the copied context.
    ctx = contextvars.copy_context()

    data_box: dict[str, Any] = {}

    def _data_setup() -> None:
        t_d = time.monotonic()
        try:
            from torchx_tpu.examples.data import TokenDataset

            with _launch_span("launch.data_setup"):
                gen = device_prefetch(
                    ({"tokens": rows} for rows in
                     TokenDataset(data_path, seq, batch, start_step=resumed_step)),
                    mesh,
                    depth=prefetch,
                )
                # pull batch 1 now so its host->device transfer overlaps
                # the compile instead of the first step
                data_box["first"] = next(gen)
            data_box["batches"] = gen
        except BaseException as e:  # noqa: BLE001 - re-raised on join
            data_box["error"] = e
        data_box["seconds"] = time.monotonic() - t_d

    data_thread = None
    if data_path:
        data_thread = threading.Thread(
            target=lambda: ctx.run(_data_setup), name="tpx-data-setup", daemon=True
        )
        data_thread.start()

    restore_box: dict[str, Any] = {}
    restore_thread = None
    if latest is not None:
        # resuming: restore onto the ABSTRACT train state (skipping the
        # init compile entirely) concurrently with the AOT compile below
        from torchx_tpu.parallel.aot_fit import abstract_train_state

        lower_state = abstract_train_state(cfg, mesh, optimizer)

        def _restore() -> None:
            t_r = time.monotonic()
            try:
                with _launch_span("launch.restore", step=latest):
                    step_r, restored = ckpt.restore_latest(lower_state)
                restore_box["step"] = step_r
                restore_box["state"] = restored
            except BaseException as e:  # noqa: BLE001 - re-raised on join
                restore_box["error"] = e
            restore_box["seconds"] = time.monotonic() - t_r

        restore_thread = threading.Thread(
            target=lambda: ctx.run(_restore), name="tpx-ckpt-restore", daemon=True
        )
        restore_thread.start()
    else:
        t0 = time.monotonic()
        with _launch_span("launch.init_state"):
            state = init_state(cfg, mesh, optimizer)
        _stage("init_state", time.monotonic() - t0)
        lower_state = state

    # AOT compile while restore/data IO is in flight. The loop then calls
    # the Compiled executable directly — no per-step jit cache lookup — and
    # variant configs (e.g. the int8 bench leg) lower to distinct programs
    # that each land in (and relaunch from) the persistent XLA cache.
    t0 = time.monotonic()
    state_shardings = jax.tree.map(lambda x: x.sharding, lower_state)

    # resolve --grad-bucket-mb against the (possibly abstract) param tree:
    # bucket layout only needs shapes/dtypes, so the plan is fixed before
    # the compile and never perturbs the compilation cache between runs
    grad_plan = None
    grad_bucket_mb_used = 0
    bucket_trials: tuple = ()
    if grad_bucket_mb not in (0, "0", None, ""):
        from torchx_tpu.parallel import overlap

        grad_bucket_mb_used, bucket_trials = overlap.resolve_bucket_mb(
            lower_state.params, grad_bucket_mb
        )
        grad_plan = overlap.plan_buckets(
            lower_state.params, grad_bucket_mb_used * 1024 * 1024
        )
        if jax.process_index() == 0:
            print(f"grad buckets -> {grad_plan.describe()}", flush=True)

    train_step = make_train_step(
        cfg, mesh, optimizer, state_shardings=state_shardings,
        grad_bucket_plan=grad_plan,
    )
    batch_sds = {
        "tokens": jax.ShapeDtypeStruct(
            (batch, seq + 1),
            jnp.int32,
            sharding=NamedSharding(mesh, BATCH_SPEC),
        )
    }
    with _launch_span("launch.compile"):
        step_fn = train_step.lower(lower_state, batch_sds).compile()
    _stage("compile", time.monotonic() - t0)

    if restore_thread is not None:
        restore_thread.join()
        if "error" in restore_box:
            raise restore_box["error"]
        if restore_box.get("state") is None:
            # every candidate step failed verification and was quarantined
            # (restore_latest returned (None, None)): train from scratch
            # instead of dying on the missing state
            t0 = time.monotonic()
            with _launch_span("launch.init_state"):
                state = init_state(cfg, mesh, optimizer)
            _stage("init_state", time.monotonic() - t0)
            resumed_step = 0
            if jax.process_index() == 0:
                print(
                    "no restorable checkpoint step (all quarantined);"
                    " starting fresh",
                    flush=True,
                )
        else:
            state = restore_box["state"]
            resumed_step = int(restore_box["step"])
            _stage("restore", restore_box["seconds"])
            if jax.process_index() == 0:
                print(
                    f"resumed from checkpoint step {resumed_step}", flush=True
                )

    if data_thread is not None:
        data_thread.join()
        if "error" in data_box:
            raise data_box["error"]
        if resumed_step != (latest or 0):
            # restore fell back past a corrupt newest step: rebuild the
            # stream so data and params resume from the same step
            from torchx_tpu.examples.data import TokenDataset

            data_box["batches"].close()
            gen = device_prefetch(
                ({"tokens": rows} for rows in
                 TokenDataset(data_path, seq, batch, start_step=resumed_step)),
                mesh,
                depth=prefetch,
            )
            data_box["first"] = next(gen)
            data_box["batches"] = gen
        _stage("data_setup", data_box["seconds"])
        _first_batch = [data_box["first"]]
        _batches = data_box["batches"]

        def next_batch() -> dict[str, jnp.ndarray]:
            if _first_batch:
                return _first_batch.pop()
            return next(_batches)

    else:
        import itertools

        # constant device batch: passthrough prefetcher (depth 0) keeps one
        # code path and an honest (≈0) data-wait account
        data = synthetic_batch(cfg, mesh, batch, seq)
        _batches = Prefetcher(itertools.repeat(data), depth=0)
        next_batch = lambda: next(_batches)  # noqa: E731

    tokens_per_step = batch * seq
    flops_per_token = cfg.flops_per_token()  # cfg.max_seq already == seq

    # step 1 (already AOT-compiled above) = launch-to-first-step
    t0 = time.monotonic()
    with _launch_span("launch.first_step"):
        state, loss, aux = step_fn(state, next_batch())
        jax.block_until_ready(loss)
    first_step_s = time.monotonic() - launch_ref
    _stage("first_step", time.monotonic() - t0)
    if jax.process_index() == 0:
        print(
            f"step 1 loss={float(loss):.4f}"
            f" launch-to-first-step={first_step_s:.1f}s",
            flush=True,
        )
        _report_first_step(first_step_s, resumed_step, breakdown)

    from torchx_tpu.ops.attention import traced

    # where the step ran and what it lowered to: the device as jax reports
    # it, the attention implementation the layer body traced, and whether
    # the fused norm ran (empty unless --kernels selected it)
    ran_on = {
        **device,
        "attention": traced("attention"),
        "norm_residual": traced("norm_residual"),
        "largest_param_shards": _shard_report(state.params),
    }

    if steps <= 1:
        # single-step smoke: the compile-including step is the only timing
        _batches.close()
        return {
            **ran_on,
            "loss": float(loss),
            "tokens_per_sec": tokens_per_step / first_step_s,
            "tokens_per_sec_per_chip": tokens_per_step / first_step_s / n_devices,
            "mfu": tokens_per_step / first_step_s * flops_per_token / peak,
            "launch_to_first_step_s": first_step_s,
            "launch_breakdown": dict(breakdown),
            "remat_policy": remat_policy_used,
            "kernels": kernels_used,
            "grad_bucket_mb": grad_bucket_mb_used,
            "grad_buckets": grad_plan.n_buckets if grad_plan else 0,
        }

    # a few untimed warmup steps: dispatch pipelining + allocator settling
    warmup_steps = min(3, max(steps - 2, 0))
    for _ in range(warmup_steps):
        state, loss, aux = step_fn(state, next_batch())
    jax.block_until_ready(loss)

    import contextlib

    profiler = None
    if _profile_enabled(profile):
        profiler = _make_profiler(
            cfg, mesh, batch, seq, tokens_per_step, flops_per_token, peak
        )
    if profiler is not None:
        # per-next() wait intervals credit the current step's data_wait
        _batches.set_wait_observer(profiler.observe_wait)

    def _prof_phase(name: str):
        return profiler.phase(name) if profiler is not None else (
            contextlib.nullcontext()
        )

    if profile_dir and jax.process_index() == 0:
        # xprof trace of the steady-state steps (view with tensorboard or
        # xprofiler; the TPU observability hook from SURVEY §5)
        jax.profiler.start_trace(profile_dir)

    is_moe = bool(getattr(cfg, "n_experts", 0))

    def _emit_log(entry: dict) -> None:
        # the async copies issued at the log boundary are long since done;
        # float() here is a host-memory read, not a device round-trip
        aux_vec = entry["aux"]
        moe_note = (
            f" router_aux={float(aux_vec[llama.AUX_BALANCE]):.3f}"
            f" router_entropy={float(aux_vec[llama.AUX_ENTROPY]):.2f}"
            f" router_overflow={float(aux_vec[llama.AUX_OVERFLOW]):.1%}"
            if is_moe
            else ""
        )
        print(
            f"step {entry['step']} loss={float(entry['loss']):.4f}"
            f" tokens/sec={entry['tps']:,.0f}"
            f" tokens/sec/chip={entry['tps'] / n_devices:,.0f}"
            f" MFU={entry['mfu']:.1%}"
            f" window_mfu={entry['window_mfu']:.1%}{moe_note}",
            flush=True,
        )

    t0 = time.monotonic()
    timed_steps = max(steps - 1 - warmup_steps, 1)
    # host-side global step counter: int(state.step) would force a
    # device sync every iteration, breaking dispatch pipelining
    global_step = resumed_step + 1 + warmup_steps
    pending = None  # deferred log entry: printed one window late
    window_t0, window_steps = t0, 0
    # data-wait accounting anchors: the prefetcher's cumulative wait at
    # loop entry, and at the last log fence (for per-window splits)
    wait_anchor = window_wait = _batches.data_wait_s
    # preemption grace: SIGTERM sets the event; the loop fences, forces a
    # final durable save, and exits cleanly inside the notice window
    preempt_evt, _restore_sigterm = _install_preempt_handler()
    preempted = False
    try:
        for i in range(timed_steps):
            with hot.step_span(global_step + 1):
                if profiler is not None:
                    # the phase boundary is host-visible only behind a
                    # completion fence, so profiled steps serialize dispatch
                    # (a measured, documented perturbation — the headline
                    # bench legs run unprofiled)
                    profiler.begin_step()
                    b = next_batch()
                    with profiler.phase("forward_backward"):
                        state, loss, aux = step_fn(state, b)
                        jax.block_until_ready(loss)
                else:
                    state, loss, aux = step_fn(state, next_batch())
                global_step += 1
                window_steps += 1
                if ckpt is not None and global_step % ckpt_every == 0:
                    with _prof_phase("checkpoint"), hot.span(hot.TRAIN_CHECKPOINT):
                        ckpt.save(global_step, state)
                if preempt_evt is not None and preempt_evt.is_set():
                    preempted = True
                    jax.block_until_ready(state.params)
                    if ckpt is not None:
                        ckpt.save(global_step, state, force=True)
                        ckpt.wait()  # durable BEFORE the hard kill lands
                    if jax.process_index() == 0:
                        print(
                            f"preemption notice: checkpointed step {global_step},"
                            " exiting",
                            flush=True,
                        )
                    break
                if (i + 1) % log_every == 0 or i + 1 == timed_steps:
                    with _prof_phase("host"), hot.span(hot.TRAIN_LOG):
                        with hot.span(hot.TRAIN_FENCE):
                            jax.block_until_ready(loss)  # completion fence: timing only
                        now = time.monotonic()
                        dt = (now - t0) / (i + 1)
                        tps = tokens_per_step / dt
                        window_dt = (now - window_t0) / window_steps
                        window_mfu = (
                            tokens_per_step / window_dt * flops_per_token / peak
                        )
                        wait_now = _batches.data_wait_s
                        wait_per_step = (wait_now - window_wait) / window_steps
                        window_wait = wait_now
                        obs_metrics.STEP_SECONDS.observe(window_dt, phase="total")
                        obs_metrics.STEP_SECONDS.observe(
                            wait_per_step, phase="data_wait"
                        )
                        _step_heartbeat(
                            step=global_step,
                            avg_step_s=round(window_dt, 6),
                            data_wait_s=round(wait_per_step, 6),
                            mfu=round(window_mfu, 4),
                            remat_policy=remat_policy_used,
                        )
                        # Logging must not stall the device: a synchronous
                        # float(loss) here is a full device->host round trip
                        # that lands INSIDE the next timed window. Instead
                        # start an async copy and print the PREVIOUS window's
                        # entry, so the transfer overlaps the next window's
                        # compute.
                        for arr in (loss, aux):
                            copy_async = getattr(arr, "copy_to_host_async", None)
                            if copy_async is not None:
                                copy_async()
                        if pending is not None and jax.process_index() == 0:
                            _emit_log(pending)
                        pending = {
                            "step": global_step,
                            "loss": loss,
                            "aux": aux,
                            "tps": tps,
                            "mfu": tps * flops_per_token / peak,
                            "window_mfu": window_mfu,
                        }
                        window_t0, window_steps = time.monotonic(), 0
                if profiler is not None:
                    profiler.end_step(global_step)
        jax.block_until_ready(state.params)
        total = time.monotonic() - t0
        data_wait_s = _batches.data_wait_s - wait_anchor
    finally:
        _restore_sigterm()
        # graceful drain: release the prefetch producer even when the loop
        # exits early (error, interrupt) — never leave a thread blocked on
        # a full queue
        _batches.close()
    if pending is not None and jax.process_index() == 0:
        _emit_log(pending)  # after timing: the flush is off the clock
    if profile_dir and jax.process_index() == 0:
        jax.profiler.stop_trace()
        print(f"profile trace written to {profile_dir}", flush=True)
    tps = tokens_per_step * timed_steps / total
    if ckpt is not None:
        if ckpt.latest_step() != global_step:  # final state, any interval
            ckpt.save(global_step, state, force=True)
        ckpt.close()
    profile_summary = None
    if profiler is not None:
        _batches.set_wait_observer(None)
        try:
            # summarize + tpx_profile_* gauges + the observe_collectives
            # calibration fold (when the mesh moved collective bytes)
            profile_summary = profiler.close()
        except Exception as e:  # noqa: BLE001 - profiling is best-effort
            if jax.process_index() == 0:
                print(f"profile summary failed: {e}", flush=True)
    results = {
        **ran_on,
        "loss": float(loss),
        "tokens_per_sec": tps,
        "tokens_per_sec_per_chip": tps / n_devices,
        "mfu": tps * flops_per_token / peak,
        "launch_to_first_step_s": first_step_s,
        "launch_breakdown": dict(breakdown),
        "final_step": int(state.step),
        "resumed_from_step": resumed_step,
        # steady-state step-time split: how much of each timed step the
        # host spent blocked on input vs the device computing
        "step_time_s": total / timed_steps,
        "data_wait_s": data_wait_s,
        "data_wait_frac": data_wait_s / total if total > 0 else 0.0,
        "remat_policy": remat_policy_used,
        "prefetch_depth": prefetch,
        # step-time optimization knobs actually in effect for this run
        "kernels": kernels_used,
        "grad_bucket_mb": grad_bucket_mb_used,
        "grad_buckets": grad_plan.n_buckets if grad_plan else 0,
        # True when a SIGTERM preemption notice cut the run short (the
        # final checkpoint is durable; the supervisor resubmits from it)
        "preempted": preempted,
    }
    if bucket_trials:
        results["grad_bucket_trials"] = [t.to_dict() for t in bucket_trials]
    if profile_summary is not None:
        results["profile"] = profile_summary
    return results


def all_configs() -> dict:
    """Dense llama presets plus the MoE family (models/moe.py)."""
    from torchx_tpu.models import moe

    return {**llama.CONFIGS, **moe.CONFIGS}


def main(argv: Optional[list[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default="tiny", choices=sorted(all_configs()))
    parser.add_argument(
        "--mesh",
        default="fsdp=-1",
        help="axis sizes pp/dp/fsdp/ep/tp/sp, e.g. dp=2,fsdp=-1,tp=4"
        " (ep shards MoE experts independently of tp, e.g. ep=8,tp=1)",
    )
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--ring-attention", action="store_true")
    parser.add_argument(
        "--remat-policy",
        default=None,
        choices=["full", "dots", "dots_attn", "auto"],
        help="rematerialization policy (default: the config's own);"
        " 'auto' AOT-compiles candidates and picks the cheapest-recompute"
        " policy that fits device HBM",
    )
    parser.add_argument(
        "--prefetch",
        type=int,
        default=2,
        help="device input prefetch depth (batches staged ahead of the"
        " step; 0 = synchronous)",
    )
    parser.add_argument(
        "--int8",
        action="store_true",
        help="AQT int8 training matmuls (see docs/performance.md for the"
        " measured v5e guidance before enabling)",
    )
    parser.add_argument(
        "--int8-scope",
        default=None,
        choices=["all", "ffn"],
        help="which projections to quantize (implies --int8)",
    )
    parser.add_argument("--lr", type=float, default=None)
    parser.add_argument(
        "--grad-bucket-mb",
        default="0",
        help="bucket the gradient sync so per-bucket reduces overlap the"
        " backward pass: a size cap in MiB, 'auto' (remat_auto-style"
        " candidate ladder), or 0 to keep the single fused sync."
        " Gradients are bitwise identical either way",
    )
    parser.add_argument(
        "--kernels",
        default=None,
        choices=["reference", "pallas", "interpret"],
        help="attention/norm kernel implementation: 'pallas' selects the"
        " fused Mosaic kernels on TPU (reference fallback elsewhere);"
        " 'interpret' runs the same kernels in the Pallas interpreter"
        " (parity testing); default reference XLA ops",
    )
    parser.add_argument(
        "--log-every", type=int, default=None,
        help="steps between log lines, >= 1 (each is a device fence;"
        " 8+ on TPU)",
    )
    parser.add_argument(
        "--data", default=None, help="packed uint32 token file (see datapreproc); synthetic data when unset"
    )
    parser.add_argument(
        "--profile-dir", default=None, help="write an xprof trace of the timed steps here"
    )
    parser.add_argument(
        "--profile",
        action="store_true",
        help="per-step phase attribution (data_wait / forward_backward /"
        " grad_sync / optimizer / checkpoint / host) appended to the obs"
        " session's profile.jsonl — view with `tpx profile`; also"
        " enabled by TPX_PROFILE=1. Fences every step: use for"
        " attribution runs, not headline numbers",
    )
    parser.add_argument(
        "--ckpt-dir", default=None, help="checkpoint directory (enables save+resume)"
    )
    parser.add_argument(
        "--ckpt-every", type=int, default=0, help="save every N steps (default 100 when --ckpt-dir is set)"
    )
    args = parser.parse_args(argv)

    cfg = all_configs()[args.config]()
    if args.ring_attention:
        cfg = dataclasses.replace(cfg, use_ring_attention=True)
    if args.remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=args.remat_policy)
    if args.int8 or args.int8_scope:
        cfg = dataclasses.replace(
            cfg, int8_matmuls=True, int8_scope=args.int8_scope or "all"
        )
    if args.log_every is not None and args.log_every < 1:
        parser.error("--log-every must be >= 1")
    # None = keep train()'s own defaults (single source of truth)
    overrides = {
        k: v
        for k, v in {"log_every": args.log_every, "lr": args.lr}.items()
        if v is not None
    }
    import os

    from torchx_tpu import settings

    # an elastic reshape overrides --mesh: the supervisor injects the
    # degraded shape for resubmitted attempts as $TPX_MESH, so the job
    # comes up on the surviving capacity without anyone editing flags
    mesh_spec = os.environ.get(settings.ENV_TPX_MESH) or args.mesh
    metrics = train(
        cfg,
        parse_mesh_arg(mesh_spec),
        args.batch,
        args.seq,
        args.steps,
        **overrides,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        data_path=args.data,
        profile_dir=args.profile_dir,
        prefetch=args.prefetch,
        profile=args.profile,
        grad_bucket_mb=args.grad_bucket_mb,
        kernels=args.kernels or "reference",
    )
    if jax.process_index() == 0:
        print("final:", metrics, flush=True)


if __name__ == "__main__":
    main()
