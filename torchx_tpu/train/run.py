"""``train()``: one training run as a sequence of stages that return values.

*resolve* decides what will run (sequence length, kernels, the remat
policy, the mesh and the optimizer); *open_io* opens the checkpoint and
starts the corpus and restore threads; *compile_step* AOT-compiles the step
while they work; *join* collects the state and the feed; *first_step* is
launch-to-first-step; *loop* is warm-up and the timed window; *summarize*
is the result dict. :func:`train` calls them in that order, and what passes
between them is one :class:`TrainRun` — a value a caller can hold: mesh,
resolved config, optimizer, state, compiled step, feed, checkpointer.

The overlap of compile with restore and data IO is behaviour: corpus setup
(memmap open + first host batch + its device transfer) and the heavy
checkpoint restore run on threads while the main thread compiles, and both
join before the first step.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import signal
import threading
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding

from torchx_tpu.models import llama
from torchx_tpu.obs import hot
from torchx_tpu.obs import metrics as obs_metrics
from torchx_tpu.ops.attention import traced
from torchx_tpu.parallel.mesh import (
    BATCH_SPEC,
    MeshConfig,
    device_info,
    make_mesh,
)
from torchx_tpu.parallel.prefetch import Prefetcher, device_prefetch
from torchx_tpu.parallel.remat_auto import choose_remat_policy
from torchx_tpu.parallel.xla_cache import setup_compilation_cache
from torchx_tpu.train.data import TokenDataset
from torchx_tpu.train.report import (
    _launch_ref,
    _launch_span,
    _make_profiler,
    _profile_enabled,
    _report_first_step,
    _shard_report,
    _step_heartbeat,
    device_peak_flops,
)
from torchx_tpu.train.step import (
    TrainState,
    abstract_train_state,
    init_state,
    make_optimizer,
    make_train_step,
    synthetic_batch,
)


@dataclasses.dataclass
class TrainRun:
    """One training run between its stages: what :func:`resolve` decided,
    then what :func:`open_io`, :func:`compile_step` and :func:`join` built.
    After ``join`` a caller can step it itself:
    ``run.state, loss, aux = run.step_fn(run.state, run.next_batch())``."""

    cfg: llama.LlamaConfig  # resolved: max_seq, kernels, a concrete remat policy
    mesh: Mesh
    optimizer: optax.GradientTransformation
    batch: int
    seq: int
    device: dict[str, Any]  # parallel.mesh.device_info()
    peak_flops: float  # of all the mesh's devices (the MFU denominator)
    kernels_used: str
    launch_ref: float  # the clock launch-to-first-step starts on
    breakdown: dict[str, float] = dataclasses.field(default_factory=dict)
    ckpt: Optional[Any] = None  # parallel.checkpoint.Checkpointer
    ckpt_every: int = 0
    resumed_step: int = 0
    prefetch: int = 2
    grad_plan: Optional[Any] = None  # parallel.overlap.BucketPlan
    grad_bucket_mb_used: int = 0
    bucket_trials: tuple = ()
    step_fn: Optional[Any] = None  # the compiled step
    state: Optional[TrainState] = None
    feed: Optional[Prefetcher] = None
    first_batch: Optional[dict[str, jnp.ndarray]] = None  # pulled during compile

    def stage(self, name: str, seconds: float) -> None:
        """Record one ``launch.breakdown`` stage."""
        self.breakdown[name] = seconds
        obs_metrics.LAUNCH_STAGE_SECONDS.observe(seconds, stage=name)

    def next_batch(self) -> dict[str, jnp.ndarray]:
        """The feed's next batch, the one pulled during the compile first."""
        if self.first_batch is not None:
            first, self.first_batch = self.first_batch, None
            return first
        return next(self.feed)

    @property
    def remat_policy_used(self) -> str:
        """What the step actually does — "none" when remat is off entirely."""
        return self.cfg.remat_policy if self.cfg.remat else "none"

    @property
    def tokens_per_step(self) -> int:
        """Tokens one step consumes: the global batch times the sequence."""
        return self.batch * self.seq

    @property
    def flops_per_token(self) -> float:
        """Model FLOPs a token costs at this run's sequence (``max_seq == seq``)."""
        return self.cfg.flops_per_token()

    @property
    def n_devices(self) -> int:
        """Devices jax sees (the per-chip divisor of the rates)."""
        return self.device["device_count"]


class Timed(NamedTuple):
    """What :func:`loop` measured."""

    loss: Any
    total_s: float
    timed_steps: int
    data_wait_s: float
    preempted: bool
    profile_summary: Optional[Any]


class _Background:
    """``work()`` on a daemon thread under a copy of the caller's context
    (spans started there keep their parent); :meth:`join` hands back its
    value or re-raises its error, and ``seconds`` is how long it ran."""

    def __init__(self, name: str, work: Callable[[], Any]) -> None:
        self._box: dict[str, Any] = {}
        ctx = contextvars.copy_context()

        def body() -> None:
            t0 = time.monotonic()
            try:
                self._box["value"] = work()
            except BaseException as e:  # noqa: BLE001 - re-raised on join
                self._box["error"] = e
            self.seconds = time.monotonic() - t0

        self._thread = threading.Thread(
            target=lambda: ctx.run(body), name=name, daemon=True
        )
        self._thread.start()

    def join(self) -> Any:
        self._thread.join()
        if "error" in self._box:
            raise self._box["error"]
        return self._box["value"]


class Pending(NamedTuple):
    """What :func:`open_io` left in flight for :func:`join`."""

    lower_state: Any  # the state to lower against: real, or abstract on resume
    latest: Optional[int]  # the newest checkpointed step the data stream assumed
    data_path: Optional[str]
    data: Optional[_Background]
    restore: Optional[_Background]


def _resolve_kernels(
    cfg: llama.LlamaConfig, kernels: str
) -> tuple[llama.LlamaConfig, str]:
    if not kernels or kernels == "reference":
        return cfg, "reference"
    # "pallas" degrades to "reference" off-TPU (the Mosaic kernels
    # need real TPU cores); "interpret" runs the same kernels through
    # the Pallas interpreter anywhere (tests, CPU sim)
    from torchx_tpu.ops.fused import resolve_kernels

    kernels_used = resolve_kernels(kernels)
    if kernels_used != kernels and jax.process_index() == 0:
        print(
            f"kernels: {kernels!r} unavailable on this backend;"
            " using reference ops",
            flush=True,
        )
    return dataclasses.replace(cfg, kernels=kernels_used), kernels_used


def _resolve_remat(run: TrainRun) -> None:
    cfg = run.cfg
    if cfg.remat_policy != "auto":
        return
    if not cfg.remat:
        # remat disabled: the policy is never consulted, but "auto"
        # must not leak into traces/results as if it were concrete
        run.cfg = dataclasses.replace(cfg, remat_policy="full")
        return
    # resolve "auto" -> the cheapest-recompute policy whose
    # compiled step fits HBM (trial compiles land in the
    # persistent XLA cache, so the winner's real compile below is
    # a cache hit)
    t0 = time.monotonic()
    with _launch_span("launch.remat_select"):
        policy, trials = choose_remat_policy(cfg, run.mesh, run.batch, run.seq)
    run.cfg = dataclasses.replace(cfg, remat_policy=policy)
    run.stage("remat_select", time.monotonic() - t0)
    if jax.process_index() == 0:
        verdicts = ", ".join(
            f"{t.policy}={'fits' if t.fits else 'no'}" for t in trials
        )
        print(f"remat auto -> {policy} ({verdicts})", flush=True)


def resolve(
    cfg: llama.LlamaConfig,
    mesh_config: MeshConfig,
    batch: int,
    seq: int,
    lr: float = 3e-4,
    warmup: int = 100,
    kernels: str = "reference",
    launch_anchor: Optional[float] = None,
) -> TrainRun:
    """Decide what will run: the launch clock, ``max_seq``, the kernels this
    backend has, the mesh (the first device query: backend init), the
    optimizer, and a concrete remat policy for ``"auto"``."""
    t_call = time.monotonic()
    launch_ref = _launch_ref(t_call, launch_anchor)
    cfg, kernels_used = _resolve_kernels(
        dataclasses.replace(cfg, max_seq=seq), kernels
    )
    t0 = time.monotonic()
    with _launch_span("launch.backend_init"):
        setup_compilation_cache()  # relaunches compile in seconds, not minutes
        mesh = make_mesh(mesh_config)  # first device query: backend init
        device = device_info()
        peak = device_peak_flops() * device["device_count"]
    run = TrainRun(
        cfg=cfg,
        mesh=mesh,
        optimizer=make_optimizer(lr=lr, warmup=warmup),
        batch=batch,
        seq=seq,
        device=device,
        peak_flops=peak,
        kernels_used=kernels_used,
        launch_ref=launch_ref,
    )
    run.stage("import", t_call - launch_ref)
    run.stage("backend_init", time.monotonic() - t0)
    _resolve_remat(run)
    return run


def _init_state(run: TrainRun) -> TrainState:
    t0 = time.monotonic()
    with _launch_span("launch.init_state"):
        state = init_state(run.cfg, run.mesh, run.optimizer)
    run.stage("init_state", time.monotonic() - t0)
    return state


def _token_stream(
    run: TrainRun, data_path: str, start_step: int
) -> tuple[dict[str, jnp.ndarray], Prefetcher]:
    """The corpus stream from ``start_step`` and its first batch, pulled now
    so that its host->device transfer overlaps the compile instead of the
    first step."""
    feed = device_prefetch(
        ({"tokens": rows} for rows in
         TokenDataset(data_path, run.seq, run.batch, start_step=start_step)),
        run.mesh,
        depth=run.prefetch,
    )
    return next(feed), feed


def open_io(
    run: TrainRun,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    data_path: Optional[str] = None,
    prefetch: int = 2,
) -> Pending:
    """Open the checkpointer and list its latest step (no tensor IO), then
    start what the compile will overlap: the corpus thread and, when
    resuming, the restore thread onto the ABSTRACT train state (skipping
    the init compile entirely). A fresh run initialises its state here."""
    run.prefetch = prefetch
    latest = None
    if ckpt_dir:
        from torchx_tpu.parallel.checkpoint import Checkpointer

        run.ckpt_every = ckpt_every or 100  # ckpt_dir alone must still checkpoint
        run.ckpt = Checkpointer(ckpt_dir, save_interval_steps=run.ckpt_every)
        latest = run.ckpt.latest_step()  # cheap step listing, no tensor IO
    run.resumed_step = latest or 0

    data = None
    if data_path:
        start_step = run.resumed_step

        def _data_setup() -> tuple[dict[str, jnp.ndarray], Prefetcher]:
            with _launch_span("launch.data_setup"):
                return _token_stream(run, data_path, start_step)

        data = _Background("tpx-data-setup", _data_setup)

    if latest is None:
        run.state = _init_state(run)
        return Pending(run.state, latest, data_path, data, None)

    lower_state = abstract_train_state(run.cfg, run.mesh, run.optimizer)

    def _restore() -> tuple[Optional[int], Optional[TrainState]]:
        with _launch_span("launch.restore", step=latest):
            return run.ckpt.restore_latest(lower_state)

    restore = _Background("tpx-ckpt-restore", _restore)
    return Pending(lower_state, latest, data_path, data, restore)


def compile_step(run: TrainRun, lower_state: Any, grad_bucket_mb: Any = 0) -> None:
    """AOT-compile the step against ``lower_state`` while restore and data
    IO are in flight. The loop then calls the Compiled executable directly
    — no per-step jit cache lookup — and variant configs (e.g. the int8
    bench leg) lower to distinct programs that each land in (and relaunch
    from) the persistent XLA cache."""
    t0 = time.monotonic()
    state_shardings = jax.tree.map(lambda x: x.sharding, lower_state)

    # resolve --grad-bucket-mb against the (possibly abstract) param tree:
    # bucket layout only needs shapes/dtypes, so the plan is fixed before
    # the compile and never perturbs the compilation cache between runs
    if grad_bucket_mb not in (0, "0", None, ""):
        from torchx_tpu.parallel import overlap

        run.grad_bucket_mb_used, run.bucket_trials = overlap.resolve_bucket_mb(
            lower_state.params, grad_bucket_mb
        )
        run.grad_plan = overlap.plan_buckets(
            lower_state.params, run.grad_bucket_mb_used * 1024 * 1024
        )
        if jax.process_index() == 0:
            print(f"grad buckets -> {run.grad_plan.describe()}", flush=True)

    train_step = make_train_step(
        run.cfg, run.mesh, run.optimizer, state_shardings=state_shardings,
        grad_bucket_plan=run.grad_plan,
    )
    batch_sds = {
        "tokens": jax.ShapeDtypeStruct(
            (run.batch, run.seq + 1),
            jnp.int32,
            sharding=NamedSharding(run.mesh, BATCH_SPEC),
        )
    }
    with _launch_span("launch.compile"):
        run.step_fn = train_step.lower(lower_state, batch_sds).compile()
    run.stage("compile", time.monotonic() - t0)


def _join_restore(run: TrainRun, restore: _Background) -> None:
    step, state = restore.join()
    if state is None:
        # every candidate step failed verification and was quarantined
        # (restore_latest returned (None, None)): train from scratch
        # instead of dying on the missing state
        run.state = _init_state(run)
        run.resumed_step = 0
        if jax.process_index() == 0:
            print(
                "no restorable checkpoint step (all quarantined);"
                " starting fresh",
                flush=True,
            )
        return
    run.state = state
    run.resumed_step = int(step)
    run.stage("restore", restore.seconds)
    if jax.process_index() == 0:
        print(f"resumed from checkpoint step {run.resumed_step}", flush=True)


def join(run: TrainRun, pending: Pending) -> None:
    """Collect what the threads did: the restored state (or a fresh one
    when no step could be restored) and the feed with its first batch."""
    if pending.restore is not None:
        _join_restore(run, pending.restore)
    if pending.data is None:
        # constant device batch: passthrough prefetcher (depth 0) keeps one
        # code path and an honest (≈0) data-wait account
        data = synthetic_batch(run.cfg, run.mesh, run.batch, run.seq)
        run.feed = Prefetcher(itertools.repeat(data), depth=0)
        return
    run.first_batch, run.feed = pending.data.join()
    if run.resumed_step != (pending.latest or 0):
        # restore fell back past a corrupt newest step: rebuild the
        # stream so data and params resume from the same step
        run.feed.close()
        run.first_batch, run.feed = _token_stream(
            run, pending.data_path, run.resumed_step
        )
    run.stage("data_setup", pending.data.seconds)


def first_step(run: TrainRun) -> tuple[Any, float]:
    """Step 1 (already AOT-compiled) = launch-to-first-step; returns its
    loss and the seconds since the launch clock started."""
    t0 = time.monotonic()
    with _launch_span("launch.first_step"):
        run.state, loss, _ = run.step_fn(run.state, run.next_batch())
        jax.block_until_ready(loss)
    first_step_s = time.monotonic() - run.launch_ref
    run.stage("first_step", time.monotonic() - t0)
    if jax.process_index() == 0:
        print(
            f"step 1 loss={float(loss):.4f}"
            f" launch-to-first-step={first_step_s:.1f}s",
            flush=True,
        )
        _report_first_step(first_step_s, run.resumed_step, run.breakdown)
    return loss, first_step_s


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


def _save_on_preempt(run: TrainRun, state: TrainState, global_step: int) -> None:
    jax.block_until_ready(state.params)
    if run.ckpt is not None:
        run.ckpt.save(global_step, state, force=True)
        run.ckpt.wait()  # durable BEFORE the hard kill lands
    if jax.process_index() == 0:
        print(
            f"preemption notice: checkpointed step {global_step},"
            " exiting",
            flush=True,
        )


class _LogWindows:
    """The timed loop's log windows: each fences the device, times itself,
    feeds the step metrics and the heartbeat, and prints one window late."""

    def __init__(self, run: TrainRun, t0: float) -> None:
        self.run = run
        self.t0 = t0
        self.window_t0, self.window_i0 = t0, 0  # the open window's start: clock, step
        # the prefetcher's cumulative wait at the last log fence
        self.window_wait = run.feed.data_wait_s
        self.pending: Optional[dict] = None  # deferred entry: printed one window late

    def close_window(self, i: int, global_step: int, loss: Any, aux: Any) -> None:
        run = self.run
        with hot.span(hot.TRAIN_FENCE):
            jax.block_until_ready(loss)  # completion fence: timing only
        now = time.monotonic()
        tps = run.tokens_per_step / ((now - self.t0) / (i + 1))
        window_steps = i + 1 - self.window_i0
        window_dt = (now - self.window_t0) / window_steps
        window_mfu = (
            run.tokens_per_step / window_dt * run.flops_per_token / run.peak_flops
        )
        wait_now = run.feed.data_wait_s
        wait_per_step = (wait_now - self.window_wait) / window_steps
        self.window_wait = wait_now
        obs_metrics.STEP_SECONDS.observe(window_dt, phase="total")
        obs_metrics.STEP_SECONDS.observe(wait_per_step, phase="data_wait")
        _step_heartbeat(
            step=global_step,
            avg_step_s=round(window_dt, 6),
            data_wait_s=round(wait_per_step, 6),
            mfu=round(window_mfu, 4),
            remat_policy=run.remat_policy_used,
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
        self.flush()
        self.pending = {
            "step": global_step,
            "loss": loss,
            "aux": aux,
            "tps": tps,
            "mfu": tps * run.flops_per_token / run.peak_flops,
            "window_mfu": window_mfu,
        }
        self.window_t0, self.window_i0 = time.monotonic(), i + 1

    def flush(self) -> None:
        """Print the deferred entry, if there is one."""
        entry, self.pending = self.pending, None
        if entry is None or jax.process_index() != 0:
            return
        # the async copies issued at the log boundary are long since done;
        # float() here is a host-memory read, not a device round-trip
        aux_vec = entry["aux"]
        moe_note = (
            f" router_aux={float(aux_vec[llama.AUX_BALANCE]):.3f}"
            f" router_entropy={float(aux_vec[llama.AUX_ENTROPY]):.2f}"
            f" router_overflow={float(aux_vec[llama.AUX_OVERFLOW]):.1%}"
            if getattr(self.run.cfg, "n_experts", 0)
            else ""
        )
        print(
            f"step {entry['step']} loss={float(entry['loss']):.4f}"
            f" tokens/sec={entry['tps']:,.0f}"
            f" tokens/sec/chip={entry['tps'] / self.run.n_devices:,.0f}"
            f" MFU={entry['mfu']:.1%}"
            f" window_mfu={entry['window_mfu']:.1%}{moe_note}",
            flush=True,
        )


def _open_profiler(run: TrainRun, profile: bool) -> Optional[Any]:
    if not _profile_enabled(profile):
        return None
    profiler = _make_profiler(
        run.cfg, run.mesh, run.batch, run.seq, run.tokens_per_step,
        run.flops_per_token, run.peak_flops,
    )
    if profiler is not None:
        # per-next() wait intervals credit the current step's data_wait
        run.feed.set_wait_observer(profiler.observe_wait)
    return profiler


def _close_profiler(run: TrainRun, profiler: Optional[Any]) -> Optional[Any]:
    if profiler is None:
        return None
    run.feed.set_wait_observer(None)
    try:
        # summarize + tpx_profile_* gauges + the observe_collectives
        # calibration fold (when the mesh moved collective bytes)
        return profiler.close()
    except Exception as e:  # noqa: BLE001 - profiling is best-effort
        if jax.process_index() == 0:
            print(f"profile summary failed: {e}", flush=True)
        return None


def _timed_steps(
    run: TrainRun,
    timed_steps: int,
    global_step: int,
    log_every: int,
    profiler: Optional[Any],
    preempt_evt: Optional[threading.Event],
) -> tuple[Any, int, float, bool]:
    """The timed window: ``timed_steps`` steps with the checkpoint cadence,
    the SIGTERM fence and the log windows; ``(loss, global_step, seconds,
    preempted)``."""

    def _prof_phase(name: str):
        return profiler.phase(name) if profiler is not None else (
            contextlib.nullcontext()
        )

    step_fn, ckpt, state = run.step_fn, run.ckpt, run.state
    preempted = False
    t0 = time.monotonic()
    windows = _LogWindows(run, t0)
    for i in range(timed_steps):
        with hot.step_span(global_step + 1):
            if profiler is not None:
                # the phase boundary is host-visible only behind a
                # completion fence, so profiled steps serialize dispatch
                # (a measured, documented perturbation — the headline
                # bench legs run unprofiled)
                profiler.begin_step()
                b = run.next_batch()
                with profiler.phase("forward_backward"):
                    state, loss, aux = step_fn(state, b)
                    jax.block_until_ready(loss)
            else:
                state, loss, aux = step_fn(state, run.next_batch())
            # host-side global step counter: int(state.step) would force a
            # device sync every iteration, breaking dispatch pipelining
            global_step += 1
            if ckpt is not None and global_step % run.ckpt_every == 0:
                with _prof_phase("checkpoint"), hot.span(hot.TRAIN_CHECKPOINT):
                    ckpt.save(global_step, state)
            if preempt_evt is not None and preempt_evt.is_set():
                preempted = True
                _save_on_preempt(run, state, global_step)
                break
            if (i + 1) % log_every == 0 or i + 1 == timed_steps:
                with _prof_phase("host"), hot.span(hot.TRAIN_LOG):
                    windows.close_window(i, global_step, loss, aux)
            if profiler is not None:
                profiler.end_step(global_step)
    jax.block_until_ready(state.params)
    total = time.monotonic() - t0
    run.state = state
    windows.flush()  # after timing: the flush is off the clock
    return loss, global_step, total, preempted


def loop(
    run: TrainRun,
    steps: int,
    log_every: int = 1,
    profile: bool = False,
    profile_dir: Optional[str] = None,
) -> Timed:
    """Steps 2..``steps``: a few untimed warm-up steps (dispatch pipelining
    + allocator settling), then the timed window, then the final save."""
    warmup_steps = min(3, max(steps - 2, 0))
    for _ in range(warmup_steps):
        run.state, loss, _ = run.step_fn(run.state, run.next_batch())
    if warmup_steps:
        jax.block_until_ready(loss)

    profiler = _open_profiler(run, profile)
    if profile_dir and jax.process_index() == 0:
        # xprof trace of the steady-state steps (view with tensorboard or
        # xprofiler; the TPU observability hook from SURVEY §5)
        jax.profiler.start_trace(profile_dir)

    timed_steps = max(steps - 1 - warmup_steps, 1)
    wait_anchor = run.feed.data_wait_s  # the feed's cumulative wait at loop entry
    # preemption grace: SIGTERM sets the event; the loop fences, forces a
    # final durable save, and exits cleanly inside the notice window
    preempt_evt, restore_sigterm = _install_preempt_handler()
    try:
        loss, global_step, total, preempted = _timed_steps(
            run, timed_steps, run.resumed_step + 1 + warmup_steps, log_every,
            profiler, preempt_evt,
        )
        data_wait_s = run.feed.data_wait_s - wait_anchor
    finally:
        restore_sigterm()
        # graceful drain: release the prefetch producer even when the loop
        # exits early (error, interrupt) — never leave a thread blocked on
        # a full queue
        run.feed.close()
    if profile_dir and jax.process_index() == 0:
        jax.profiler.stop_trace()
        print(f"profile trace written to {profile_dir}", flush=True)
    if run.ckpt is not None:
        if run.ckpt.latest_step() != global_step:  # final state, any interval
            run.ckpt.save(global_step, run.state, force=True)
        run.ckpt.close()
    return Timed(
        loss, total, timed_steps, data_wait_s, preempted,
        _close_profiler(run, profiler),
    )


def summarize(
    run: TrainRun, loss: Any, first_step_s: float, timed: Optional[Timed] = None
) -> dict[str, Any]:
    """The result dict: where the step ran and what it lowered to, then
    the first step's numbers alone (a single-step smoke, ``timed`` None:
    the compile-including step is the only timing) or the timed window's."""
    tps = (
        run.tokens_per_step * timed.timed_steps / timed.total_s
        if timed
        else run.tokens_per_step / first_step_s
    )
    results = {
        # the device as jax reports it, the attention implementation the
        # layer body traced, and whether the fused norm ran (empty unless
        # --kernels selected it)
        **run.device,
        "attention": traced("attention"),
        "norm_residual": traced("norm_residual"),
        "largest_param_shards": _shard_report(run.state.params),
        "loss": float(timed.loss if timed else loss),
        "tokens_per_sec": tps,
        "tokens_per_sec_per_chip": tps / run.n_devices,
        "mfu": tps * run.flops_per_token / run.peak_flops,
        "launch_to_first_step_s": first_step_s,
        "launch_breakdown": dict(run.breakdown),
        "remat_policy": run.remat_policy_used,
        # step-time optimization knobs actually in effect for this run
        "kernels": run.kernels_used,
        "grad_bucket_mb": run.grad_bucket_mb_used,
        "grad_buckets": run.grad_plan.n_buckets if run.grad_plan else 0,
    }
    if timed is None:
        return results
    results.update(
        final_step=int(run.state.step),
        resumed_from_step=run.resumed_step,
        # steady-state step-time split: how much of each timed step the
        # host spent blocked on input vs the device computing
        step_time_s=timed.total_s / timed.timed_steps,
        data_wait_s=timed.data_wait_s,
        data_wait_frac=timed.data_wait_s / timed.total_s if timed.total_s > 0 else 0.0,
        prefetch_depth=run.prefetch,
        # True when a SIGTERM preemption notice cut the run short (the
        # final checkpoint is durable; the supervisor resubmits from it)
        preempted=timed.preempted,
    )
    if run.bucket_trials:
        results["grad_bucket_trials"] = [t.to_dict() for t in run.bucket_trials]
    if timed.profile_summary is not None:
        results["profile"] = timed.profile_summary
    return results


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
    """Train ``cfg`` on ``mesh_config`` for ``steps`` steps and report:
    the stages of this module, in order."""
    run = resolve(cfg, mesh_config, batch, seq, lr, warmup, kernels, launch_anchor)
    pending = open_io(run, ckpt_dir, ckpt_every, data_path, prefetch)
    compile_step(run, pending.lower_state, grad_bucket_mb)
    join(run, pending)
    loss, first_step_s = first_step(run)
    if steps <= 1:
        run.feed.close()
        return summarize(run, loss, first_step_s)
    timed = loop(run, steps, log_every, profile, profile_dir)
    return summarize(run, loss, first_step_s, timed)
