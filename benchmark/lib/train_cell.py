"""A training cell: the program's compiled step with its state, driven from
the seed through three checked steps and then, the same object, through the
measured window.

From the program: ``make_optimizer``, ``TrainState``, ``make_train_step``,
the mesh, ``TokenDataset`` and ``device_prefetch`` — the step, the state and
the feed of ``examples/train_llama.train()``, which itself hands back neither
state nor losses and takes no seed, so it cannot be the one object that the
check reads and the window times (PERF.md, open questions).
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import check, counts, device, kinds, models, peaks, trace as trace_lib
from .spec import Cell, scratch_dir

F32 = jnp.float32
CHECKED_STEPS = 3
# what the program's ``make_optimizer`` fixes besides lr and warm-up
ADAM = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0, "decay_steps": 100_000}


def write_tokens(path: str, seed: int, n_tokens: int, vocab: int) -> None:
    """The seeded corpus as the packed uint32 file the trainer's dataset reads."""
    rng = np.random.default_rng([int(seed), 0xDA7A])
    rng.integers(0, vocab, n_tokens, dtype=np.uint32).tofile(path)


def _leaf_norms(tree, minus=None) -> dict:  # noqa: ANN001
    """Each leaf's 2-norm (of ``tree - minus`` where given), reduced on the
    device inside one program so that no float32 copy of a tree is made."""
    if minus is None:
        minus = jax.tree.map(lambda x: jnp.zeros((), x.dtype), tree)
    norms = jax.jit(
        lambda t, m: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32)))), t, m
        )
    )(tree, minus)
    flat = jax.tree_util.tree_flatten_with_path(norms)[0]
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): float(v) for path, v in flat
    }


def _first_moment(opt_state):  # noqa: ANN001
    found = [
        s for s in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer state")
    return found[0].mu


def build(cfg, mesh, optimizer, config: dict, seed: int, batch: int, seq: int):  # noqa: ANN001
    """The one object: the state from the benchmark's seeded weights, and the
    program's step compiled for it. -> (state, step, shardings, seconds, seconds)"""
    from jax.sharding import NamedSharding

    from torchx_tpu.examples import train_llama as tl
    from torchx_tpu.models import llama
    from torchx_tpu.parallel.mesh import BATCH_SPEC

    t0 = time.monotonic()
    _, specs_fn = llama.model_fns(cfg)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs_fn(cfg, pp=False))
    params = models.make_weights(config, seed, shardings)
    state = tl.normalize_state_shardings(
        tl.TrainState(
            params=params,
            opt_state=jax.jit(optimizer.init)(params),
            step=jnp.zeros((), jnp.int32),
        ),
        mesh,
    )
    del params
    jax.block_until_ready(state)
    init_state_s = time.monotonic() - t0

    t0 = time.monotonic()
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    batch_sds = {
        "tokens": jax.ShapeDtypeStruct(
            (batch, seq + 1), jnp.int32, sharding=NamedSharding(mesh, BATCH_SPEC)
        )
    }
    step_fn = (
        tl.make_train_step(cfg, mesh, optimizer, state_shardings=state_shardings)
        .lower(state, batch_sds)
        .compile()
    )
    return state, step_fn, shardings, init_state_s, time.monotonic() - t0


def checked_steps(state, step_fn, feed, config: dict, seed: int, shardings):  # noqa: ANN001
    """The first steps, through the window's own call and feed. -> the state
    and what the check compares: each loss, the first gradient's norms as the
    optimizer got it (the first moment after one step over 1 - b1), and the
    norms of the parameters' change."""
    losses, program = [], {}
    for i in range(CHECKED_STEPS):
        state, loss, _ = step_fn(state, next(feed))
        losses.append(float(loss))
        if i == 0:
            mu = _leaf_norms(_first_moment(state.opt_state))
            program["first_grad"] = {k: v / (1 - ADAM["b1"]) for k, v in mu.items()}
    start = models.make_weights(config, seed, shardings)
    program["delta"] = _leaf_norms(state.params, start)
    program["losses"] = losses
    return state, program


def job_setup(cell: Cell, **config_overrides):  # noqa: ANN003
    """The program's config, mesh and optimizer for the cell's job, and its
    batch and sequence length."""
    from torchx_tpu.examples import train_llama as tl
    from torchx_tpu.parallel.mesh import make_mesh
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec

    job, dep = cell.traffic, cell.config["deployment"]
    batch, seq = int(dep["batch"]), int(job["seq"])
    cfg = models.program_config(
        cell.config, max_seq=seq, remat_policy=dep["remat_policy"], kernels="reference",
        **config_overrides,
    )
    mesh = make_mesh(parse_mesh_spec(dep["mesh"]), devices=jax.devices()[: cell.chips])
    optimizer = tl.make_optimizer(lr=job["lr"], warmup=job["warmup"])
    return cfg, mesh, optimizer, batch, seq


def program_int8_control(cell: Cell, seed: int, tokens_path: str) -> dict:
    """The program with its own int8 matmul path switched on, through the same
    first steps: what a later PR might be tempted to time."""
    from torchx_tpu.examples.data import TokenDataset
    from torchx_tpu.parallel.prefetch import device_prefetch

    config = cell.config
    cfg, mesh, optimizer, batch, seq = job_setup(cell, int8_matmuls=True)
    state, step_fn, shardings, _, _ = build(cfg, mesh, optimizer, config, seed, batch, seq)
    feed = device_prefetch(({"tokens": r} for r in TokenDataset(tokens_path, seq, batch)), mesh, depth=2)
    try:
        _, numbers = checked_steps(state, step_fn, feed, config, seed, shardings)
    finally:
        feed.close()
    return numbers


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    allow_cpu: bool = False,
    control: Optional[str] = None,
) -> dict:
    from torchx_tpu.examples.data import TokenDataset
    from torchx_tpu.parallel.prefetch import device_prefetch
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    dev = device.require_chips(cell.chips, allow_cpu)
    setup_compilation_cache()
    compiles = device.CompileCounter()
    config, job = cell.config, cell.traffic
    cfg, mesh, optimizer, batch, seq = job_setup(cell)

    tokens_path = os.path.join(scratch_dir(cell), "tokens.bin")
    write_tokens(tokens_path, seed, int(job["corpus_tokens"]), config["vocab_size"])

    state, step_fn, shardings, init_state_s, compile_s = build(
        cfg, mesh, optimizer, config, seed, batch, seq
    )

    fed: list[np.ndarray] = []  # the first host batches, for the reference

    def rows():
        for r in TokenDataset(tokens_path, seq, batch):
            if len(fed) < CHECKED_STEPS:
                fed.append(np.array(r))
            yield {"tokens": r}

    feed = device_prefetch(rows(), mesh, depth=2)
    try:
        # -- first steps, through the window's own call and feed -----------
        state, program = checked_steps(state, step_fn, feed, config, seed, shardings)
        for _ in range(2):  # settle the pipeline before the window opens
            state, loss, aux = step_fn(state, next(feed))
        jax.block_until_ready(loss)

        # -- the window ------------------------------------------------------
        profile_dir = os.path.join(scratch_dir(cell), "trace")
        shutil.rmtree(profile_dir, ignore_errors=True)
        trace_at, trace_steps = 3, int(job.get("trace_steps", 8))
        wait0 = feed.data_wait_s
        t_open = time.monotonic()
        steps, prev, tracing = 0, None, False
        while time.monotonic() - t_open < seconds:
            if trace and steps == trace_at:
                jax.block_until_ready(loss)
                jax.profiler.start_trace(profile_dir)
                tracing = True
            state, loss, aux = step_fn(state, next(feed))
            steps += 1
            if tracing and steps == trace_at + trace_steps:
                jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                tracing = False
            if prev is not None:
                jax.block_until_ready(prev)  # at most two steps in flight
            prev = loss
        jax.block_until_ready(state.params)
        t_close = time.monotonic()
        if tracing:
            jax.profiler.stop_trace()
        data_wait_s = feed.data_wait_s - wait0
    finally:
        feed.close()
    window_s = t_close - t_open
    final_loss = float(loss)
    aux_faults = kinds.of(config).aux_must_be_zero(aux)
    memory_peak = device.memory_peak_bytes(cell.chips)
    compiled_in_window = compiles.between(t_open, t_close)
    del state, step_fn, loss, aux, prev

    tokens_per_step = batch * seq
    tokens_per_s = steps * tokens_per_step / window_s
    flops_per_token = counts.train_flops_per_token(config, seq)
    run_rec = {
        "cell": cell,
        "device": dev,
        "memory_peak_bytes": memory_peak,
        "attempted": steps,
        "failed": 0 if math.isfinite(final_loss) else steps,
        "end_to_end": {
            "train_tokens_per_s_per_chip": (tokens_per_s / cell.chips, "tokens/s/chip"),
            "setup_s": (t_open - t_start, "s"),
        },
        "counters": {
            "steps": steps,
            "window_s": window_s,
            "step_s": window_s / steps,
            "data_wait_s": data_wait_s,
            "init_state_s": init_state_s,
            "compile_s": compile_s,
            "tokens_per_s": tokens_per_s,
            "flops_per_token": flops_per_token,
            "flops_per_step": flops_per_token * tokens_per_step,
            "chips": cell.chips,
            **aux_faults,
        },
        "trace": trace_lib.reduce_trace(profile_dir, cell.chips) if trace else None,
    }
    if dev["platform"] == "tpu":
        run_rec["counters"]["peak_flops_per_s"] = peaks.peak(dev["kind"], "bf16_flops_per_s")
    print(
        f"train: {steps} steps in {window_s:.3f}s, step {window_s / steps * 1e3:.2f} ms,"
        f" data wait {data_wait_s:.3f}s, final loss {final_loss:.4f},"
        f" compiles in window {compiled_in_window}",
        flush=True,
    )

    # -- correct: the reference follows the same first steps ---------------
    verdict = check.Verdict()
    if compiled_in_window:
        verdict.flag(f"{compiled_in_window} compilations inside the window")
    if not math.isfinite(final_loss):
        verdict.flag("final loss is not finite")
    for name, value in aux_faults.items():
        if value:
            verdict.flag(f"{name} {value} is not 0")
    t0 = time.monotonic()
    reference = follow_reference(config, seed, fed, job, None)
    print(f"check: reference followed {len(fed)} steps in {time.monotonic() - t0:.1f}s", flush=True)
    compare(verdict, program, reference, cell.check)
    if control:  # lower precision in the program's place, two ways
        low = follow_reference(config, seed, fed, job, control)
        for label, numbers in ((control, low), ("program_int8", program_int8_control(cell, seed, tokens_path))):
            shown = check.Verdict()
            compare(shown, numbers, reference, cell.check)
            for name, v, _ in shown.rows:
                print(f"control[{label}]: {name} = {v:.6g}", flush=True)
            run_rec.setdefault("controls", {})[label] = {name: v for name, v, _ in shown.rows}
        for leaf, ref in reference["first_grad"].items():  # every leaf, for a look
            print(f"leaf {leaf}: first gradient norm reference {ref:.6g}, program"
                  f" {program['first_grad'][leaf] / ref - 1:+.5f}, control"
                  f" {low['first_grad'][leaf] / ref - 1:+.5f}; change reference"
                  f" {reference['delta'][leaf]:.6g}, program"
                  f" {program['delta'][leaf] / max(reference['delta'][leaf], 1e-30) - 1:+.5f}", flush=True)
    run_rec["verdict"] = verdict
    run_rec["program_numbers"], run_rec["reference_numbers"] = program, reference
    return run_rec


def follow_reference(config: dict, seed: int, fed: list, job: dict, quant: Optional[str]) -> dict:
    """The reference's readings of the fed steps: the kind's loss under
    ``reference/train.py``'s clip and AdamW."""
    from benchmark.reference import train as ref_train

    weights = models.make_weights(config, seed)
    weights = jax.tree.map(lambda x: x.astype(F32), weights)
    opt = dict(ADAM, lr=job["lr"], warmup=job["warmup"])
    return ref_train.follow(kinds.reference(config).mean_nll, weights, fed, config, opt, quant)


def _shares(norms: dict) -> dict:
    total = math.sqrt(sum(v * v for v in norms.values())) or 1.0
    return {k: v / total for k, v in norms.items()}


def compare(verdict: check.Verdict, program: dict, reference: dict, limits: dict) -> None:
    """Each followed step's loss, the first gradient's norm and the
    parameters' change, the last two by the worst leaf."""
    n = len(reference["losses"])
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(program["losses"][:n], reference["losses"])
    )
    verdict.compare("loss_gap", loss_gap, limits["loss_gap_limit"])
    gap, leaf = check.worst_leaf_gap(program["first_grad"], reference["first_grad"])
    print(f"check: worst first-gradient leaf is {leaf}", flush=True)
    verdict.compare("first_grad_norm_gap", gap, limits["first_grad_norm_gap_limit"])
    # the same with each side's norms over its own global norm: the clip
    # divides by a global norm that the program rounds to bfloat16, one scalar
    # whose rounding moves every leaf alike by up to 0.7% and hides the leaves
    gap, leaf = check.worst_leaf_gap(_shares(program["first_grad"]), _shares(reference["first_grad"]))
    print(f"check: worst first-gradient share is {leaf}", flush=True)
    verdict.compare("first_grad_share_gap", gap, limits["first_grad_share_gap_limit"])
    gap, leaf = check.worst_leaf_gap(program["delta"], reference["delta"])
    print(f"check: worst parameter-change leaf is {leaf}", flush=True)
    verdict.compare("param_change_norm_gap", gap, limits["param_change_norm_gap_limit"])
