"""What a training step is: the optimizer, the state it updates, the
jitted SPMD step, and the state's shapes and shardings without the state.

Everything that builds or describes ``jit_step`` lives here, once: the
trainer (:mod:`torchx_tpu.train.run`) calls it in a loop, the AOT-fit
machinery (:mod:`torchx_tpu.parallel.aot_fit`) compiles it against
abstract inputs, and the benchmark's harness runs it in a loop of its own.
This module knows the model, the mesh and the hot-path scope names — not
the run around the step, and not what the job reports about itself.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchx_tpu.models import llama
from torchx_tpu.obs import hot
from torchx_tpu.parallel.mesh import BATCH_SPEC


def make_optimizer(
    lr: float = 3e-4, weight_decay: float = 0.1, warmup: int = 100
) -> optax.GradientTransformation:
    """Global-norm clipping at 1.0, then AdamW on a warm-up + cosine
    schedule; each half runs under its own scope of the compiled step
    (``grad_clip``, ``optimizer``)."""
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
    """What one step reads and returns: parameters, the optimizer's state
    and the step count, as one pytree."""

    params: llama.Params
    opt_state: Any
    step: jnp.ndarray


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[]
)


def init_state(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    seed: int = 0,
) -> TrainState:
    """Initialize params *sharded* (jit with out_shardings so the full
    fp32 model never materializes on one device)."""
    init_fn, specs_fn = llama.model_fns(cfg)  # dense vs MoE dispatch
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
    """One seeded batch of uniform random tokens, placed on the mesh."""
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )
    return {"tokens": jax.device_put(tokens, NamedSharding(mesh, BATCH_SPEC))}


def _specs_for_state(state_shapes: Any, param_specs: Any) -> Any:
    """PartitionSpec tree matching a TrainState shape tree.

    Optimizer-state subtrees that mirror the params tree (Adam's mu/nu)
    inherit the param specs wholesale; everything else (step counters,
    empty states) replicates. Matching is by pytree structure, so this
    stays correct for any optax chain whose stateful members mirror params.
    """
    params_treedef = jtu.tree_structure(state_shapes.params)

    def rec(node: Any) -> Any:
        try:
            if jtu.tree_structure(node) == params_treedef:
                return param_specs
        except Exception:
            pass
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # namedtuple
            return type(node)(*(rec(c) for c in node))
        if isinstance(node, (list, tuple)):
            return type(node)(rec(c) for c in node)
        return P()  # scalar / unrecognized leaf: replicated

    return dataclasses.replace(
        state_shapes,
        params=param_specs,
        opt_state=rec(state_shapes.opt_state),
        step=P(),
    )


def abstract_train_state(cfg: Any, mesh: Mesh, optimizer: Any):
    """TrainState of ShapeDtypeStructs carrying the training shardings."""
    init_fn, specs_fn = llama.model_fns(cfg)  # dense vs MoE dispatch
    params_shapes = jax.eval_shape(
        lambda: init_fn(cfg, jax.random.PRNGKey(0))
    )
    opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
    state_shapes = TrainState(
        params=params_shapes,
        opt_state=opt_shapes,
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )
    pspecs = specs_fn(cfg, pp=mesh.shape.get("pp", 1) > 1)
    spec_tree = _specs_for_state(state_shapes, pspecs)
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)
        ),
        state_shapes,
        spec_tree,
    )
