"""Plain reference of the training step around a kind's loss: gradients,
clipping, AdamW. What no model kind owns; the loss itself (``mean_nll``) is
in the kind's reference, beside its layer.

Float32 throughout at ``precision=HIGHEST``; the optimizer is written out
here (global-norm clip, AdamW with bias correction and decoupled weight decay,
linear warm-up of the learning rate from zero) and imports no optimizer
library and nothing of the program. The moments live on the host between
steps so that float32 weights and gradients of a model that fills the chip in
bfloat16 still fit it.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def named_leaves(tree: dict) -> list:
    """``[(name, leaf)]`` with names like ``layers/wq``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf) for path, leaf in flat]


def leaf_norms(tree: dict) -> dict:
    """``{name: float}`` of each leaf's 2-norm."""
    return {
        name: float(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(F32)))))
        for name, leaf in named_leaves(tree)
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def _adamw_leaf(p, g, m, v, clip_scale, lr, t, b1, b2, eps, wd):  # noqa: ANN001
    g = g * clip_scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1**t)
    v_hat = v / (1 - b2**t)
    return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p), m, v


def learning_rate(count: int, opt: dict) -> float:
    """Linear warm-up from zero to ``lr`` over ``warmup`` steps, then a cosine
    decay to a tenth of it at step ``decay_steps``."""
    if count < opt["warmup"]:
        return opt["lr"] * count / opt["warmup"]
    span = opt["decay_steps"] - opt["warmup"]
    cosine = 0.5 * (1.0 + math.cos(math.pi * min(count - opt["warmup"], span) / span))
    return opt["lr"] * (0.9 * cosine + 0.1)


def follow(mean_nll: Callable, params: dict, batches: list, c: dict, opt: dict,
           quant: Optional[str] = None) -> dict:
    """Follow ``len(batches)`` steps from float32 ``params`` under the kind's
    ``mean_nll(params, tokens, c, quant)``. Returns each
    step's loss, the per-leaf norm of the first gradient as the optimizer gets
    it (after the clip), and the per-leaf norm of the parameters' change."""
    grad_fn = jax.jit(
        jax.value_and_grad(lambda p, t: mean_nll(p, t, c, quant))
    )
    start = jax.tree.map(lambda x: np.asarray(x), params)  # host copy of step 0
    moments = None
    losses, first_grad = [], None
    for step, tokens in enumerate(batches):
        loss, grads = grad_fn(params, jnp.asarray(tokens))
        losses.append(float(loss))
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.where(gnorm < opt["clip"], 1.0, opt["clip"] / gnorm)
        if step == 0:  # the clip scales every leaf alike; no second tree is made
            first_grad = {k: v * float(scale) for k, v in leaf_norms(grads).items()}
            moments = jax.tree.map(
                lambda g: (np.zeros(g.shape, np.float32), np.zeros(g.shape, np.float32)),
                grads,
            )
        lr = learning_rate(step, opt)
        p_leaves, treedef = jax.tree.flatten(params)
        g_leaves = jax.tree.leaves(grads)
        mv_leaves = treedef.flatten_up_to(moments)
        del params, grads
        new_p, new_mv = [], []
        for i in range(len(p_leaves)):
            p, m, v = _adamw_leaf(
                p_leaves[i], g_leaves[i], mv_leaves[i][0], mv_leaves[i][1],
                scale, lr, float(step + 1), opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
            )
            p_leaves[i] = g_leaves[i] = None
            new_p.append(p)
            new_mv.append((np.asarray(m), np.asarray(v)))
        params = jax.tree.unflatten(treedef, new_p)
        moments = jax.tree.unflatten(treedef, new_mv)
    delta = {  # leaf by leaf: no second tree on the device
        name: float(jnp.sqrt(jnp.sum(jnp.square(now - jnp.asarray(was)))))
        for (name, now), was in zip(named_leaves(params), jax.tree.leaves(start))
    }
    return {"losses": losses, "first_grad": first_grad, "delta": delta}
