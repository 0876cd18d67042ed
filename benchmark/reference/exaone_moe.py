"""Plain reference of kind ``exaone_moe``: K-EXAONE-236B-A23B's block in float32
at ``precision=HIGHEST``, given one chip's share of it as the program is.

Straight ``jax.numpy``: no kernels, no cache, no sorting, no blocks walked. It
borrows the benchmark's plain pieces (``matmul`` with the fp8 control, RMSNorm,
the half-split rotary embedding, SwiGLU) and imports nothing of the program.
Layer ``i`` (``layer_types[i]``, counted over the whole stack):

* Attention: ``u = RMSNorm(x)``; ``q = u W_q`` as ``[h, hd]``, ``k = u W_k``,
  ``v = u W_v`` as ``[kvh, hd]``; ``q`` and ``k`` normed a head (RMSNorm over
  ``hd``, gains ``q_norm``, ``k_norm``); on a ``sliding_attention`` layer ``q``
  and ``k`` rotated (pairs ``(i, i + hd/2)``) and key ``j`` admitted for query
  ``i`` where ``i - sliding_window < j <= i``; on a ``full_attention`` layer no
  rotary embedding and ``j <= i``; softmax of ``q.k / sqrt(hd)``, each K/V head
  serving ``h / kvh`` query heads; ``W_o``.
* Sparse FFN (after ``first_k_dense_replace`` dense layers, a SwiGLU of
  ``intermediate_size``): ``s = sigmoid(u W_r)`` in float32 over all
  ``published_num_experts``; the ``num_experts_per_tok`` largest of ``s + b``
  chosen (``n_group`` = ``topk_group`` = 1: no group step); weights
  ``s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor`` over all the
  chosen: the bias chooses and never weighs; ``y = sum_e w_e SwiGLU_e(u) +
  SwiGLU_shared(u)``. Every expert given runs on every token and the others'
  weights are zero: plain, and exact. Nothing is dropped.

**The share** (the same departure as the program's, ``kinds/exaone_moe.py``):
the weights given hold ``num_experts`` routed experts, ids
``experts_held_from`` onward of the ``published_num_experts`` the router
scores. The sum over ``e`` runs over those; what the experts on the other
chips would add is left out, the shared expert is added once, and that partial
sum goes on to the next layer. With all experts given it is the published
layer (:func:`routed` of the eight shares sum to it). The embedding and the
head are one slice of the vocabulary. Left out here as in the program: the
multi-token-prediction block.

``quant="fp8"`` is the control (``reference/model.py::matmul``): every weight
matmul in float8, the router excepted as for Mixtral and ``mla_moe``, all else
float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import F32, HI, matmul, rms_norm, rope, swiglu

GROUPS = ("dense_layers", "layers")  # the parameter tree's groups of equal layers, as they run
VOCAB_CHUNK = 16384  # the head is multiplied this many columns at a time


def masked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window: int) -> jnp.ndarray:
    """Causal grouped-query attention, ``window`` > 0 admitting only the last
    ``window`` keys; one K/V head at a time so that only one group's ``[s, s]``
    scores are alive."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, hd)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = (j <= i) & (j > i - window) if window else j <= i

    def one_group(args):  # noqa: ANN001
        qh, kh, vh = args  # [b, s, rep, hd], [b, s, hd], [b, s, hd]
        scores = jnp.einsum("bqrd,bkd->brqk", qh, kh, precision=HI) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("brqk,bkd->bqrd", probs, vh, precision=HI)

    out = jax.lax.map(
        jax.checkpoint(one_group), (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0))
    )  # [kvh, b, s, rep, hd]
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h * hd)


def attention(u: jnp.ndarray, lw: dict, c: dict, sliding: bool, quant: Optional[str]) -> jnp.ndarray:
    b, s, _ = u.shape
    h, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = rms_norm(matmul(u, lw["wq"], quant).reshape(b, s, h, hd), lw["q_norm"], c["rms_norm_eps"])
    k = rms_norm(matmul(u, lw["wk"], quant).reshape(b, s, kvh, hd), lw["k_norm"], c["rms_norm_eps"])
    v = matmul(u, lw["wv"], quant).reshape(b, s, kvh, hd)
    if sliding:
        theta = c["rope_theta"] if "rope_theta" in c else float(c["rope_parameters"]["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    return matmul(masked_attention(q, k, v, c["sliding_window"] if sliding else 0), lw["wo"], quant)


def routed(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    """The weighted sum of the routed experts given, routed over all published."""
    scores = jax.nn.sigmoid(matmul(u, lw["w_router"], None))  # [b, s, published] float32
    _, chosen = jax.lax.top_k(scores + lw["router_bias"].astype(F32), c["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if c.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * float(c["routed_scaling_factor"])
    weight = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32) * picked[..., None], axis=-2)  # [b, s, published]
    first, held = c.get("experts_held_from", 0), lw["w_gate"].shape[0]
    weight = weight[..., first : first + held]  # the experts that live here; the others' routings add nothing here

    def one_expert(out, args):  # noqa: ANN001
        w_gate, w_up, w_down, w_e = args
        return out + w_e[..., None] * swiglu(u, w_gate, w_up, w_down, quant), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros(u.shape, F32),
        (lw["w_gate"], lw["w_up"], lw["w_down"], jnp.moveaxis(weight, -1, 0)),
    )  # fmt: skip
    return out


def layer(x: jnp.ndarray, lw: dict, c: dict, sliding: bool, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on ``x[b, s, d]`` with that layer's weights: a sparse
    layer where it has a router, else a dense one."""
    eps = c["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["attn_norm"], eps), lw, c, sliding, quant)
    m = rms_norm(x, lw["mlp_norm"], eps)
    if "w_router" in lw:
        return x + routed(m, lw, c, quant) + swiglu(m, lw["ws_gate"], lw["ws_up"], lw["ws_down"], quant)
    return x + swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[.., vocab]`` logits over the vocabulary's slice, a chunk of columns at a time."""
    x = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    w = params["lm_head"]
    return jnp.concatenate(
        [matmul(x, w[:, i : i + VOCAB_CHUNK], quant) for i in range(0, w.shape[1], VOCAB_CHUNK)], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("config_items", "sliding", "quant"))
def _layer_jit(x, layers, i, config_items, sliding, quant):  # noqa: ANN001
    # sliced inside the program: no copy of a whole layer's experts is made
    lw = {k: w[i] for k, w in layers.items()}
    return layer(x, lw, dict(config_items), sliding, quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _head_jit(x, params, config_items, quant):  # noqa: ANN001
    return head(x, params, dict(config_items), quant)


def _static(c: dict) -> tuple:
    """What the equations read, hashable; the rotary base out of its nested group."""
    keys = (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "sliding_window",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "experts_held_from",
    )  # fmt: skip
    items = [(k, c[k]) for k in keys if c.get(k) is not None]
    return (*items, ("rope_theta", float(c["rope_parameters"]["rope_theta"])))


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if k not in GROUPS}


def _sliding(c: dict) -> list[bool]:
    return [t == "sliding_attention" for t in c["layer_types"]]


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, d]`` float32: what :func:`head` is given, the residual stream
    behind the last layer, layer by layer so that only one layer's float32
    copies are alive beside the given weights."""
    x = params["embed"][tokens].astype(F32)
    sliding, at = _sliding(c), 0
    for group in GROUPS:
        if group in params:
            for i in range(params[group]["wq"].shape[0]):
                x = _layer_jit(x, params[group], jnp.int32(i), _static(c), sliding[at], quant)
                at += 1
    return x


def logits(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, vocab]`` float32 logits of ``tokens[b, s]``: the head over the
    whole :func:`stream`. The benchmark's check never holds these: it gives
    :func:`head` the served positions a slice at a time (``lib/serve_cell.py``)."""
    return _head_jit(stream(params, tokens, c, quant), _top(params), _static(c), quant)


def mean_nll(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None):
    """Mean next-token negative log-likelihood of ``tokens[b, s+1]``, each layer
    recomputed in the backward pass."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs].astype(F32)
    static, sliding, at = dict(_static(c)), _sliding(c), 0
    for group in GROUPS:
        if group in params:
            for i in range(params[group]["wq"].shape[0]):
                lw = {k: w[i] for k, w in params[group].items()}
                x = jax.checkpoint(functools.partial(layer, c=static, sliding=sliding[at], quant=quant))(x, lw)
                at += 1
    top = _top(params)

    def row_nll(args):  # noqa: ANN001 - one row's [s, vocab] logits at a time
        xr, tr = args
        lg = head(xr[None], top, static, quant)[0]
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tr[:, None], axis=-1)[:, 0]

    return jnp.mean(jax.lax.map(jax.checkpoint(row_nll), (x, targets)))
