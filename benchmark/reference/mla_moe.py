"""Plain reference of kind ``mla_moe``: the language model of Kimi-VL-A3B
(DeepSeek-V3's block) in float32 at ``precision=HIGHEST``, as published.

Straight ``jax.numpy``: no kernels, no cache, no absorption, no sorting. It
borrows the benchmark's plain pieces (``matmul`` with the fp8 control,
RMSNorm, SwiGLU) and imports nothing of the program. Layer by layer:

* Attention (``q_lora_rank`` null): ``u = RMSNorm(x)``; ``q = u W_q`` as ``[h,
  nope + rope]``; ``u W_kva`` as ``[rank + rope]`` = ``(c, k_rope)``; ``c =
  RMSNorm_kv(c)``; ``q_rope`` and ``k_rope`` rotated in interleaved pairs ``(2i,
  2i+1)``; ``c W_kvb`` as ``[h, nope + v]`` = ``(k_nope, v)``; every head's key is
  ``(k_nope, k_rope)``; causal softmax of ``q.k / sqrt(nope + rope)``; ``W_o``.
* Expert layers (after ``first_k_dense_replace`` dense ones): ``s = sigmoid(u
  W_r)`` in float32; the ``num_experts_per_tok`` largest of ``s + b`` are chosen
  (``n_group`` = ``topk_group`` = 1: the group step is the identity); weights
  ``s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``: the bias
  chooses and never weighs; ``y = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)``.
  Every expert runs on every token and the others' weights are zero: plain,
  and exact. Nothing is dropped.
* A dense layer is a SwiGLU of ``intermediate_size``.

One departure, in how the weights are stored and not in the equations: the
program rotates dimension ``i`` with ``i + rope/2`` (``torchx_tpu/ops/rope.py``),
so the benchmark's seeded weights hold the ``rope`` rotary columns of each head
of ``W_q``, and of ``W_kva``, in the order evens first, then odds. This
reference puts the activations those columns give back into the published
order (:func:`_published_order`) and rotates them as published. Not built, here
or in the program: the vision tower and its projector (the catalog row has no
keys for them), and expert parallelism (``ep_size`` 1).

``quant="fp8"`` is the control (``reference/model.py::matmul``): every weight
matmul in float8, the router excepted as for Mixtral, all else float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import F32, HI, matmul, rms_norm, swiglu

GROUPS = ("dense_layers", "layers")  # the parameter tree's groups of equal layers, as they run
VOCAB_CHUNK = 16384  # the head is multiplied this many columns at a time


def _published_order(x: jnp.ndarray) -> jnp.ndarray:
    """``[..., rope]`` stored evens first, then odds -> the published order."""
    half = x.shape[-1] // 2
    return jnp.stack((x[..., :half], x[..., half:]), axis=-1).reshape(x.shape)


def rope_interleaved(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding of ``[b, s, heads, rope]`` at positions 0..s-1, pair
    ``i`` being dimensions ``(2i, 2i+1)``, as the checkpoint is published."""
    rope = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rope, 2, dtype=F32) / rope))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack((even * cos - odd * sin, odd * cos + even * sin), axis=-1).reshape(x.shape)


def mla(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    """Latent attention of the normed ``u [b, s, d]``, expanded, one head's
    ``[s, s]`` scores alive at a time."""
    b, s, _ = u.shape
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    theta = float(c["rope_theta"])
    q = matmul(u, lw["wq"], quant).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_interleaved(_published_order(q[..., dn:]), theta)
    kva = matmul(u, lw["w_kva"], quant)
    latent = rms_norm(kva[..., :r], lw["kv_norm"], c["rms_norm_eps"])
    k_rope = rope_interleaved(_published_order(kva[..., None, r:]), theta)[:, :, 0]  # [b, s, rope]
    kv = matmul(latent, lw["w_kvb"], quant).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one_head(args):  # noqa: ANN001
        qn, qr, kn, vh = args  # [b, s, .] of one head
        scores = (
            jnp.einsum("bqd,bkd->bqk", qn, kn, precision=HI) + jnp.einsum("bqd,bkd->bqk", qr, k_rope, precision=HI)
        ) * (dn + dr) ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, vh, precision=HI)

    heads = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    out = jax.lax.map(jax.checkpoint(one_head), (heads(q_nope), heads(q_rope), heads(k_nope), heads(v)))
    return matmul(jnp.moveaxis(out, 0, 2).reshape(b, s, h * dv), lw["wo"], quant)


def experts(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    """The routed experts' weighted sum and the shared expert beside it."""
    scores = jax.nn.sigmoid(matmul(u, lw["w_router"], None))  # [b, s, E] float32
    _, chosen = jax.lax.top_k(scores + lw["router_bias"].astype(F32), c["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if c.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * float(c["routed_scaling_factor"])
    n_experts = lw["w_router"].shape[-1]
    weight = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32) * picked[..., None], axis=-2)  # [b, s, E]

    def one_expert(out, args):  # noqa: ANN001
        w_gate, w_up, w_down, w_e = args
        return out + w_e[..., None] * swiglu(u, w_gate, w_up, w_down, quant), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros(u.shape, F32),
        (lw["w_gate"], lw["w_up"], lw["w_down"], jnp.moveaxis(weight, -1, 0)),
    )  # fmt: skip
    return routed + swiglu(u, lw["ws_gate"], lw["ws_up"], lw["ws_down"], quant)


def layer(x: jnp.ndarray, lw: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on ``x[b, s, d]`` with that layer's weights: an
    expert layer where it has a router, else a dense one."""
    eps = c["rms_norm_eps"]
    x = x + mla(rms_norm(x, lw["attn_norm"], eps), lw, c, quant)
    m = rms_norm(x, lw["mlp_norm"], eps)
    if "w_router" in lw:
        return x + experts(m, lw, c, quant)
    return x + swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[.., vocab]`` logits, the head's columns a chunk at a time: its
    float32 copy (1.3 GB for 163,840 rows of 2,048) is never whole. The fp8
    control scales a weight by output column, so chunks round as the whole."""
    x = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    w = params["embed"].T if c.get("tie_word_embeddings") else params["lm_head"]
    return jnp.concatenate(
        [matmul(x, w[:, i : i + VOCAB_CHUNK], quant) for i in range(0, w.shape[1], VOCAB_CHUNK)], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _layer_jit(x, layers, i, config_items, quant):  # noqa: ANN001
    # sliced inside the program: no copy of a whole layer's experts is made
    lw = {k: w[i] for k, w in layers.items()}
    return layer(x, lw, dict(config_items), quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _head_jit(x, params, config_items, quant):  # noqa: ANN001
    return head(x, params, dict(config_items), quant)


def _static(c: dict) -> tuple:
    keys = (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "tie_word_embeddings",
    )  # fmt: skip
    return tuple((k, c[k]) for k in keys if c.get(k) is not None)


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if k not in GROUPS}


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, d]`` float32: what :func:`head` is given, the residual stream
    behind the last layer, layer by layer so that only one layer's float32
    copies are alive beside the given weights."""
    x = params["embed"][tokens].astype(F32)
    for group in GROUPS:
        if group in params:
            for i in range(params[group]["wq"].shape[0]):
                x = _layer_jit(x, params[group], jnp.int32(i), _static(c), quant)
    return x


def logits(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, vocab]`` float32 logits of ``tokens[b, s]``: the head over the
    whole :func:`stream`. The benchmark's check never holds these: it gives
    :func:`head` the served positions a slice at a time (``lib/serve_cell.py``)."""
    return _head_jit(stream(params, tokens, c, quant), _top(params), _static(c), quant)


def mean_nll(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None):
    """Mean next-token negative log-likelihood of ``tokens[b, s+1]``. Layers
    run under ``lax.scan``, a group at a time, each one recomputed in the
    backward pass."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs].astype(F32)

    def body(x, lw):  # noqa: ANN001
        return layer(x, lw, c, quant), None

    for group in GROUPS:
        if group in params:
            x, _ = jax.lax.scan(jax.checkpoint(body), x, params[group])
    top = _top(params)

    def row_nll(args):  # noqa: ANN001 - one row's [s, vocab] logits at a time
        xr, tr = args
        lg = head(xr[None], top, c, quant)[0]
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tr[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(jax.checkpoint(row_nll), (x, targets))
    return jnp.mean(nll)
