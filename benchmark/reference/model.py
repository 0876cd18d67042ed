"""Plain reference: the dense Mistral block and the Mixtral block in float32.

Straight ``jax.numpy`` at ``precision=HIGHEST``: no kernels, no cache, no
batching tricks, no expert capacity. It imports nothing of the program and is
given only the benchmark's own seeded weights. Departures from the published
models: none in the equations (RMSNorm, rotary embedding in the half-split
layout of the published checkpoints, grouped-query causal attention, SwiGLU;
for Mixtral a softmax over the router logits, the top ``k`` experts,
renormalised, and the weighted sum of those experts' SwiGLU).

``quant="fp8"`` is the control, never the reference: every weight matmul
rounds both operands to float8 (e4m3, scaled per output channel of the weight
and per row of the activation, as fp8 matmuls are), a precision below the
bfloat16 the configurations state. Everything else stays float32, so the
control shows what the lower precision alone does. (int8 with the same scales
keeps 7 bits against bfloat16's 8 and, with all else in float32, lands closer
to the reference than the all-bfloat16 program does: it separates nothing;
PERF.md has the readings.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8_round(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / FP8_MAX
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    # straight-through: the backward pass sees the identity, as fp8 training does
    return x + jax.lax.stop_gradient(rounded - x)


def matmul(x: jnp.ndarray, w: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
    """``x[..., in] @ w[in, out]`` in float32."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "fp8":
        x, w = _fp8_round(x, -1), _fp8_round(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x: jnp.ndarray, gain: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding of ``[b, s, heads, hd]`` at positions 0..s-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Causal grouped-query attention, one key/value head at a time so that
    the ``[s, s]`` scores of only one group are alive."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, hd)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one_group(args):  # noqa: ANN001
        qh, kh, vh = args  # [b, s, rep, hd], [b, s, hd], [b, s, hd]
        scores = jnp.einsum("bqrd,bkd->brqk", qh, kh, precision=HI) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("brqk,bkd->bqrd", probs, vh, precision=HI)

    # recomputed in a backward pass: the scores of all groups are never kept
    out = jax.lax.map(
        jax.checkpoint(one_group),
        (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)),
    )  # [kvh, b, s, rep, hd]
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h * hd)


def swiglu(x, w_gate, w_up, w_down, quant):  # noqa: ANN001
    gate = jax.nn.silu(matmul(x, w_gate, quant))
    return matmul(gate * matmul(x, w_up, quant), w_down, quant)


def mixtral_ffn(x, lw, top_k: int, quant):  # noqa: ANN001
    """For each token: softmax over the router logits, the ``top_k`` largest,
    renormalised to sum to one, times those experts' SwiGLU. Every expert runs
    on every token and the others' weights are zero: plain, and exact."""
    probs = jax.nn.softmax(matmul(x, lw["w_router"], None), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    n_experts = lw["w_router"].shape[-1]
    weight = jnp.sum(
        jax.nn.one_hot(top_idx, n_experts, dtype=F32) * top_vals[..., None], axis=-2
    )  # [b, s, E]
    out = jnp.zeros(x.shape, F32)
    for e in range(n_experts):
        y = swiglu(x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], quant)
        out = out + weight[..., e : e + 1] * y
    return out


def layer(x: jnp.ndarray, lw: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on ``x[b, s, d]`` with that layer's weights."""
    b, s, _ = x.shape
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = rms_norm(x, lw["attn_norm"], eps)
    q = rope(matmul(a, lw["wq"], quant).reshape(b, s, h, hd), theta)
    k = rope(matmul(a, lw["wk"], quant).reshape(b, s, kvh, hd), theta)
    v = matmul(a, lw["wv"], quant).reshape(b, s, kvh, hd)
    x = x + matmul(attention(q, k, v), lw["wo"], quant)
    m = rms_norm(x, lw["mlp_norm"], eps)
    if "w_router" in lw:
        return x + mixtral_ffn(m, lw, c["num_experts_per_tok"], quant)
    return x + swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    w = params["embed"].T if c.get("tie_word_embeddings") else params["lm_head"]
    return matmul(x, w, quant)


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
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok", "tie_word_embeddings",
    )
    return tuple((k, c[k]) for k in keys if c.get(k) is not None)


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if k != "layers"}


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, d]`` float32: what :func:`head` is given, the residual stream
    behind the last layer, layer by layer so that only one layer's float32
    copy is alive beside the given weights."""
    x = params["embed"][tokens].astype(F32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        x = _layer_jit(x, params["layers"], jnp.int32(i), _static(c), quant)
    return x


def logits(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, vocab]`` float32 logits of ``tokens[b, s]``: the head over the
    whole :func:`stream`. The benchmark's check never holds these: it gives
    :func:`head` the served positions a slice at a time (``lib/serve_cell.py``)."""
    return _head_jit(stream(params, tokens, c, quant), _top(params), _static(c), quant)


def mean_nll(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None):
    """Mean next-token negative log-likelihood of ``tokens[b, s+1]``. Layers
    run under ``lax.scan`` with each one recomputed in the backward pass."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs].astype(F32)

    def body(x, lw):  # noqa: ANN001
        return layer(x, lw, c, quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    top = _top(params)

    def row_nll(args):  # noqa: ANN001 - one row's [s, vocab] logits at a time
        xr, tr = args
        lg = head(xr[None], top, c, quant)[0]
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tr[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(jax.checkpoint(row_nll), (x, targets))
    return jnp.mean(nll)
