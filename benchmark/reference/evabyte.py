"""Plain reference of kind ``evabyte``: EvaByte's block in float32 at
``precision=HIGHEST``.

Straight ``jax.numpy``: no kernels, no cache, no blocks, no cache coordinate.
It borrows the benchmark's plain pieces (``matmul`` with the fp8 control, RMSNorm,
the half-split rotary embedding, SwiGLU) and imports nothing of the program.
``d = hidden_size``, ``W = window_size``, ``C = chunk_size``; a layer::

    h = RMSNorm(x) * (1 + g_attn)                       # norm_add_unit_offset: gains are stored about 0
    q, k, v = h W_q, h W_k, h W_v                       # heads of head_dim, no bias; q and k roped at the byte's position t
    x = x + Eva(q, k, v) W_o
    h' = RMSNorm(x) * (1 + g_mlp)
    x = x + (silu(h' W_gate) * (h' W_up)) W_down

``Eva``. Byte ``t`` lies in window ``w(t) = t // W`` and chunk ``t // C``; a
window is ``W / C`` whole chunks. Every whole chunk ``c`` is pooled once, from
its **roped** keys, with the head's two learned vectors ``phi`` and ``mu``::

    a_j  = softmax over the chunk's C positions j of (phi . k_j)
    k~_c = sum_j a_j k_j + mu              v~_c = sum_j a_j v_j

and byte ``t`` attends, in **one** softmax at scale ``head_dim^-0.5``, the exact
rows of its own window up to itself and the pooled rows of every chunk of every
earlier window::

    S_t = {j : W w(t) <= j <= t}           R_t = {c : c < (W / C) w(t)}
    o_t = (sum_{S_t} e^{q.k_j s} v_j + sum_{R_t} e^{q.k~_c s} v~_c) / (sum_{S_t} e^{q.k_j s} + sum_{R_t} e^{q.k~_c s})

A chunk of the current window is seen exactly and never through its pooled row.
The two sets are two masks, ``[s, s]`` and ``[s, s / C]``, over one row of scores
a query; queries go ``Q_BLOCK`` at a time and cache heads one at a time, so that
12 k positions fit.

Logits: ``RMSNorm(x) * (1 + g) W_head`` in float32 (``fp32_logits``), ``W_head``
``[d, num_pred_heads * vocab]``, head ``i`` (columns ``[i vocab, (i + 1) vocab)``)
predicting byte ``t + 1 + i``. :func:`head` is head 0, the next byte, which is
what is served; :func:`logits` is all of them. The residual adds are float32 here
like everything else (``fp32_skip_add``).

``quant="fp8"`` is the control (``reference/model.py::matmul``): every weight
matmul in float8, all else (rope, pooling, the softmax) float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import F32, HI, matmul, rms_norm, rope, swiglu

Q_BLOCK = 512  # queries whose [.., Q_BLOCK, s + s / C] scores are alive at once
_MASKED = -1e30  # not -inf: a padded query past the sequence admits nothing and must stay a number


def pool_chunks(k: jnp.ndarray, v: jnp.ndarray, phi: jnp.ndarray, mu: jnp.ndarray, chunk: int):  # noqa: ANN201
    """``k``, ``v [b, s, kvh, hd]`` (roped keys) -> the pooled rows of the
    sequence's whole chunks ``(k~, v~) [b, s // chunk, kvh, hd]``."""
    b, s, kvh, hd = k.shape
    n = s // chunk
    kc, vc = (x[:, : n * chunk].reshape(b, n, chunk, kvh, hd) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("bncgd,gd->bncg", kc, phi, precision=HI), axis=2)
    return jnp.einsum("bncg,bncgd->bngd", a, kc, precision=HI) + mu, jnp.einsum("bncg,bncgd->bngd", a, vc, precision=HI)


def eva(q, k, v, phi, mu, window: int, chunk: int):  # noqa: ANN001, ANN201
    """``q [b, s, h, hd]``, ``k`` and ``v [b, s, kvh, hd]`` roped, ``phi`` and
    ``mu [kvh, hd]`` -> ``[b, s, h hd]``."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    k_pooled, v_pooled = pool_chunks(k, v, phi, mu, chunk)
    n_chunks = k_pooled.shape[1]
    padded = -s % Q_BLOCK if s > Q_BLOCK else 0
    qg = jnp.pad(q, ((0, 0), (0, padded), (0, 0), (0, 0))).reshape(b, s + padded, kvh, h // kvh, hd)
    block = min(Q_BLOCK, s)
    j, c = jnp.arange(s), jnp.arange(n_chunks)

    def one_head(args):  # noqa: ANN001, ANN202
        qh, kh, vh, kph, vph = args  # [b, s + padded, rep, hd], [b, s, hd] x 2, [b, n_chunks, hd] x 2

        def queries(t0):  # noqa: ANN001, ANN202
            t = (t0 + jnp.arange(block))[:, None]
            own = (j[None, :] <= t) & (j[None, :] >= window * (t // window))  # S_t
            far = c[None, :] < (window // chunk) * (t // window)  # R_t
            qb = jax.lax.dynamic_slice_in_dim(qh, t0, block, axis=1)
            scores = jnp.concatenate(
                (
                    jnp.where(own, jnp.einsum("bqrd,bkd->brqk", qb, kh, precision=HI), _MASKED),
                    jnp.where(far, jnp.einsum("bqrd,bkd->brqk", qb, kph, precision=HI), _MASKED),
                ),
                axis=-1,
            )
            probs = jax.nn.softmax(scores * hd**-0.5, axis=-1)
            return jnp.einsum("brqk,bkd->bqrd", probs[..., :s], vh, precision=HI) + jnp.einsum(
                "brqk,bkd->bqrd", probs[..., s:], vph, precision=HI
            )

        out = jax.lax.map(jax.checkpoint(queries), jnp.arange(0, s + padded, block))  # [blocks, b, block, rep, hd]
        return jnp.moveaxis(out, 0, 1).reshape(b, s + padded, h // kvh, hd)[:, :s]

    heads_first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    out = jax.lax.map(one_head, tuple(heads_first(x) for x in (qg, k, v, k_pooled, v_pooled)))  # [kvh, b, s, rep, hd]
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h * hd)


def layer(x: jnp.ndarray, lw: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on ``x[b, s, d]`` with that layer's weights."""
    b, s, _ = x.shape
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // h
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    u = rms_norm(x, 1.0 + lw["attn_norm"].astype(F32), eps)
    q = rope(matmul(u, lw["wq"], quant).reshape(b, s, h, hd), theta)
    k = rope(matmul(u, lw["wk"], quant).reshape(b, s, kvh, hd), theta)
    v = matmul(u, lw["wv"], quant).reshape(b, s, kvh, hd)
    attended = eva(q, k, v, lw["eva_phi"].astype(F32), lw["eva_mu_k"].astype(F32), c["window_size"], c["chunk_size"])
    x = x + matmul(attended, lw["wo"], quant)
    m = rms_norm(x, 1.0 + lw["mlp_norm"].astype(F32), eps)
    return x + swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def _normed(x: jnp.ndarray, params: dict, c: dict) -> jnp.ndarray:
    return rms_norm(x, 1.0 + params["final_norm"].astype(F32), c["rms_norm_eps"])


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[.., vocab]`` logits of head 0, the next byte: what is served."""
    return matmul(_normed(x, params, c), params["lm_head"][:, : c["vocab_size"]], quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _layer_jit(x, layers, i, config_items, quant):  # noqa: ANN001
    lw = {k: w[i] for k, w in layers.items()}  # sliced inside the program: no copy of the stack is made
    return layer(x, lw, dict(config_items), quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _all_heads_jit(x, params, config_items, quant):  # noqa: ANN001
    return matmul(_normed(x, params, dict(config_items)), params["lm_head"], quant)


def _static(c: dict) -> tuple:
    """What the equations read, hashable."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads", "rms_norm_eps", "rope_theta", "window_size",
            "chunk_size", "vocab_size")  # fmt: skip
    return tuple((k, c[k]) for k in keys)


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if k != "layers"}


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, d]`` float32: what :func:`head` is given, the residual stream
    behind the last layer, layer by layer so that only one layer's float32
    copies are alive beside the given weights."""
    x = params["embed"][tokens].astype(F32)
    for i in range(params["layers"]["wq"].shape[0]):
        x = _layer_jit(x, params["layers"], jnp.int32(i), _static(c), quant)
    return x


def logits(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, num_pred_heads * vocab]`` float32 logits of ``tokens[b, s]``: every
    head over the whole :func:`stream`, head ``i`` in columns ``[i vocab, (i + 1)
    vocab)``. The benchmark's check never holds these: it gives :func:`head` the
    served positions a slice at a time (``lib/serve_cell.py``)."""
    return _all_heads_jit(stream(params, tokens, c, quant), _top(params), _static(c), quant)


def mean_nll(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None):
    """Mean next-byte negative log-likelihood of ``tokens[b, s+1]`` under head
    0, each layer recomputed in the backward pass. (The published model is
    trained on all its heads at once; the benchmark trains no cell of this kind,
    and the program's loss is head 0's too.)"""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs].astype(F32)
    static = dict(_static(c))
    for i in range(params["layers"]["wq"].shape[0]):
        lw = {k: w[i] for k, w in params["layers"].items()}
        x = jax.checkpoint(functools.partial(layer, c=static, quant=quant))(x, lw)
    lg = head(x, _top(params), static, quant)
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0])
