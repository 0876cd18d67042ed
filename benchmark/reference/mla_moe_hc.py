"""Plain reference of kind ``mla_moe_hc``: Xing4.0-29B-A4B's decoder in float32
at ``precision=HIGHEST``, from the published keys and the equations of
``kinds/mla_moe_hc.py``'s docstring.

Straight ``jax.numpy``: no kernels, no cache, no absorption, no sorting, no
fused loop. It borrows the benchmark's plain pieces (``reference/model.py``:
``matmul`` with the fp8 control, RMSNorm, SwiGLU) and imports nothing of the
program. A token's residual stream is ``X [n, d]`` (``hc_mult`` n), the
embedding in every row; around each sublayer :func:`hyper_connection` computes
``H_pre``, ``H_post`` and the doubly stochastic ``H_res`` from the stream itself
and mixes as published; the rows are summed ahead of the final norm.

* Attention: ``u = RMSNorm(H_pre X)``; ``c_q = RMSNorm_q(u W_qa)``; ``q = c_q W_qb``
  as ``[h, nope + rope]``; ``u W_kva`` as ``[rank + rope]`` = ``(c, k_rope)``; ``c =
  RMSNorm_kv(c)``; ``q_rope`` and ``k_rope`` rotated in interleaved pairs ``(2i, 2i+1)``
  at YaRN's frequencies (:func:`yarn_inv_freq`); ``c W_kvb`` as ``[h, nope + v]``;
  causal softmax of ``q.k (nope + rope)^-0.5 mscale^2``; ``W_o``.
* Expert layers (after ``first_k_dense_replace`` dense SwiGLUs of
  ``intermediate_size``): ``s = sigmoid(u W_r)``; the ``num_experts_per_tok`` largest
  of ``s + bias`` chosen; weights ``s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scaling_factor``; every expert runs on every token with the others'
  weights zero; plus the shared SwiGLU.

Two departures, in how the weights are stored and not in the equations: the
``rope`` rotary columns of each head of ``W_qb``, and of ``W_kva``, are held evens
first, then odds (the program pairs dimension ``i`` with ``i + rope/2``);
:func:`_published_order` puts the activations back before rotating as published,
as ``reference/mla_moe.py`` does; and ``W_kvb`` is held as its key and its value
columns apart, each transposed (``w_uk [h, nope, rank]``, ``w_uv [h, v, rank]``),
which :func:`mla` transposes back. Not built, here or in the program: the
multi-token-prediction layer.

``quant="fp8"`` is the control (``reference/model.py::matmul``): every weight
matmul in float8, the router and the hyper-connections' 24-wide projection
excepted (they decide, they do not carry the signal), all else float32.
"""

from __future__ import annotations

import functools
import math
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


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(rope: int, theta: float, sc: dict) -> jnp.ndarray:
    """``[rope / 2]`` inverse frequencies: ``theta^(-2i/rope)`` below the ramp,
    divided by ``factor`` above it, blended linearly over the pair index between."""
    factor, l0 = float(sc["factor"]), float(sc["original_max_position_embeddings"])

    def pair_turning(rotations: float) -> float:  # the pair that turns `rotations` times over l0 positions
        return rope * math.log(l0 / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(pair_turning(float(sc["beta_slow"]))), rope - 1)
    f = theta ** (-jnp.arange(0, rope, 2, dtype=F32) / rope)
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=F32) - low) / (high - low if high != low else 0.001), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def rope_interleaved(x: jnp.ndarray, theta: float, sc: dict) -> jnp.ndarray:
    """YaRN's rotary embedding of ``[b, s, heads, rope]`` at positions 0..s-1,
    pair ``i`` being dimensions ``(2i, 2i+1)``, as the checkpoint is published."""
    factor = float(sc["factor"])
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * yarn_inv_freq(x.shape[-1], theta, sc)[None, :]
    scale = _mscale(factor, float(sc["mscale"])) / _mscale(factor, float(sc["mscale_all_dim"]))
    cos, sin = (jnp.cos(ang) * scale)[None, :, None, :], (jnp.sin(ang) * scale)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack((even * cos - odd * sin, odd * cos + even * sin), axis=-1).reshape(x.shape)


def softmax_scale(c: dict) -> float:
    sc = c["rope_scaling"]
    m = _mscale(float(sc["factor"]), float(sc["mscale_all_dim"])) if sc.get("mscale_all_dim") else 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def mla(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    """Latent attention of the normed ``u [b, s, d]`` with a compressed query,
    expanded, one head's ``[s, s]`` scores alive at a time."""
    b, s, _ = u.shape
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    theta, sc, eps = float(c["rope_theta"]), c["rope_scaling"], c["rms_norm_eps"]
    c_q = rms_norm(matmul(u, lw["w_qa"], quant), lw["q_latent_norm"], eps)
    q = matmul(c_q, lw["w_qb"], quant).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_interleaved(_published_order(q[..., dn:]), theta, sc)
    kva = matmul(u, lw["w_kva"], quant)
    latent = rms_norm(kva[..., :r], lw["kv_norm"], eps)
    k_rope = rope_interleaved(_published_order(kva[..., None, r:]), theta, sc)[:, :, 0]  # [b, s, rope]
    k_nope = matmul(latent, lw["w_uk"].reshape(h * dn, r).T, quant).reshape(b, s, h, dn)  # c W_kvb as [h, nope + v], its two parts
    v = matmul(latent, lw["w_uv"].reshape(h * dv, r).T, quant).reshape(b, s, h, dv)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(c)

    def one_head(args):  # noqa: ANN001
        qn, qr, kn, vh = args  # [b, s, .] of one head
        scores = (
            jnp.einsum("bqd,bkd->bqk", qn, kn, precision=HI) + jnp.einsum("bqd,bkd->bqk", qr, k_rope, precision=HI)
        ) * scale
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
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * float(c["routed_scaling_factor"])
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


def hc_coefficients(x: jnp.ndarray, phi, b, a, c: dict):  # noqa: ANN001, ANN201
    """The stream ``x [..., n, d]`` -> ``(H_pre [..., n], H_post [..., n], H_res
    [..., n, n])`` of one sublayer, the last doubly stochastic as
    ``hc_sinkhorn_iters`` row-then-column normalisations leave it."""
    n, eps = c["hc_mult"], float(c["hc_eps"])
    b, a = b.astype(F32), a.astype(F32)
    flat = x.reshape(*x.shape[:-2], -1)
    z = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    m = matmul(z, phi, None)
    h_pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n : 2 * n] + b[n : 2 * n])
    logits = jnp.clip(a[2] * m[..., 2 * n :] + b[2 * n :], float(c["mhc_h_res_clamp_min"]), float(c["mhc_h_res_clamp_max"]))
    h_res = jnp.exp(logits).reshape(*m.shape[:-1], n, n)
    for _ in range(int(c["hc_sinkhorn_iters"])):
        h_res = h_res / (jnp.sum(h_res, axis=-1, keepdims=True) + eps)
        h_res = h_res / (jnp.sum(h_res, axis=-2, keepdims=True) + eps)
    return h_pre, h_post, h_res


def hyper_connection(x: jnp.ndarray, lw: dict, sub: str, c: dict, f) -> jnp.ndarray:  # noqa: ANN001
    """``X' = H_res X + H_post^T F(H_pre X)`` around sublayer ``sub`` of the layer ``lw``."""
    h_pre, h_post, h_res = hc_coefficients(x, lw[f"hc_{sub}_phi"], lw[f"hc_{sub}_b"], lw[f"hc_{sub}_a"], c)
    y = f(jnp.einsum("...n,...nd->...d", h_pre, x, precision=HI))
    return jnp.einsum("...ij,...jd->...id", h_res, x, precision=HI) + h_post[..., :, None] * y[..., None, :]


def layer(x: jnp.ndarray, lw: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on the stream ``x[b, s, n, d]`` with that layer's
    weights: an expert layer where it has a router, else a dense one."""
    eps = c["rms_norm_eps"]
    x = hyper_connection(x, lw, "attn", c, lambda v: mla(rms_norm(v, lw["attn_norm"], eps), lw, c, quant))

    def ffn(v):  # noqa: ANN001, ANN202
        m = rms_norm(v, lw["mlp_norm"], eps)
        if "w_router" in lw:
            return experts(m, lw, c, quant)
        return swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)

    return hyper_connection(x, lw, "mlp", c, ffn)


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[.., vocab]`` logits of the stream ``x [.., n, d]``: its rows summed,
    the final norm, the head's columns a chunk at a time (the fp8 control scales
    a weight by output column, so chunks round as the whole)."""
    x = rms_norm(jnp.sum(x, axis=-2), params["final_norm"], c["rms_norm_eps"])
    w = params["embed"].T if c.get("tie_word_embeddings") else params["lm_head"]
    return jnp.concatenate(
        [matmul(x, w[:, i : i + VOCAB_CHUNK], quant) for i in range(0, w.shape[1], VOCAB_CHUNK)], axis=-1
    )


def embed(params: dict, tokens: jnp.ndarray, c: dict) -> jnp.ndarray:
    """``[b, s, n, d]``: a token's embedding in every row of its stream."""
    x = params["embed"][tokens].astype(F32)
    return jnp.broadcast_to(x[..., None, :], (*x.shape[:-1], c["hc_mult"], x.shape[-1]))


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _layer_jit(x, layers, i, config_items, quant):  # noqa: ANN001
    # sliced inside the program: no copy of a whole layer's experts is made
    lw = {k: w[i] for k, w in layers.items()}
    return layer(x, lw, _config(config_items), quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _head_jit(x, params, config_items, quant):  # noqa: ANN001
    return head(x, params, _config(config_items), quant)


def _static(c: dict) -> tuple:
    keys = (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok", "routed_scaling_factor", "tie_word_embeddings",
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
    )  # fmt: skip
    return (*((k, c[k]) for k in keys if c.get(k) is not None), ("rope_scaling", tuple(sorted(c["rope_scaling"].items()))))


def _config(items: tuple) -> dict:
    c = dict(items)
    return dict(c, rope_scaling=dict(c["rope_scaling"]))


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if k not in GROUPS}


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, n, d]`` float32: what :func:`head` is given, the residual
    streams behind the last layer, layer by layer so that only one layer's
    float32 copies are alive beside the given weights."""
    x = embed(params, tokens, c)
    for group in GROUPS:
        if group in params:
            for i in range(params[group]["w_qa"].shape[0]):
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
    x = embed(params, inputs, c)

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
