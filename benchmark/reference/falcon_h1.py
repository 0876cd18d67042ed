"""Plain reference of kind ``falcon_h1``: Falcon-H1's block in float32 at
``precision=HIGHEST``.

Straight ``jax.numpy``: no kernels, no cache, no chunks; the recurrence is a
``lax.scan`` over positions from an empty state. It borrows the benchmark's
plain pieces (``matmul`` with the fp8 control, RMSNorm, the half-split rotary
embedding, causal grouped-query attention) and imports nothing of the program.
``d`` = ``hidden_size``; the embedding's rows times ``embedding_multiplier``;
then a layer::

    u  = RMSNorm(x)                                              # attn_norm (input_layernorm): one norm for both branches
    x  = x + ssm_out_multiplier * Mixer(ssm_in_multiplier * u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    v  = RMSNorm(x)                                              # mlp_norm (pre_ff_layernorm)
    x  = x + mlp_multipliers[1] * (silu(mlp_multipliers[0] * (v W_gate)) * (v W_up)) W_down

``Attn``: ``q = u W_q`` as ``[h, hd]``, ``k = key_multiplier * (u W_k)``, ``v = u
W_v`` as ``[kvh, hd]``; ``q`` and ``k`` rotated over the whole head (``rope_theta``,
pairs ``(i, i + hd / 2)``); causal softmax of ``q.k / sqrt(hd)``, each K/V head
serving ``h / kvh`` query heads; ``W_o``. No bias anywhere.

``Mixer`` (``H = mamba_n_heads`` heads of ``P = mamba_d_head``, ``N = mamba_d_state``,
``G = mamba_n_groups``, ``d_ssm = mamba_d_ssm = H P``, ``K = mamba_d_conv``)::

    [z | xBC | dt] = (u W_in) * m      # widths d_ssm | d_ssm + 2 G N | H; m = ssm_multipliers[0..4] on the z, x, B, C, dt segments
    xBC_t = silu(sum_k w_k xBC_{t-K+1+k} + b)        # depthwise, causal: inputs ahead of position 0 are zero
    [x | B | C] = xBC                                # d_ssm | G N | G N
    dt = softplus(dt + dt_bias);  A = -exp(A_log)    # a head each
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t # S [H, P, N], S_{-1} = 0; head h reads group h // (H / G)
    y_t = S_t C_t + D x_t
    out = (RMSNorm_groups(y * silu(z)) * gain) W_out # mamba_rms_norm, norm_before_gate false: the gate, then a norm
                                                     # over each of the G groups of d_ssm / G values, one learned gain

Logits: ``lm_head_multiplier * (RMSNorm(x) W_head)``, a chunk of the
vocabulary's columns at a time (a float32 copy of the whole 261,120-wide head
would be 5.3 GB beside the weights).

``quant="fp8"`` is the control (``reference/model.py::matmul``): every weight
matmul in float8, all else (the convolution, the recurrence, the norms) float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import F32, attention, matmul, rms_norm, rope

VOCAB_CHUNK = 16320  # the head is multiplied this many columns at a time (261,120 = 16 x 16,320)


def attend(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    b, s, _ = u.shape
    h, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    theta = float(c["rope_theta"])
    q = rope(matmul(u, lw["wq"], quant).reshape(b, s, h, hd), theta)
    k = rope(c["key_multiplier"] * matmul(u, lw["wk"], quant).reshape(b, s, kvh, hd), theta)
    v = matmul(u, lw["wv"], quant).reshape(b, s, kvh, hd)
    return matmul(attention(q, k, v), lw["wo"], quant)


def recurrence(x, b_in, c_out, dt, a, d_skip, state=None):  # noqa: ANN001, ANN201
    """``x [b, s, H, P]``, ``b_in`` and ``c_out [b, s, H, N]`` (a head's group's),
    ``dt [b, s, H]``, ``a`` and ``d_skip [H]`` -> ``(y [b, s, H, P], S [b, H, P,
    N])`` behind the last position, one position at a time."""
    if state is None:
        state = jnp.zeros((x.shape[0], *x.shape[2:], b_in.shape[-1]), F32)

    def position(s, at):  # noqa: ANN001, ANN202
        x_t, b_t, c_t, dt_t = at
        s = jnp.exp(dt_t * a)[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1) + d_skip[:, None] * x_t

    state, y = jax.lax.scan(position, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, b_in, c_out, dt)))
    return jnp.moveaxis(y, 0, 1), state


def mixer(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    b, s, _ = u.shape
    heads, p, n, g, k = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"]
    d_ssm = c["mamba_d_ssm"]
    m = c["ssm_multipliers"]
    by_segment = jnp.concatenate(
        [jnp.full((w,), v, F32) for w, v in zip((d_ssm, d_ssm, g * n, g * n, heads), m)]
    )
    zxbcdt = matmul(u, lw["ssm_in"], quant) * by_segment
    z, xbc, dt = zxbcdt[..., :d_ssm], zxbcdt[..., d_ssm : 2 * d_ssm + 2 * g * n], zxbcdt[..., 2 * d_ssm + 2 * g * n :]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = lw["ssm_conv_w"].astype(F32)  # [K, width]: tap j multiplies the input K - 1 - j positions back
    xbc = jax.nn.silu(sum(padded[:, j : j + s] * w[j] for j in range(k)) + lw["ssm_conv_b"].astype(F32))
    x = xbc[..., :d_ssm].reshape(b, s, heads, p)
    to_heads = lambda v: jnp.repeat(v.reshape(b, s, g, n), heads // g, axis=2)  # noqa: E731 - head h reads group h // (H / G)
    b_in, c_out = to_heads(xbc[..., d_ssm : d_ssm + g * n]), to_heads(xbc[..., d_ssm + g * n :])
    dt = jax.nn.softplus(dt + lw["ssm_dt_bias"].astype(F32))
    y, _ = recurrence(x, b_in, c_out, dt, -jnp.exp(lw["ssm_A_log"].astype(F32)), lw["ssm_D"].astype(F32))
    y = y.reshape(b, s, d_ssm) * jax.nn.silu(z)
    grouped = y.reshape(b, s, g, d_ssm // g)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + c["rms_norm_eps"])
    return matmul(grouped.reshape(b, s, d_ssm) * lw["ssm_norm"].astype(F32), lw["ssm_out"], quant)


def layer(x: jnp.ndarray, lw: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on ``x[b, s, d]`` with that layer's weights."""
    eps = c["rms_norm_eps"]
    u = rms_norm(x, lw["attn_norm"], eps)
    x = (
        x
        + c["ssm_out_multiplier"] * mixer(c["ssm_in_multiplier"] * u, lw, c, quant)
        + c["attention_out_multiplier"] * attend(c["attention_in_multiplier"] * u, lw, c, quant)
    )
    v = rms_norm(x, lw["mlp_norm"], eps)
    gate_by, down_by = c["mlp_multipliers"]
    gate = jax.nn.silu(gate_by * matmul(v, lw["w_gate"], quant))
    return x + down_by * matmul(gate * matmul(v, lw["w_up"], quant), lw["w_down"], quant)


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[.., vocab]`` logits, a chunk of the vocabulary's columns at a time."""
    x = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    w = params["lm_head"]
    return c["lm_head_multiplier"] * jnp.concatenate(
        [matmul(x, w[:, i : i + VOCAB_CHUNK], quant) for i in range(0, w.shape[1], VOCAB_CHUNK)], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _layer_jit(x, layers, i, config_items, quant):  # noqa: ANN001
    lw = {k: w[i] for k, w in layers.items()}  # sliced inside the program: no copy of the stack is made
    return layer(x, lw, dict(config_items), quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _head_jit(x, params, config_items, quant):  # noqa: ANN001
    return head(x, params, dict(config_items), quant)


def _static(c: dict) -> tuple:
    """What the equations read, hashable."""
    keys = (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_d_ssm",
        "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
    )  # fmt: skip
    return tuple((k, tuple(c[k]) if isinstance(c[k], list) else c[k]) for k in keys)


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if k != "layers"}


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, d]`` float32: what :func:`head` is given, the residual stream
    behind the last layer, layer by layer so that only one layer's float32
    copies are alive beside the given weights."""
    x = params["embed"][tokens].astype(F32) * c["embedding_multiplier"]
    for i in range(params["layers"]["wq"].shape[0]):
        x = _layer_jit(x, params["layers"], jnp.int32(i), _static(c), quant)
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
    x = params["embed"][inputs].astype(F32) * c["embedding_multiplier"]
    static = dict(_static(c))
    for i in range(params["layers"]["wq"].shape[0]):
        lw = {k: w[i] for k, w in params["layers"].items()}
        x = jax.checkpoint(functools.partial(layer, c=static, quant=quant))(x, lw)
    top = _top(params)

    def row_nll(args):  # noqa: ANN001 - one row's [s, vocab] logits at a time
        xr, tr = args
        lg = head(xr[None], top, static, quant)[0]
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tr[:, None], axis=-1)[:, 0]

    return jnp.mean(jax.lax.map(jax.checkpoint(row_nll), (x, targets)))
