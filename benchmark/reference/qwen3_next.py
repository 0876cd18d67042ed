"""Plain reference of kind ``qwen3_next``: Qwen3-Next-80B-A3B's block in float32 at
``precision=HIGHEST``, given one chip's share of it as the program is.

Straight ``jax.numpy``: no kernels, no cache, no chunks, no sorting; the linear layers'
recurrence is a ``lax.scan`` over positions from an empty state, exactly as written
below. It borrows the benchmark's plain pieces (``matmul`` with the fp8 control, RMSNorm,
the half-split rotary embedding, causal grouped-query attention, SwiGLU) and imports
nothing of the program. ``norm(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)``: every gain
but the linear mixer's is stored about zero. Layer ``i`` (counted over the whole stack)::

    h = x + mixer_i(norm(x, attn_norm));   out = h + moe(norm(h, mlp_norm))

and ``mixer_i`` attends where ``(i + 1) % full_attention_interval == 0``, else it is a Gated
DeltaNet layer. No bias anywhere.

* **Attention** (``h = num_attention_heads`` query / ``kvh = num_key_value_heads`` K/V heads of
  ``hd = head_dim``): ``u W_q`` as ``[h, 2 hd]``, a head's first ``hd`` its query and its last ``hd``
  its gate; ``k = u W_k``, ``v = u W_v`` as ``[kvh, hd]``; ``q`` and ``k`` normed a head (``norm`` over
  ``hd``, gains ``q_norm``, ``k_norm``: zero-centred too); the first ``partial_rotary_factor x hd``
  values of a head rotated (``rope_theta``, pairs ``(i, i + 32)`` of those 64), the rest left;
  causal softmax of ``q.k / sqrt(hd)``; ``o * sigmoid(gate)``; ``W_o``.
* **Gated DeltaNet** (``Hk = linear_num_key_heads``, ``H = linear_num_value_heads`` heads of ``D =
  linear_key_head_dim = linear_value_head_dim``, ``K = linear_conv_kernel_dim``)::

      [q | k | v | z] = u W_in;   [b | a] = u W_ba           # Hk D | Hk D | H D | H D;  H | H
      [q | k | v]_t = silu(sum_j w_j [q | k | v]_{t-K+1+j})   # depthwise, causal: inputs ahead of position 0 are zero
      q, k repeated to H heads (head h reads key head h // (H / Hk))
      q = l2norm(q) / sqrt(D);   k = l2norm(k)                # x / sqrt(sum x^2 + 1e-6)
      beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)
      S <- exp(g_t) S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T;   o_t = S^T q_t    # S [D, D] a head, S_{-1} = 0
      out = (w_n * o / sqrt(mean(o^2) + eps) * silu(z)) W_out  # a plain gain of D, the norm a head, THEN the gate

* **Experts**: ``p = softmax(u W_r)`` in float32 over all ``published_num_experts``; the
  ``num_experts_per_tok`` largest renormalised to sum to one (``norm_topk_prob``); each a SwiGLU
  of ``moe_intermediate_size``; plus ``sigmoid(u . w_s) * SwiGLU_shared(u)``. Every expert given
  runs on every token and the others' weights are zero: plain, and exact.

**The share** (the same departure as the program's, ``kinds/qwen3_next.py``): the weights
given hold ``num_experts`` routed experts, ids ``experts_held_from`` onward of the
``published_num_experts`` the router scores. The sum runs over those; what the experts on
the other chips would add is left out, the shared expert is added once, and that partial sum
goes on to the next layer. With all experts given it is the published layer (:func:`routed`
of the four shares sum to it). The embedding and the head are one slice of the vocabulary.
Left out here as in the program: the multi-token-prediction block.

``quant="fp8"`` is the control (``reference/model.py::matmul``): every weight matmul in
float8, the router and the shared expert's gate excepted, all else (the convolution, the
recurrence, the norms) float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import F32, HI, attention, matmul, rms_norm, rope, swiglu

VOCAB_CHUNK = 9496  # the head is multiplied this many columns at a time (37,984 = 4 x 9,496)


def norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with its gain stored about zero."""
    return rms_norm(x, 1.0 + w.astype(F32), eps)


def attends(c: dict, i: int) -> bool:
    return (i + 1) % c["full_attention_interval"] == 0


def gated_attention(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    b, s, _ = u.shape
    h, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    rotary = int(hd * c["partial_rotary_factor"])
    theta, eps = float(c["rope_theta"]), c["rms_norm_eps"]
    q_and_gate = matmul(u, lw["wq"], quant).reshape(b, s, h, 2 * hd)
    q, gate = norm(q_and_gate[..., :hd], lw["q_norm"], eps), q_and_gate[..., hd:]
    k = norm(matmul(u, lw["wk"], quant).reshape(b, s, kvh, hd), lw["k_norm"], eps)
    v = matmul(u, lw["wv"], quant).reshape(b, s, kvh, hd)
    turned = lambda x: jnp.concatenate((rope(x[..., :rotary], theta), x[..., rotary:]), axis=-1)  # noqa: E731
    out = attention(turned(q), turned(k), v)  # [b, s, h hd]
    return matmul(out * jax.nn.sigmoid(gate.reshape(b, s, h * hd)), lw["wo"], quant)


def delta_rule(q, k, v, beta, g, state=None):  # noqa: ANN001, ANN201
    """``q``, ``k`` and ``v [b, s, H, D]``, ``beta`` and ``g [b, s, H]`` -> ``(o [b, s, H, D], S [b, H, D, D])``
    behind the last position, one position at a time."""
    if state is None:
        state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), F32)

    def position(s, at):  # noqa: ANN001, ANN202
        q_t, k_t, v_t, beta_t, g_t = at
        s = jnp.exp(g_t)[..., None, None] * s
        u_t = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HI))
        s = s + k_t[..., None] * u_t[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    state, o = jax.lax.scan(position, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, beta, g)))
    return jnp.moveaxis(o, 0, 1), state


def delta_net(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    b, s, _ = u.shape
    hk, h, d, taps = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_conv_kernel_dim"]
    width = (2 * hk + h) * d
    qkvz, ba = matmul(u, lw["gdn_in"], quant), matmul(u, lw["gdn_ba"], quant)
    qkv, z = qkvz[..., :width], qkvz[..., width:]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    w = lw["gdn_conv_w"].astype(F32)  # [K, width]: tap j multiplies the input K - 1 - j positions back
    qkv = jax.nn.silu(sum(padded[:, j : j + s] * w[j] for j in range(taps)))
    l2norm = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    to_value_heads = lambda x: jnp.repeat(x.reshape(b, s, hk, d), h // hk, axis=2)  # noqa: E731
    q = l2norm(to_value_heads(qkv[..., : hk * d])) * d**-0.5
    k = l2norm(to_value_heads(qkv[..., hk * d : 2 * hk * d]))
    v = qkv[..., 2 * hk * d :].reshape(b, s, h, d)
    beta = jax.nn.sigmoid(ba[..., :h])
    g = -jnp.exp(lw["gdn_A_log"].astype(F32)) * jax.nn.softplus(ba[..., h:] + lw["gdn_dt_bias"].astype(F32))
    o, _ = delta_rule(q, k, v, beta, g)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c["rms_norm_eps"]) * lw["gdn_norm"].astype(F32)
    return matmul(o.reshape(b, s, h * d) * jax.nn.silu(z), lw["gdn_out"], quant)


def routed(u: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    """The weighted sum of the routed experts given, routed over all published."""
    probs = jax.nn.softmax(matmul(u, lw["w_router"], None), axis=-1)  # [b, s, published] float32
    picked, chosen = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c.get("norm_topk_prob", True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32) * picked[..., None], axis=-2)  # [b, s, published]
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


def shared(u: jnp.ndarray, lw: dict, quant: Optional[str]) -> jnp.ndarray:
    """The shared expert, weighed a token by ``sigmoid(u . w_s)``."""
    gate = jax.nn.sigmoid(matmul(u, lw["w_shared_gate"][:, None], None))  # [b, s, 1]
    return gate * swiglu(u, lw["ws_gate"], lw["ws_up"], lw["ws_down"], quant)


def layer(x: jnp.ndarray, lw: dict, c: dict, attending: bool, quant: Optional[str] = None) -> jnp.ndarray:
    """One decoder layer on ``x[b, s, d]`` with that layer's weights (what every layer has and its own mixer's)."""
    eps = c["rms_norm_eps"]
    u = norm(x, lw["attn_norm"], eps)
    x = x + (gated_attention if attending else delta_net)(u, lw, c, quant)
    m = norm(x, lw["mlp_norm"], eps)
    return x + routed(m, lw, c, quant) + shared(m, lw, quant)


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[.., vocab]`` logits over the vocabulary's slice, a chunk of columns at a time."""
    x = norm(x, params["final_norm"], c["rms_norm_eps"])
    w = params["lm_head"]
    return jnp.concatenate(
        [matmul(x, w[:, i : i + VOCAB_CHUNK], quant) for i in range(0, w.shape[1], VOCAB_CHUNK)], axis=-1
    )


def _weights_of(params: dict, i, j, attending: bool) -> dict:  # noqa: ANN001
    """Layer ``i``'s slice of what every layer has and slice ``j`` of its kind's mixers: sliced inside
    the program, so that no copy of a whole layer's experts is made."""
    own = params["mixers"]["full" if attending else "state"]
    return {**{k: w[i] for k, w in params["layers"].items()}, **{k: w[j] for k, w in own.items()}}


@functools.partial(jax.jit, static_argnames=("config_items", "attending", "quant"))
def _layer_jit(x, params, i, j, config_items, attending, quant):  # noqa: ANN001
    return layer(x, _weights_of(params, i, j, attending), dict(config_items), attending, quant)


@functools.partial(jax.jit, static_argnames=("config_items", "quant"))
def _head_jit(x, params, config_items, quant):  # noqa: ANN001
    return head(x, params, dict(config_items), quant)


def _static(c: dict) -> tuple:
    """What the equations read, hashable."""
    keys = (
        "num_attention_heads", "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta", "rms_norm_eps",
        "full_attention_interval", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
        "linear_conv_kernel_dim", "num_experts_per_tok", "norm_topk_prob", "experts_held_from",
    )  # fmt: skip
    return tuple((k, c[k]) for k in keys if c.get(k) is not None)


def _top(params: dict) -> dict:
    return {k: w for k, w in params.items() if not isinstance(w, dict)}


def _places(params: dict, c: dict):  # noqa: ANN202
    """``(layer, its place in its kind's stack, whether it attends)`` for every layer, in the order they run."""
    seen = {True: 0, False: 0}
    for i in range(params["layers"]["attn_norm"].shape[0]):
        kind = attends(c, i)
        yield i, seen[kind], kind
        seen[kind] += 1


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, d]`` float32: what :func:`head` is given, the residual stream behind the last
    layer, layer by layer so that only one layer's float32 copies are alive beside the given weights."""
    x = params["embed"][tokens].astype(F32)
    stacks = {k: params[k] for k in ("layers", "mixers")}
    for i, j, attending in _places(params, c):
        x = _layer_jit(x, stacks, jnp.int32(i), jnp.int32(j), _static(c), attending, quant)
    return x


def logits(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    """``[b, s, vocab]`` float32 logits of ``tokens[b, s]``: the head over the whole :func:`stream`.
    The benchmark's check never holds these: it gives :func:`head` the served positions a slice at
    a time (``lib/serve_cell.py``)."""
    return _head_jit(stream(params, tokens, c, quant), _top(params), _static(c), quant)


def mean_nll(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None):
    """Mean next-token negative log-likelihood of ``tokens[b, s+1]``, each layer recomputed in the backward pass."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs].astype(F32)
    static = dict(_static(c))
    for i, j, attending in _places(params, c):
        run = functools.partial(layer, c=static, attending=attending, quant=quant)
        x = jax.checkpoint(run)(x, _weights_of(params, i, j, attending))
    top = _top(params)

    def row_nll(args):  # noqa: ANN001 - one row's [s, vocab] logits at a time
        xr, tr = args
        lg = head(xr[None], top, static, quant)[0]
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tr[:, None], axis=-1)[:, 0]

    return jnp.mean(jax.lax.map(jax.checkpoint(row_nll), (x, targets)))
