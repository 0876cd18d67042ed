"""The plain reference of kind ``tied``: its own block, logits and loss under
its own keys, in float32 at ``precision=HIGHEST``. It borrows the benchmark's
plain pieces (matmul with the fp8 control, RMSNorm, rotary embedding,
attention, SwiGLU) and imports nothing of the program.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import F32, attention, matmul, rms_norm, rope, swiglu

SCALE = 1.0  # tests/test_extend.py writes a copy with another value: then no run is correct


def layer(x: jnp.ndarray, lw: dict, c: dict, quant: Optional[str]) -> jnp.ndarray:
    b, s, d = x.shape
    h, kvh = c["n_head"], c["n_head_kv"]
    eps, theta = c["layer_norm_epsilon"], c["rotary_emb_base"]
    a = rms_norm(x, lw["attn_norm"], eps)
    q = rope(matmul(a, lw["wq"], quant).reshape(b, s, h, d // h), theta)
    k = rope(matmul(a, lw["wk"], quant).reshape(b, s, kvh, d // h), theta)
    v = matmul(a, lw["wv"], quant).reshape(b, s, kvh, d // h)
    x = x + matmul(attention(q, k, v), lw["wo"], quant)
    m = rms_norm(x, lw["mlp_norm"], eps)
    return x + swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def stream(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    x = params["embed"][tokens].astype(F32)
    for i in range(c["n_layer"]):
        x = layer(x, {k: w[i] for k, w in params["layers"].items()}, c, quant)
    return x


def head(x: jnp.ndarray, params: dict, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], c["layer_norm_epsilon"])
    return SCALE * matmul(x, params["embed"].T, quant)


def logits(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None) -> jnp.ndarray:
    return head(stream(params, tokens, c, quant), params, c, quant)


def mean_nll(params: dict, tokens: jnp.ndarray, c: dict, quant: Optional[str] = None):
    lg = logits(params, tokens[:, :-1], c, quant)
    picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)
