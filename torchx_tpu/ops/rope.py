"""Rotary position embeddings (RoPE), Llama-3 style.

Frequencies are precomputed once in float32 and closed over by the jitted
step (static across steps — no recompute in the hot loop); the rotation is
a pair of fused multiplies XLA folds into the attention projections.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(
    head_dim: int, max_seq: int, theta: float = 500000.0, start=0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (cos, sin), each [max_seq, head_dim//2], float32.

    ``start`` offsets the position index (static int or traced scalar):
    sequence-sharded layouts (ring attention under a manualized ``sp``
    axis) compute the frequencies for their own shard of positions with
    ``start = axis_index("sp") * local_seq``.
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_seq, dtype=jnp.float32) + jnp.asarray(
        start, dtype=jnp.float32
    )
    freqs = jnp.outer(t, inv_freq)  # [seq, head_dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(
    x: jnp.ndarray,  # [batch, seq, heads, head_dim]
    cos: jnp.ndarray,  # [seq, head_dim/2] (already sliced to positions)
    sin: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate [batch, seq, heads, head_dim] by the given frequencies."""
    dtype = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate((x1 * c - x2 * s, x2 * c + x1 * s), axis=-1).astype(dtype)


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """:func:`apply_rope`'s rotation for ``x`` ``[..., heads, rope]`` at
    ``cos``/``sin`` ``[..., rope/2]``, one row of frequencies a token whatever
    the leading axes are (they broadcast: ``[s, .]`` against ``[b, s, ., .]``,
    ``[rows, .]`` against ``[rows, ., .]``)."""
    dtype = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate((x1 * c - x2 * s, x2 * c + x1 * s), axis=-1).astype(dtype)
