"""Rotary position embeddings (RoPE), Llama-3 style.

Frequencies are precomputed once in float32 and closed over by the jitted
step (static across steps — no recompute in the hot loop); the rotation is
a pair of fused multiplies XLA folds into the attention projections.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from torchx_tpu.ops.attention import note_traced


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's rotary scaling as a model's ``rope_scaling`` publishes it: the
    frequencies a context of ``original_max_seq`` turns fewer than ``beta_slow``
    times are divided by ``factor``, those it turns more than ``beta_fast``
    times are kept, and a linear ramp over the pair index blends the two sets
    between. ``mscale`` over ``mscale_all_dim`` scales cos and sin;
    ``mscale_all_dim`` alone scales the softmax (:meth:`attention_mscale`)."""

    factor: float
    original_max_seq: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _mscale(self, m: float) -> float:
        return 0.1 * m * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0

    @property
    def attention_mscale(self) -> float:
        """``m`` of the softmax scale ``head_width ** -0.5 * m * m``."""
        return self._mscale(self.mscale_all_dim)

    @property
    def rotation_mscale(self) -> float:
        """What cos and sin are multiplied by."""
        return self._mscale(self.mscale) / self._mscale(self.mscale_all_dim)

    def ramp_bounds(self, head_dim: int, theta: float) -> tuple[int, int]:
        """The pair indices the ramp runs between: below ``low`` a frequency is
        kept, from ``high`` on it is divided by ``factor``."""

        def pair_turning(rotations: float) -> float:
            return head_dim * math.log(self.original_max_seq / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low, high = math.floor(pair_turning(self.beta_fast)), math.ceil(pair_turning(self.beta_slow))
        return max(low, 0), min(high, head_dim - 1)

    def inv_freq(self, head_dim: int, theta: float) -> jnp.ndarray:
        """``[head_dim / 2]`` float32: ``f / factor`` where the ramp is 1, ``f`` where it is 0."""
        f = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
        low, high = self.ramp_bounds(head_dim, theta)
        span = high - low if high != low else 0.001
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / span, 0.0, 1.0)
        return f / self.factor * ramp + f * (1.0 - ramp)


def rope_frequencies(
    head_dim: int, max_seq: int, theta: float = 500000.0, start=0, scaling: Optional[YarnScaling] = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (cos, sin), each [max_seq, head_dim//2], float32.

    ``start`` offsets the position index (static int or traced scalar):
    sequence-sharded layouts (ring attention under a manualized ``sp``
    axis) compute the frequencies for their own shard of positions with
    ``start = axis_index("sp") * local_seq``. ``scaling`` blends the
    frequencies as YaRN does (:class:`YarnScaling`);
    ``ops.attention.traced("rope")`` answers ``yarn`` or ``plain``.
    """
    note_traced("rope", "yarn" if scaling else "plain")
    if scaling is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    else:
        inv_freq = scaling.inv_freq(head_dim, theta)
    t = jnp.arange(max_seq, dtype=jnp.float32) + jnp.asarray(
        start, dtype=jnp.float32
    )
    freqs = jnp.outer(t, inv_freq)  # [seq, head_dim/2]
    m = scaling.rotation_mscale if scaling else 1.0
    return (jnp.cos(freqs), jnp.sin(freqs)) if m == 1.0 else (jnp.cos(freqs) * m, jnp.sin(freqs) * m)


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


def _whole(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """``x c + (x P) s`` over whole heads: ``P`` the signed permutation that takes a head's ``(x1, x2)`` to
    ``(-x2, x1)``, ``c`` and ``s`` the tables laid twice side by side. Every product of the matmul is an element of
    ``x`` times 0, 1 or -1, so it is exact in ``x``'s own dtype (float32 at the highest precision, which the chip
    needs to carry all 24 bits); the rotation itself is float32 and rounded once, as :func:`apply_rope`'s."""
    note_traced("rotation", "whole_heads")
    half = x.shape[-1] // 2
    eye, zero = jnp.eye(half, dtype=x.dtype), jnp.zeros((half, half), x.dtype)
    swapped = jnp.matmul(x, jnp.block([[zero, eye], [-eye, zero]]), precision="highest" if x.dtype == jnp.float32 else None)
    c = jnp.concatenate((cos, cos), axis=-1)[None, :, None, :]
    s = jnp.concatenate((sin, sin), axis=-1)[None, :, None, :]
    return (x.astype(jnp.float32) * c + swapped.astype(jnp.float32) * s).astype(x.dtype)


@jax.custom_vjp
def apply_rope_whole(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """:func:`apply_rope` to the bit, for a training step: ``[batch, seq, heads, head_dim]`` rotated without
    splitting a head into its halves.

    :func:`apply_rope` splits the last axis at ``head_dim / 2`` and joins it again. Inside a serving program's
    fusion over 16-128 rows that costs nothing; over a training step's ``[2, 4096, 32, 128]`` the chip's compiler
    materialises the float32 halves (64 of 128 lanes each), re-lays them and joins them, in the forward, in the
    recomputation and, transposed, in the backward: 4.6 ms a step of pure movement in ``mistral7b-train-4k`` and
    more inside the fusions that compute (PERF.md section 6, PR 51). Here the halves change places in a
    ``[head_dim, head_dim]`` matmul on the otherwise idle MXU (:func:`_whole`) and everything else works on whole
    128-lane rows. The gradient is the inverse rotation, the same function at ``-sin``, so it needs no halves
    either and rounds once as autodiff of :func:`apply_rope` does; ``cos`` and ``sin`` are tables made from
    positions and get no gradient. ``ops.attention.traced("rotation")`` answers ``whole_heads``."""
    return _whole(x, cos, sin)


def _apply_rope_whole_fwd(x, cos, sin):  # noqa: ANN001, ANN202
    return _whole(x, cos, sin), (cos, sin)


def _apply_rope_whole_bwd(tables, g):  # noqa: ANN001, ANN202
    cos, sin = tables
    return _whole(g, cos, -sin), jnp.zeros_like(cos), jnp.zeros_like(sin)


apply_rope_whole.defvjp(_apply_rope_whole_fwd, _apply_rope_whole_bwd)


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """:func:`apply_rope`'s rotation for ``x`` ``[..., heads, rope]`` at
    ``cos``/``sin`` ``[..., rope/2]``, one row of frequencies a token whatever
    the leading axes are (they broadcast: ``[s, .]`` against ``[b, s, ., .]``,
    ``[rows, .]`` against ``[rows, ., .]``)."""
    dtype = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate((x1 * c - x2 * s, x2 * c + x1 * s), axis=-1).astype(dtype)
