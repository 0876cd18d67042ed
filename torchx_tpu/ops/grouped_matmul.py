"""Grouped matmul over the experts held: rows sorted by expert, one matrix a group.

``grouped_matmul(lhs [m, k], rhs [g, k, n], group_sizes [g])`` multiplies the
first ``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next
``group_sizes[1]`` by ``rhs[1]``, and so on (``sum(group_sizes) == m``): what a
dropless expert layer needs once its (token, choice) rows are in expert
order. No row is padded to a capacity and none is dropped; an expert nobody
chose costs nothing but the look at its count.

``rhs`` may also be a whole stack of layers, ``[L, g, k, n]``, with ``layer``
saying which one to multiply by: a layer's experts sliced out of the stack
under a ``lax.scan`` are copied in front of a kernel (1.1 GB a layer a step
at 64 experts of 2048 x 1408: 21 ms of a decode step, my chip run, PR 27),
where the kernel can as well be given every layer's experts and sizes that
are zero outside the one layer: an empty group costs a look at its count.

On a TPU, where :func:`kernel_eligible` allows, it is the Pallas grouped
matmul that ships with jax (``jax.experimental.pallas.ops.tpu.megablox``): a
grid over row tiles that each visit the one or two experts their rows belong
to, so an expert's weights are read once for each row tile it reaches into
and a tile's rows are multiplied on the MXU together. The tile sizes are
chosen from the shapes (:func:`_tiling`). Elsewhere (the CPU, shapes that do
not tile) it is :func:`jax.lax.ragged_dot`, which is also what the kernel is
tested against. ``ops.attention.traced("grouped_matmul")`` says which a
program lowered to (``megablox`` / ``ragged_dot``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchx_tpu.ops.attention import note_traced

#: bytes of one ``rhs`` tile the kernel keeps in each of its two buffers
_RHS_TILE_BYTES = 3 * 1024 * 1024


def _tiles(x: int, limit: int) -> list[int]:
    """The multiples of 128 that divide ``x`` and are at most ``limit``."""
    return [t for t in range(128, min(x, limit) + 1, 128) if x % t == 0]


def _tiling(m: int, k: int, n: int, itemsize: int, groups: int = 1) -> tuple[int, int, int]:
    """(tm, tk, tn), a 0 where a side does not tile. The row tile is the
    smallest of 128, 256 and 512 rows that holds a group of mean size
    (``m / groups``): a tile multiplies all its rows by every expert that
    reaches into it, so at decode's six rows a group a tile of 384 rows did
    64 times the arithmetic and ran compute-bound at 56% of the weights' wire
    (my chip run, PR 27), while prefill's hundreds of rows a group want tiles
    that re-read an expert's weights seldom. The ``rhs`` tile is the largest
    ``[tk, tn]`` under :data:`_RHS_TILE_BYTES`, the wider of two of one size."""
    fits = [t for t in _tiles(m, 512) if t >= m / groups]
    tm = min(fits, default=max(_tiles(m, 512), default=0))
    pairs = [
        (tk * tn, tn, tk)
        for tk in _tiles(k, k)
        for tn in _tiles(n, n)
        if tk * tn * itemsize <= _RHS_TILE_BYTES
    ]
    _, tn, tk = max(pairs, default=(0, 0, 0))
    return tm, tk, tn


def kernel_eligible(
    lhs_shape: tuple[int, ...],  # [m, k]
    rhs_shape: tuple[int, ...],  # [g, k, n]
    lhs_dtype: jnp.dtype,
    rhs_dtype: jnp.dtype,
    backend: str,
) -> bool:
    """Whether :func:`grouped_matmul` lowers to the Pallas kernel: a pure
    function of shapes, dtypes and backend. The kernel needs a TPU, bf16 or
    float32 on both sides, and ``m``, ``k`` and ``n`` that split into tiles
    of whole lanes."""
    m, k = lhs_shape
    _, _, n = rhs_shape
    return (
        backend == "tpu"
        and lhs_dtype == rhs_dtype
        and lhs_dtype in (jnp.bfloat16, jnp.float32)
        and all(_tiling(m, k, n, jnp.dtype(rhs_dtype).itemsize))
    )


def grouped_matmul(
    lhs: jnp.ndarray,  # [m, k] rows in group order
    rhs: jnp.ndarray,  # [g, k, n], or a stack of layers [L, g, k, n] with ``layer``
    group_sizes: jnp.ndarray,  # [g] int32, summing to m
    layer: jnp.ndarray | None = None,  # scalar int32: which of ``rhs``'s L layers
    interpret: bool = False,
    spread_over: int = 0,
) -> jnp.ndarray:
    """-> ``[m, n]`` in ``lhs``'s dtype, accumulated in float32.
    ``group_sizes`` may sum to less than ``m`` (a chip's share of the experts:
    the rows of experts held elsewhere lie behind the last group): those rows
    of the result are not computed and hold whatever the buffer held.
    ``spread_over`` is the number of groups the ``m`` rows were drawn over where
    that is more than ``rhs`` holds (the published experts): a group's mean
    size, which the row tile is chosen by, is ``m / spread_over``.
    ``interpret`` runs the Pallas kernel in its interpreter (the CPU tests)."""
    group_sizes = group_sizes.astype(jnp.int32)
    g = rhs.shape[-3]
    if interpret or kernel_eligible(lhs.shape, rhs.shape[-3:], lhs.dtype, rhs.dtype, jax.default_backend()):
        # imported here: Pallas costs a second that no CPU process should pay
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        note_traced("grouped_matmul", "megablox")
        if rhs.ndim == 4:  # every layer's groups, all empty but this layer's
            every = jnp.zeros((rhs.shape[0] * g,), jnp.int32)
            group_sizes = jax.lax.dynamic_update_slice(every, group_sizes, (layer * g,))
            rhs = rhs.reshape(rhs.shape[0] * g, *rhs.shape[2:])
        tiling = _tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2], rhs.dtype.itemsize, spread_over or g)
        return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype, tiling=tiling, interpret=interpret)
    note_traced("grouped_matmul", "ragged_dot")
    return jax.lax.ragged_dot(lhs, rhs[layer] if rhs.ndim == 4 else rhs, group_sizes)
