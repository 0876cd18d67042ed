"""Grouped matmul over the experts held: rows sorted by expert, one matrix a group.

``grouped_matmul(lhs [m, k], rhs [g, k, n], group_sizes [g])`` multiplies the
first ``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next
``group_sizes[1]`` by ``rhs[1]``, and so on (``sum(group_sizes) <= m``): what a
dropless expert layer needs once its (token, choice) rows are in expert
order. No row is padded to a capacity and none is dropped; an expert nobody
chose costs nothing but the look at its count.

``rhs`` may also be a whole stack of layers, ``[L, g, k, n]``, with ``layer``
saying which one to multiply by: a layer's experts sliced out of the stack
under a ``lax.scan`` are copied in front of a kernel (1.1 GB a layer a step
at 64 experts of 2048 x 1408: 21 ms of a decode step, my chip run, PR 27),
where the kernel can as well be given the stack and read its layer where it
lies.

On a TPU, where :func:`kernel_eligible` allows, it is this repo's own Pallas
kernel (:mod:`torchx_tpu.ops.grouped_matmul_kernel`, the Pallas call
``grouped_matmul_walk``; PR 46): a walk over the groups that have rows in
which an expert's weights cross the wire once a call, chunk by chunk into a
ring that the next group's copies refill as the chunks are done with, while
the row tiles of ``lhs`` and of the result stay in fast memory for as long as
groups reach into them. Until PR 46 it was the grouped matmul that ships
with jax (``megablox``), a grid over row tiles that reads an expert's weights
once for every row tile the expert's rows reach into: +18% of the experts'
bytes at the 1,920 sorted rows of a step that carries a chunk of a prompt
(PERF.md section 6, PR 46). The kernel is traced once a shape in a process,
not once a call site. The tile sizes are chosen from the shapes
(:func:`_tiling`). Elsewhere (the CPU, shapes that do not tile) it is
:func:`jax.lax.ragged_dot`, which is also what the kernel is tested against
and what differentiates it. ``ops.attention.traced("grouped_matmul")`` says
which a program lowered to (``walk`` / ``ragged_dot``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchx_tpu.ops.attention import note_traced

#: bytes of one chunk of a group's weights: what one copy brings and one product multiplies by
_CHUNK_BYTES = 1024 * 1024
#: bytes of the ring a group's ``[k, tn]`` weights land in, the kernel's largest buffer in fast memory
_RING_BYTES = 32 * 1024 * 1024


def _tiles(x: int, limit: int) -> list[int]:
    """The multiples of 128 that divide ``x`` and are at most ``limit``."""
    return [t for t in range(128, min(x, limit) + 1, 128) if x % t == 0]


def _tiling(m: int, k: int, n: int, itemsize: int, groups: int = 1) -> tuple[int, int, int]:
    """(tm, tk, tn), a 0 where a side does not tile. The row tile is the
    smallest of 128, 256 and 512 rows that holds a group of mean size
    (``m / groups``): a tile multiplies all its rows by every expert that
    reaches into it, so at decode's six rows a group a tile of 384 rows did
    64 times the arithmetic and ran compute-bound at 56% of the weights' wire
    (my chip run, PR 27), while the hundreds of rows a group of a long prompt
    want a last row tile long enough to hide the next group's copy behind. A
    group's weights land as ``[k, tn]``, ``tn`` the widest the ring's
    :data:`_RING_BYTES` hold (all of ``n`` at every width a cell has: ``lhs``
    is read once for each ``tn``), in chunks of ``tk`` rows, the largest under
    :data:`_CHUNK_BYTES` that leaves two chunks or more, so that a copy is in
    flight while a chunk multiplies (my chip runs, PR 46, the kernel alone at
    1,920 rows of ``kimi``'s gate projection: chunks of 0.7 / 1.4 / 2.9 MB
    543 / 559 / 583 us a call; at 384 rows all alike)."""
    fits = [t for t in _tiles(m, 512) if t >= m / groups]
    tm = min(fits, default=max(_tiles(m, 512), default=0))
    tn = max((t for t in _tiles(n, n) if k * t * itemsize <= _RING_BYTES), default=0)
    halves = _tiles(k, max(128, k // 2))
    tk = max((t for t in halves if t * tn * itemsize <= _CHUNK_BYTES), default=min(halves, default=0))
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
    layer: jnp.ndarray | int | None = None,  # scalar int32: which of ``rhs``'s L layers
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
    ``interpret`` runs the Pallas kernel in its interpreter (the CPU tests).
    One kernel serves every shape :func:`kernel_eligible` admits, the decode
    step's few rows a group and the rows of a step that carries a chunk alike."""
    group_sizes = group_sizes.astype(jnp.int32)
    g = rhs.shape[-3]
    if interpret or kernel_eligible(lhs.shape, rhs.shape[-3:], lhs.dtype, rhs.dtype, jax.default_backend()):
        # imported here: Pallas costs a second that no CPU process should pay
        from torchx_tpu.ops.grouped_matmul_kernel import walk

        note_traced("grouped_matmul", "walk")
        tiling = _tiling(lhs.shape[0], lhs.shape[1], rhs.shape[-1], rhs.dtype.itemsize, spread_over or g)
        # an array whatever the caller holds, a layer's number in a Python loop or a scan's counter: one trace
        at = jnp.asarray(0 if layer is None else layer, jnp.int32)
        return walk(lhs, rhs.reshape(-1, *rhs.shape[-3:]), group_sizes, at, tiling=tiling, interpret=interpret)
    note_traced("grouped_matmul", "ragged_dot")
    return jax.lax.ragged_dot(lhs, rhs[layer] if rhs.ndim == 4 else rhs, group_sizes)
