"""The Pallas TPU kernel behind :func:`torchx_tpu.ops.grouped_matmul.grouped_matmul`.

Kept in a module of its own so that importing Pallas (about a second) is
paid only by a process that lowers the kernel.

The walk is over the groups that have rows, not over row tiles. A grid step
is one (group, row tile) pair in row order, so a row tile of ``lhs`` and of
the result stays in its pipeline buffer while the groups that reach into it
go by (each is copied in once and written out once a call), and an expert's
``[k, tn]`` weights are copied once a call, by hand, ``[tk, tn]`` chunks into
a ring of ``k / tk`` chunks: the step that multiplies a group's last row tile
starts the next group's copy of a chunk as soon as it has multiplied by that
chunk, so the wire always has a chunk or more in flight and never waits for a
row tile, in one expert's worth of fast memory.

:func:`walk` is a module-level ``jax.jit``: a program's call sites of one shape
share one trace and one lowered function (a ``pallas_call`` built in a plain
function is traced and lowered again at every call site: 0.35 s a site on the
benchmark machine's cores, 42 sites in ``k-exaone``'s engine; PERF.md section
6, PR 46), so ``layer`` goes in as an int32 array, never as a static value.
Differentiation goes to :func:`jax.lax.ragged_dot`, the same function of the
same arguments.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the Pallas call's name: what a device trace and a compiled program's text show
KERNEL_NAME = "grouped_matmul_walk"
_MIB = 1024 * 1024


def _metadata(group_sizes: jnp.ndarray, m: int, tm: int):  # noqa: ANN202
    """The walk's (group, row tile) pairs in row order, a group that has no row
    in none: -> (the group of each pair, its row tile, every group's first row
    and one past the last group's, the number of pairs). The pairs' arrays are
    one longer than the most pairs there can be (``m / tm + groups - 1``), so
    that a step may look at the pair behind it."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    length = m // tm + g
    groups = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles, total_repeat_length=length)
    nth = jnp.arange(length, dtype=jnp.int32) - (jnp.cumsum(tiles) - tiles)[groups]  # which of its group's tiles
    row_tiles = jnp.minimum((starts // tm)[groups] + nth, m // tm - 1)  # the padding stays inside lhs
    offsets = jnp.concatenate([starts[:1], ends])
    return groups, row_tiles, offsets, tiles.sum()[None]


def _kernel(
    layer_ref,  # SMEM [1]
    groups_ref,  # SMEM [pairs + 1]: the group of each (group, row tile) pair
    tiles_ref,  # SMEM [pairs + 1]: its row tile (read by the index maps)
    offsets_ref,  # SMEM [g + 1]: a group's first row
    pairs_ref,  # SMEM [1]: the pairs this call walks
    lhs_ref,  # VMEM [tm, k]: this pair's row tile
    rhs_hbm,  # HBM [L, g, k, n]
    out_ref,  # VMEM [tm, tn]
    ring,  # VMEM [k / tk, tk, tn]: the group's weights, chunk c where chunk c of the last group was
    sems,  # DMA [k / tk]
    acc_ref,  # VMEM [tm, tn] float32
):
    chunks, tk, tn = ring.shape
    tm = lhs_ref.shape[0]
    j, w = pl.program_id(0), pl.program_id(1)
    pairs, layer = pairs_ref[0], layer_ref[0]
    group, behind = groups_ref[w], groups_ref[w + 1]
    first = (w == 0) | (groups_ref[jnp.maximum(w - 1, 0)] != group)  # the group's first row tile: its weights land now
    end = w == pairs - 1  # this column tile's last pair: the next copy is the next column tile's first group's
    last = end | (behind != group)  # the group's last row tile: the ring is the next group's as each chunk is done with
    more = jnp.logical_not(end) | (j + 1 < pl.num_programs(0))
    next_group, next_j = jnp.where(end, groups_ref[0], behind), jnp.where(end, j + 1, j)

    def copy(group, j, c):  # noqa: ANN001, ANN202
        at = rhs_hbm.at[layer, group, pl.ds(pl.multiple_of(c * tk, 128), tk), pl.ds(pl.multiple_of(j * tn, 128), tn)]
        return pltpu.make_async_copy(at, ring.at[c], sems.at[c])

    @pl.when((j == 0) & (w == 0))  # nothing is in flight yet: the first group's chunks, all at once
    def _():
        jax.lax.fori_loop(0, chunks, lambda c, _: copy(group, j, c).start(), None)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(c, _):  # noqa: ANN001, ANN202
        pl.when(first)(lambda: copy(group, j, c).wait())
        columns = pl.ds(pl.multiple_of(c * tk, 128), tk)
        acc_ref[...] += jnp.dot(lhs_ref[:, columns], ring[c], preferred_element_type=jnp.float32)
        pl.when(last & more)(lambda: copy(next_group, next_j, c).start())

    jax.lax.fori_loop(0, chunks, chunk, None)
    row = tiles_ref[w] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
    out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _call(tiling: tuple[int, int, int], interpret: bool, lhs, rhs, group_sizes, layer):  # noqa: ANN001, ANN202
    (m, k), n = lhs.shape, rhs.shape[-1]
    tm, tk, tn = tiling
    groups, row_tiles, offsets, pairs = _metadata(group_sizes, m, tm)
    size, wide = lhs.dtype.itemsize, jnp.dtype(jnp.float32).itemsize
    # the ring, two buffers each of the row tile and the result's, the accumulator and a product beside it
    vmem = k * tn * size + 2 * tm * (k + tn) * size + 3 * tm * tn * wide
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, pairs[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, w, layer, groups, tiles, offsets, pairs: (tiles[w], 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, w, layer, groups, tiles, offsets, pairs: (tiles[w], j)),
            scratch_shapes=[
                pltpu.VMEM((k // tk, tk, tn), rhs.dtype),
                pltpu.SemaphoreType.DMA((k // tk,)),
                pltpu.VMEM((tm, tn), jnp.float32),
            ],
        ),
        # one after another: a step waits for copies that the step before it started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=min(vmem + 8 * _MIB, 100 * _MIB)
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0, bytes_accessed=(rhs.shape[1] * k * n + m * (k * (n // tn) + n)) * size
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(layer.reshape(1), groups, row_tiles, offsets, pairs, lhs, rhs)


def _fwd(tiling, interpret, lhs, rhs, group_sizes, layer):  # noqa: ANN001, ANN202
    return _call(tiling, interpret, lhs, rhs, group_sizes, layer), (lhs, rhs, group_sizes, layer)


def _bwd(tiling, interpret, saved, grad):  # noqa: ANN001, ANN202
    lhs, rhs, group_sizes, layer = saved
    _, pull = jax.vjp(lambda lhs, rhs: jax.lax.ragged_dot(lhs, rhs[layer], group_sizes), lhs, rhs)
    return *pull(grad), None, None


_differentiable = jax.custom_vjp(_call, nondiff_argnums=(0, 1))
_differentiable.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def walk(
    lhs: jnp.ndarray,  # [m, k], m in whole row tiles
    rhs: jnp.ndarray,  # [L, g, k, n]
    group_sizes: jnp.ndarray,  # [g] int32
    layer: jnp.ndarray,  # () int32: never a Python int, which would be one trace a layer
    *,
    tiling: tuple[int, int, int],  # (tm, tk, tn): grouped_matmul._tiling
    interpret: bool = False,
) -> jnp.ndarray:
    """``lhs``'s rows of group ``i`` times ``rhs[layer, i]`` -> ``[m, n]`` in
    ``lhs``'s dtype, accumulated in float32; rows behind the last group are
    not computed."""
    return _differentiable(tiling, interpret, lhs, rhs, group_sizes, layer)
