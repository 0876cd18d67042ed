"""The ragged Pallas TPU kernel behind :func:`torchx_tpu.ops.paged_mla.paged_mla_attention`.

Kept in a module of its own so that importing Pallas (about a second) is
paid only by a process that lowers the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Latent rows one compute step of the kernel holds in each of its two
#: buffers: 512 rows of 576 bf16 values are 576 KiB, the size at which the
#: K/V kernel of ops/paged_attention_kernel.py ran best on the v5e.
_CHUNK_ROWS = 512
_MASKED = -1e30


def _decode_kernel(
    lengths_ref,  # SMEM [slots]
    tables_ref,  # SMEM [slots * bpr]
    q_ref,  # VMEM [h, width]: this slot's absorbed query heads
    pool_hbm,  # HBM [num_blocks, bs, width]
    o_ref,  # VMEM [h, rank]
    buf,  # VMEM [2, chunk, bs, width]
    sems,  # DMA [2 (buffer)]
    buf_ref,  # SMEM [1]: the buffer that holds this slot's first chunk
    *,
    bpr: int,
    rank: int,
    scale: float,
):
    _, chunk, bs, width = buf.shape
    h = q_ref.shape[0]
    rows = chunk * bs
    slot, slots = pl.program_id(0), pl.num_programs(0)
    # Mosaic multiplies float32 in one bfloat16 pass unless told otherwise
    precision = jax.lax.Precision.HIGHEST if buf.dtype == jnp.float32 else None

    def live_blocks(s):  # noqa: ANN001, ANN202
        return jnp.clip(pl.cdiv(lengths_ref[s], bs), 1, bpr)

    def each_copy(s, c, b, act):  # noqa: ANN001, ANN202
        """``act`` on the copy of every live block of chunk ``c`` of slot ``s``."""
        first = c * chunk

        def one(j, _):  # noqa: ANN001, ANN202
            blk = tables_ref[s * bpr + first + j]
            act(pltpu.make_async_copy(pool_hbm.at[blk], buf.at[b, j], sems.at[b]))

        jax.lax.fori_loop(0, jnp.minimum(chunk, live_blocks(s) - first), one, None)

    def start(dma):  # noqa: ANN001, ANN202
        dma.start()

    @pl.when(slot == 0)
    def _():
        # The rows are the values too: a masked probability is 0, and 0 * NaN
        # is NaN, so rows no copy has written yet must hold numbers.
        buf[...] = jnp.zeros_like(buf)
        buf_ref[0] = 0
        each_copy(0, 0, 0, start)

    length = jnp.maximum(lengths_ref[slot], 1)
    n_chunks = pl.cdiv(live_blocks(slot), chunk)
    first_buf = buf_ref[0]
    q = q_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)

    def step(c, carry):  # noqa: ANN001, ANN202
        m, l, acc = carry
        cur = (first_buf + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            each_copy(slot, c + 1, 1 - cur, start)

        @pl.when(jnp.logical_and(c + 1 == n_chunks, slot + 1 < slots))
        def _():
            each_copy(slot + 1, 0, 1 - cur, start)

        each_copy(slot, c, cur, lambda dma: dma.wait())
        kv = buf.at[cur].reshape(rows, width)[...]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())), precision=precision, preferred_element_type=jnp.float32
        ) * scale  # [h, rows]: every head against the one row a position holds
        s = jnp.where(col < length - c * rows, s, _MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        pv = jnp.dot(p.astype(kv.dtype), kv[:, :rank], precision=precision, preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1, keepdims=True), alpha * acc + pv

    _, l, acc = jax.lax.fori_loop(
        0,
        n_chunks,
        step,
        (
            jnp.full((h, 1), _MASKED, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, rank), jnp.float32),
        ),
    )
    buf_ref[0] = (first_buf + n_chunks) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_mla_pallas(
    q: jnp.ndarray,  # [slots, h, width]
    pool: jnp.ndarray,  # [num_blocks, bs, width]
    tables: jnp.ndarray,  # [slots, blocks_per_slot] int32
    lengths: jnp.ndarray,  # [slots] int32
    rank: int,
    scale: float,
    interpret: bool = False,
    layer=None,  # noqa: ANN001
) -> jnp.ndarray:
    """:func:`~torchx_tpu.ops.paged_mla.paged_mla_attention` as one ragged
    Pallas TPU kernel, the latent twin of
    :func:`~torchx_tpu.ops.paged_attention_kernel.paged_attention_pallas`.

    With ``layer`` the pool is the stack ``[layers, num_blocks, bs, width]`` a
    layer scan carries, read where it lies: the layer goes into the block ids
    over the stack seen flat, as in ``paged_attention_pallas``.

    One grid step per slot. The pool stays in HBM; ``tables`` and ``lengths``
    are scalar-prefetched, and the step copies only the slot's
    ``ceil(lengths[i] / bs)`` live blocks (at least one, at most the table),
    :data:`_CHUNK_ROWS` rows at a time into one of two VMEM buffers, the next
    chunk (or the next slot's first) in flight while this one is computed. A
    chunk is ``[rows, width]``: one matmul scores all ``h`` heads
    against it, and a second takes the probabilities times its first ``rank``
    columns, so a row is read once and serves every head as key and as value.
    Scores, running maximum, sum and accumulator are float32; probabilities
    are cast to the pool's dtype for the second product. ``interpret`` runs
    the kernel in Pallas's interpreter (the CPU tests).
    """
    slots, h, width = q.shape
    if layer is not None:
        tables = tables + layer * pool.shape[1]
        pool = pool.reshape(-1, *pool.shape[2:])
    _, bs, _ = pool.shape
    bpr = tables.shape[1]
    chunk = max(1, min(bpr, _CHUNK_ROWS // bs))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_decode_kernel, bpr=bpr, rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[pl.BlockSpec((None, h, width), lambda i, *_: (i, 0, 0)), in_hbm],
            out_specs=pl.BlockSpec((None, h, rank), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, bs, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_mla_decode",
    )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32), q, pool)
