"""The ragged Pallas TPU kernel behind :func:`torchx_tpu.ops.paged_attention.paged_attention`.

Kept in a module of its own so that importing Pallas (about a second) is
paid only by a process that lowers the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Bytes of K one compute step of the kernel holds, and as many of V, each
#: in two buffers: 256 positions of 8 bf16 heads of 128. On the v5e 256 KiB
#: ran 8-18% slower and 1 MiB no faster (PERF.md section 6, PR 25).
_CHUNK_BYTES = 512 * 1024
_MASKED = -1e30
_NO_POSITION = 2**30


def _decode_kernel(
    lengths_ref,  # SMEM [slots]
    tables_ref,  # SMEM [slots * bpr]
    q_ref,  # VMEM [h, hd] — this slot's query heads
    k_hbm,  # HBM [num_blocks, bs, kvh, hd]
    v_hbm,
    o_ref,  # VMEM [h, hd]
    k_buf,  # VMEM [2, chunk, bs, kvh, hd]
    v_buf,
    sems,  # DMA [2 (k, v), 2 (buffer)]
    pos_ref,  # VMEM [h, rows] int32: a column's position in the chunk, for its head's rows
    buf_ref,  # SMEM [1]: the buffer that holds this slot's first chunk
    *,
    bpr: int,
    scale: float,
    window: int,
):
    _, chunk, bs, kvh, hd = k_buf.shape
    h = q_ref.shape[0]
    rows = chunk * bs * kvh
    slot, slots = pl.program_id(0), pl.num_programs(0)
    # Mosaic multiplies float32 in one bfloat16 pass unless told otherwise; XLA's
    # einsum on the TPU does not (errors of 1e-2 against 2e-6, PERF.md section 6).
    precision = jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32 else None

    def first_block(s):  # noqa: ANN001, ANN202
        """The lowest block a slot's query reads: 0, or on a sliding layer the
        block of the window's first position (``window`` is static)."""
        return jnp.maximum(lengths_ref[s] - window, 0) // bs if window else 0

    def live_blocks(s):  # noqa: ANN001, ANN202
        if window:  # the table is a ring: the blocks from the window's first to the last written
            return jnp.maximum(pl.cdiv(lengths_ref[s], bs), 1) - first_block(s)
        return jnp.clip(pl.cdiv(lengths_ref[s], bs), 1, bpr)

    def each_copy(s, c, buf, act):  # noqa: ANN001, ANN202
        """``act`` on the copy of every live block of chunk ``c`` of slot ``s``."""
        first = c * chunk

        def one(j, _):  # noqa: ANN001, ANN202
            at = (first_block(s) + first + j) % bpr if window else first + j
            blk = tables_ref[s * bpr + at]
            act(pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[buf, j], sems.at[0, buf]))
            act(pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[buf, j], sems.at[1, buf]))

        jax.lax.fori_loop(0, jnp.minimum(chunk, live_blocks(s) - first), one, None)

    def start(dma):  # noqa: ANN001, ANN202
        dma.start()

    @pl.when(slot == 0)
    def _():
        # A masked probability is 0, and 0 * NaN is NaN: rows no copy has
        # written yet must hold numbers.
        v_buf[...] = jnp.zeros_like(v_buf)
        col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0) // (h // kvh)
        pos_ref[...] = jnp.where(col % kvh == head, col // kvh, _NO_POSITION)
        buf_ref[0] = 0
        each_copy(0, 0, 0, start)

    length = jnp.maximum(lengths_ref[slot], 1)
    n_chunks = pl.cdiv(live_blocks(slot), chunk)
    first_buf = buf_ref[0]
    q = q_ref[...]

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
        k = k_buf.at[cur].reshape(rows, hd)[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision, preferred_element_type=jnp.float32
        ) * scale  # [h, rows]: every query head against every cache head
        if window:
            base = (first_block(slot) + c * chunk) * bs
            admitted = jnp.logical_and(pos_ref[...] < length - base, pos_ref[...] >= length - window - base)
        else:
            admitted = pos_ref[...] < length - c * chunk * bs
        s = jnp.where(admitted, s, _MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        v = v_buf.at[cur].reshape(rows, hd)[...]
        pv = jnp.dot(p.astype(v.dtype), v, precision=precision, preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1, keepdims=True), alpha * acc + pv

    _, l, acc = jax.lax.fori_loop(
        0,
        n_chunks,
        step,
        (
            jnp.full((h, 1), _MASKED, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, hd), jnp.float32),
        ),
    )
    buf_ref[0] = (first_buf + n_chunks) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,  # [slots, h, hd]
    k_pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [slots, blocks_per_slot] int32
    lengths: jnp.ndarray,  # [slots] int32
    interpret: bool = False,
    layer=None,  # noqa: ANN001
    window: int = 0,
) -> jnp.ndarray:
    """:func:`paged_attention` as one ragged Pallas TPU kernel.

    ``window`` > 0 is a sliding layer: slot ``i`` attends the positions
    ``lengths[i] - window <= p < lengths[i]`` and its table is a ring, block
    ``b`` of the sequence at entry ``b % blocks_per_slot``; the step copies the
    ``ceil(window / bs) + 1`` blocks at most that the window touches, whatever
    the context, and masks the positions below the window in the first of them.

    With ``layer`` the pools are the stacks ``[layers, num_blocks, bs, kvh,
    hd]`` a layer scan carries, read where they lie: block ``b`` of layer
    ``i`` is block ``i * num_blocks + b`` of the stack seen flat (a reshape
    that moves nothing), so the layer goes into the block ids and the kernel
    is the same for a stack as for one layer's pool. (As one more index of
    every block copy it cost the latent kernel 4% of its time alone on the
    chip: PERF.md section 6, PR 28.)

    One grid step per slot. The pools stay in HBM; ``tables`` and
    ``lengths`` are scalar-prefetched, and the step copies only the slot's
    ``ceil(lengths[i] / bs)`` live blocks (at least one, at most the
    table), a chunk of :data:`_CHUNK_BYTES` of K at a time into one of two
    VMEM buffers, the next chunk (or the next slot's first) in flight
    while this one is computed. A block arrives as ``[bs * kvh, hd]`` rows,
    all cache heads of a position side by side, and is never regrouped:
    the step multiplies all ``h`` query heads with all ``kvh`` cache heads
    of the chunk in one matmul and keeps, per query head, the columns of
    its own cache head (the rest are masked with the positions at or past
    ``lengths[i]``), so K and V are read once and not repeated. Scores,
    running maximum, sum and accumulator are float32; probabilities are
    cast to the pool's dtype for ``P @ V``. ``interpret`` runs the kernel
    in Pallas's interpreter (the CPU tests).
    """
    slots, h, hd = q.shape
    if layer is not None:
        tables = tables + layer * k_pool.shape[1]
        k_pool, v_pool = (p.reshape(-1, *p.shape[2:]) for p in (k_pool, v_pool))
    _, bs, kvh, _ = k_pool.shape
    bpr = tables.shape[1]
    chunk = max(1, min(bpr, _CHUNK_BYTES // (bs * kvh * hd * k_pool.dtype.itemsize)))
    rows = chunk * bs * kvh
    per_slot = pl.BlockSpec((None, h, hd), lambda i, *_: (i, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_decode_kernel, bpr=bpr, scale=hd**-0.5, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[per_slot, in_hbm, in_hbm],
            out_specs=per_slot,
            scratch_shapes=[
                pltpu.VMEM((2, chunk, bs, kvh, hd), k_pool.dtype),
                pltpu.VMEM((2, chunk, bs, kvh, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h, rows), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_decode",
    )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32), q, k_pool, v_pool)
