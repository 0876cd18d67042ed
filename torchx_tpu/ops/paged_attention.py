"""Paged (block-table) KV-cache attention for continuous-batching decode.

The dense decode cache (:mod:`torchx_tpu.models.generate`) reserves
``[L, batch, max_seq, kvh, hd]`` per sequence — worst-case ``max_seq``
whether or not the request ever decodes that far. Serving at high
concurrency wastes most of that HBM: the vLLM observation is that KV
memory should be allocated in fixed-size *blocks* as tokens actually
arrive, with a per-sequence *block table* mapping logical positions to
physical blocks in one shared pool.

This module is the device-side half: pure, jittable functions over a
fixed ``[num_blocks, block_size, kvh, hd]`` pool per layer (one block is
``block_size * kvh * hd`` contiguous elements: every cache head of a
position side by side). Every function also takes the layers' pools as one
stack ``[layers, num_blocks, block_size, kvh, hd]`` with ``layer``, the index
to work at: the serving programs carry the stack through their layer scan
whole (``models/generate.py::_scan_groups``), writes scatter at ``[layer,
block, offset]``, the XLA reads gather at ``[layer, block]`` and the kernel
copies block ``layer * num_blocks + block`` of the stack seen flat, so no
layer's pool is ever sliced out of the stack (a 134 MB copy a layer at the
chat cell's sizes). One layer's pool with no ``layer`` is a stack of one
(:func:`stack_of`): the same code, not a second path —

* :func:`paged_attention` — single-query-token GQA attention of every slot
  against the positions below its length. On a TPU, where
  :func:`kernel_eligible` allows, it is the ragged Pallas kernel of
  :mod:`torchx_tpu.ops.paged_attention_kernel`: per slot it walks the block
  table only as far as ``ceil(lengths[i] / block_size)``, copies those
  blocks from the pool in HBM to VMEM and folds them into an online
  softmax, the query heads of a cache head together, so K and V are read
  once, never gathered into a window and never repeated: bytes per step
  follow the tokens held, not ``max_seq x slots``. Elsewhere (the CPU, a
  ``head_dim`` that is no multiple of 128, cache heads that do not fill a
  tile) it is :func:`paged_attention_xla`, which gathers the whole window
  and masks; that function is also the reference the kernel is tested
  against. ``ops.attention.traced("attention")`` says which one a program
  lowered to (``paged_pallas`` / ``paged_xla``);
* :func:`paged_attention_chunk` — the same for a chunk of query tokens per
  slot (prefill): a walk over the key blocks with a running softmax, as far as
  the chunk's last token reaches (``traced`` answers ``paged_walk``);
* a sliding layer (``window``) in both: decode reads a ring table that holds
  only the blocks its window touches (``paged_pallas_window`` /
  ``paged_xla_window``), prefill skips the key blocks below the window
  (``paged_walk_window``);
* :func:`gather_kv` — block-table gather back to a contiguous
  ``[slots, S, kvh, hd]`` view (S = blocks_per_slot * block_size);
* :func:`append_kv` / :func:`scatter_kv_chunk` — scatter one new K/V token
  (a chunk of them) per slot into the pool at its block-table position;
* :func:`write_prefill` — bulk-write a prefilled prompt's K/V into the
  blocks a slot was assigned.

Everything is static-shape (XLA compiles once per pool geometry); the
host-side allocator that assigns blocks lives in
:mod:`torchx_tpu.serve.kv_pool`. Block 0 is reserved as the trash block:
unassigned table entries point at it, writes from inactive slots land in
it, and no softmax takes it in: the XLA path masks it, the kernel does not
read past a slot's live blocks. Either path multiplies the unwritten tail
of a slot's last block by a probability of zero, so a pool must hold
numbers there (zeros at start, an earlier request's K/V later).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced

#: Physical block index every unassigned block-table entry points at.
#: Writes from inactive/padded slots land here; masked attention never
#: reads it as valid context.
TRASH_BLOCK = 0


def stack_of(pool: jnp.ndarray, layer):  # noqa: ANN001, ANN201
    """``(stack, layer)`` as every function here works on them: one layer's
    pool (``layer`` None) is layer 0 of a stack of one, a reshape that moves
    nothing."""
    return (pool[None], 0) if layer is None else (pool, layer)


def gather_kv(pool: jnp.ndarray, tables: jnp.ndarray, layer=None) -> jnp.ndarray:  # noqa: ANN001
    """Gather one layer's pooled K (or V) into per-slot contiguous views.

    ``pool``: ``[num_blocks, block_size, kvh, hd]``; ``tables``:
    ``[slots, blocks_per_slot]`` int32 physical block ids. Returns
    ``[slots, blocks_per_slot * block_size, kvh, hd]`` — position ``p`` of
    slot ``i`` is ``pool[tables[i, p // bs], p % bs]``. With ``layer`` the
    pool is a stack and the layer goes into the gather's own index.
    """
    slots, bpr = tables.shape
    stack, layer = stack_of(pool, layer)
    g = stack[layer, tables]  # [slots, bpr, bs, kvh, hd]: one gather, no slice of the stack in front of it
    # whatever a position holds: [kvh, hd] here, one latent row in ops/paged_mla.py
    return g.reshape(slots, bpr * stack.shape[2], *stack.shape[3:])


def kernel_eligible(
    q_shape: tuple[int, ...],  # [slots, h, hd]
    pool_shape: tuple[int, ...],  # [num_blocks, bs, kvh, hd]
    q_dtype: jnp.dtype,
    pool_dtype: jnp.dtype,
    backend: str,
) -> bool:
    """Whether :func:`paged_attention` lowers to the Pallas kernel: a pure
    function of shapes, dtypes and backend. The kernel needs a TPU, lanes
    full of one head (``hd`` a multiple of 128), query heads that group
    evenly over the cache heads, the cache heads of one position a whole
    number of packed sublane rows (4 or a multiple: a block then lies in HBM
    and lands in VMEM as whole ``[bs * kvh, hd]`` rows; the chip's compiler
    takes 8 heads a position and, since PR 41, 4: 20 query heads over 4), a
    block of whole packed tiles, and bf16 or float32 throughout. Where cache
    heads are too few for that and several lanes wide, a position's heads lie
    in the pool as their 128-value parts one after another
    (``LlamaConfig.cache_row``: 2 heads of 256 as 4 rows of 128) and the rows
    are what has to be 4 or a multiple; the kernel takes heads of two parts."""
    _, h, hd = q_shape
    _, bs, rows, width = pool_shape
    parts = hd // width if width and hd % width == 0 else 0  # rows of the pool one cache head lies as
    return (
        backend == "tpu"
        and width % 128 == 0
        and parts in (1, 2)
        and rows % parts == 0
        and h % (rows // parts) == 0
        and rows % 4 == 0
        and bs % 8 == 0
        and q_dtype == pool_dtype
        and pool_dtype in (jnp.bfloat16, jnp.float32)
    )


def as_heads(kv: jnp.ndarray, hd: int) -> jnp.ndarray:
    """Gathered rows ``[..., rows, width]`` as the cache heads they are, ``[..., kvh, hd]``: themselves,
    but where a head lies in the pool as several rows (``LlamaConfig.cache_row``)."""
    return kv if kv.shape[-1] == hd else kv.reshape(*kv.shape[:-2], -1, hd)


@jax.named_scope(hot.PAGED_ATTENTION)
def paged_attention(
    q: jnp.ndarray,  # [slots, h, hd] — ONE query token per slot
    k_pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [slots, blocks_per_slot] int32
    lengths: jnp.ndarray,  # [slots] int32 — valid tokens (incl. current)
    layer=None,  # noqa: ANN001 — the pools are stacks [layers, num_blocks, ...]: attend this layer's
    window: int = 0,
) -> jnp.ndarray:
    """Single-token decode attention against the paged cache.

    ``window`` > 0 is a sliding layer's: slot ``i`` attends the positions
    ``lengths[i] - window <= p < lengths[i]`` only, and ``tables`` is a ring
    (block ``b`` of the sequence at entry ``b % blocks_per_slot``) that holds
    the blocks the window touches and no others (:func:`ring_positions`).

    Positions at or beyond ``lengths[i]`` — unwritten block tails and every
    unassigned (trash) block — are out of slot ``i``'s softmax. Returns
    ``[slots, h, hd]``. Lowers to
    :func:`~torchx_tpu.ops.paged_attention_kernel.paged_attention_pallas` where
    :func:`kernel_eligible` says so and to :func:`paged_attention_xla`
    elsewhere; ``ops.attention.traced("attention")`` tells which.
    """
    if kernel_eligible(
        q.shape, k_pool.shape[-4:], q.dtype, k_pool.dtype, jax.default_backend()
    ):
        # imported here: Pallas costs a second that no CPU process should pay
        from torchx_tpu.ops.paged_attention_kernel import paged_attention_pallas

        note_traced("attention", "paged_pallas_window" if window else "paged_pallas")
        return paged_attention_pallas(q, k_pool, v_pool, tables, lengths, layer=layer, window=window)
    note_traced("attention", "paged_xla_window" if window else "paged_xla")
    return paged_attention_xla(q, k_pool, v_pool, tables, lengths, layer, window)


def ring_positions(lengths: jnp.ndarray, window: int, bpr: int, bs: int) -> jnp.ndarray:
    """The sequence position of every row of a gathered ring table, ``[slots,
    bpr * bs]``: entry ``e`` holds the one block ``b`` with ``b % bpr == e``
    among the ``bpr`` blocks from the window's first on."""
    first = jnp.maximum(lengths - window, 0)[:, None] // bs  # [slots, 1]
    block = first + (jnp.arange(bpr)[None, :] - first) % bpr  # [slots, bpr]
    return (block[:, :, None] * bs + jnp.arange(bs)).reshape(lengths.shape[0], bpr * bs)


def paged_attention_xla(
    q: jnp.ndarray,  # [slots, h, hd]
    k_pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [slots, blocks_per_slot] int32
    lengths: jnp.ndarray,  # [slots] int32
    layer=None,  # noqa: ANN001
    window: int = 0,
) -> jnp.ndarray:
    """:func:`paged_attention` in plain XLA: gather every slot's whole
    window, fold query heads onto cache heads by repetition (same as the
    dense path's ``_cached_attention``), mask by ``lengths`` (and below the
    window, the rows placed by :func:`ring_positions`). The CPU path
    and the reference the kernel is tested against."""
    slots, h, d = q.shape
    with jax.named_scope(hot.GATHER_KV):
        k = as_heads(gather_kv(k_pool, tables, layer), d)  # [slots, S, kvh, hd]
        v = as_heads(gather_kv(v_pool, tables, layer), d)
        n_rep = h // k.shape[2]
        if n_rep > 1:
            k = jnp.repeat(k, n_rep, axis=2)
            v = jnp.repeat(v, n_rep, axis=2)
    with jax.named_scope(hot.SCORES):
        logits = (
            jnp.einsum("shd,sthd->sht", q, k, preferred_element_type=jnp.float32)
            * d**-0.5
        )
        S = k.shape[1]
        if window:
            at = ring_positions(lengths, window, tables.shape[1], S // tables.shape[1])
            mask = (at < lengths[:, None]) & (at >= lengths[:, None] - window)
        else:
            mask = jnp.arange(S)[None, :] < lengths[:, None]  # [slots, S]
        logits = jnp.where(mask[:, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    with jax.named_scope(hot.VALUES):
        return jnp.einsum("sht,sthd->shd", probs, v)


#: query rows of one block of the prefill scores, and the cached rows one step
#: of its walk over the key blocks gathers and scores: ``[slots, h, 512, 512]``
#: float32 a step, whatever ``max_seq`` is
_PREFILL_Q_ROWS = 512
_PREFILL_K_ROWS = 512
_MASKED = -1e30


@jax.named_scope(hot.PAGED_ATTENTION)
def paged_attention_chunk(
    q: jnp.ndarray,  # [slots, t, h, hd] — a chunk of query tokens per slot
    k_pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [slots, blocks_per_slot] int32, block b of the sequence at entry b
    positions: jnp.ndarray,  # [slots, t] int32 — absolute position of each query
    valid: jnp.ndarray | None = None,  # [slots, t] bool — real tokens (None: all)
    layer=None,  # noqa: ANN001
    window: int = 0,
) -> jnp.ndarray:
    """Multi-query-token attention against the paged cache.

    The chunked-prefill generalisation of :func:`paged_attention`: query
    ``j`` of slot ``i`` sits at absolute position ``positions[i, j]`` and
    attends causally to every cached position ``s <= positions[i, j]`` (on a
    sliding layer, ``window`` > 0, to those above ``positions[i, j] - window``)
    — which covers both a previously-cached shared prefix *and* the chunk's
    own K/V, provided the caller scattered the chunk into the pool first.

    A walk over the key blocks: :data:`_PREFILL_Q_ROWS` query rows at a time
    gather :data:`_PREFILL_K_ROWS` cached rows a step through the table, from
    the block the first real query's window starts in (block 0 on a full
    layer) as far as the last real query reaches and no further, and fold them
    into an online softmax (running maximum, sum and accumulator in float32):
    no ``[.., t, max_seq]`` array exists, and a round costs what its rows hold.
    The query heads of a cache head are scored together against K and V as
    they lie, not repeated. Padded query rows produce finite garbage that the
    caller never samples. Returns ``[slots, t, h, hd]``.
    """
    note_traced("attention", "paged_walk_window" if window else "paged_walk")
    slots, t, h, d = q.shape
    kvh, bs, bpr = k_pool.shape[-2] * k_pool.shape[-1] // d, k_pool.shape[-3], tables.shape[1]
    step_blocks = max(1, min(bpr, _PREFILL_K_ROWS // bs))
    step_rows = step_blocks * bs
    # whole steps: the blocks past the table are the trash block, past every position
    tables = jnp.pad(tables, ((0, 0), (0, -bpr % step_blocks)), constant_values=TRASH_BLOCK)
    valid = jnp.ones((slots, t), bool) if valid is None else valid
    grouped = q.reshape(slots, t, kvh, h // kvh, d)

    def rows(q_rows, pos_rows, valid_rows):  # noqa: ANN001, ANN202
        n_q = q_rows.shape[1]

        def step(c, carry):  # noqa: ANN001, ANN202
            m, l, acc = carry
            with jax.named_scope(hot.GATHER_KV):
                held = jax.lax.dynamic_slice_in_dim(tables, c * step_blocks, step_blocks, axis=1)
                k = as_heads(gather_kv(k_pool, held, layer), d)  # [slots, step_rows, kvh, hd]
                v = as_heads(gather_kv(v_pool, held, layer), d)
            with jax.named_scope(hot.SCORES):
                s = jnp.einsum("bqgrd,bkgd->bgrqk", q_rows, k, preferred_element_type=jnp.float32) * d**-0.5
                at = c * step_rows + jnp.arange(step_rows)
                admitted = at[None, None, :] <= pos_rows[:, :, None]  # [slots, n_q, step_rows]
                if window:
                    admitted &= at[None, None, :] > pos_rows[:, :, None] - window
                s = jnp.where(admitted[:, None, None], s, _MASKED)
                m_new = jnp.maximum(m, s.max(axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l = alpha * l + p.sum(axis=-1)
            with jax.named_scope(hot.VALUES):
                pv = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(q.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, alpha[..., None] * acc + pv

        # a query admits its own position, so its maximum ends finite; a step in
        # which a row admits nothing adds exp(0) terms that the first admitted
        # score's alpha = exp(-1e30 - m) = 0 wipes out
        last = jnp.max(jnp.where(valid_rows, pos_rows, 0))
        first = jnp.minimum(jnp.min(jnp.where(valid_rows, pos_rows, 2**30)), last)
        lo = jnp.maximum(first - window + 1, 0) // step_rows if window else 0
        _, l, acc = jax.lax.fori_loop(
            lo,
            last // step_rows + 1,
            step,
            (
                jnp.full((slots, kvh, h // kvh, n_q), _MASKED, jnp.float32),
                jnp.zeros((slots, kvh, h // kvh, n_q), jnp.float32),
                jnp.zeros((slots, kvh, h // kvh, n_q, d), jnp.float32),
            ),
        )
        out = (acc / l[..., None]).astype(q.dtype)  # [slots, kvh, rep, n_q, hd]
        return jnp.moveaxis(out, 3, 1).reshape(slots, n_q, h, d)

    n = max(1, t // _PREFILL_Q_ROWS)
    if n == 1:
        return rows(grouped, positions, valid)
    split = lambda x: jnp.moveaxis(x.reshape(slots, n, t // n, *x.shape[2:]), 1, 0)  # noqa: E731
    out = jax.lax.map(lambda a: rows(*a), (split(grouped), split(positions), split(valid)))
    return jnp.moveaxis(out, 0, 1).reshape(slots, t, h, d)


def _write_rows(pool, layer, block_ids, offsets, rows):  # noqa: ANN001, ANN202
    """``rows[i]`` to ``[layer, block_ids[i], offsets[i]]`` of the stack where
    it lies (one scatter; in place on a donated or carried stack). -> the pool
    in the form it came in."""
    stack, at = stack_of(pool, layer)
    stack = stack.at[at, block_ids, offsets].set(rows, mode="drop")
    return stack[0] if layer is None else stack


@jax.named_scope(hot.APPEND_KV)
def scatter_kv_chunk(
    pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    tables: jnp.ndarray,  # [slots, blocks_per_slot]
    positions: jnp.ndarray,  # [slots, t] — logical position of each new token
    new: jnp.ndarray,  # [slots, t, kvh, hd]
    valid: jnp.ndarray | None = None,  # [slots, t] bool — False: write trash
    layer=None,  # noqa: ANN001
) -> jnp.ndarray:
    """Scatter a chunk of new K (or V) tokens per slot into table positions.

    The multi-token form of :func:`append_kv`, used by suffix prefill:
    token ``j`` of slot ``i`` lands at ``tables[i, positions[i,j] // bs]``
    offset ``positions[i,j] % bs``. ``valid`` marks real (non-padding)
    tokens; invalid ones are redirected to the trash block — their
    positions can lie past the table (bucket padding), where a clamped
    gather would otherwise alias a live block.
    """
    slots, t = positions.shape
    bs = pool.shape[1 if layer is None else 2]
    block_idx = jnp.clip(positions // bs, 0, tables.shape[1] - 1)
    block_ids = jnp.take_along_axis(tables, block_idx, axis=1)  # [slots, t]
    if valid is not None:
        block_ids = jnp.where(valid, block_ids, TRASH_BLOCK)
    offsets = positions % bs
    flat_new = new.reshape(slots * t, *new.shape[2:])
    return _write_rows(pool, layer, block_ids.reshape(-1), offsets.reshape(-1), flat_new)


@jax.named_scope(hot.APPEND_KV)
def append_kv(
    pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    tables: jnp.ndarray,  # [slots, blocks_per_slot]
    positions: jnp.ndarray,  # [slots] — logical position being written
    new: jnp.ndarray,  # [slots, kvh, hd]
    layer=None,  # noqa: ANN001
    ring: bool = False,
) -> jnp.ndarray:
    """Scatter one new K (or V) token per slot into its table position
    (``ring``: a sliding layer's table, block ``b`` at entry ``b % blocks_per_slot``).

    Slots whose table entry for ``positions[i] // block_size`` is the
    trash block (inactive slots) harmlessly overwrite trash; collisions
    there don't matter because nothing masked-in ever reads it.
    """
    slots = tables.shape[0]
    bs = pool.shape[1 if layer is None else 2]
    entry = positions // bs
    block_ids = tables[jnp.arange(slots), entry % tables.shape[1] if ring else entry]  # [slots]
    offsets = positions % bs
    return _write_rows(pool, layer, block_ids, offsets, new)


@jax.named_scope(hot.APPEND_KV)
def write_prefill(
    pool: jnp.ndarray,  # [num_blocks, bs, kvh, hd]
    block_ids: jnp.ndarray,  # [n_bucket_blocks] physical ids (trash-padded)
    kv: jnp.ndarray,  # [t_bucket, kvh, hd] — t_bucket = n_bucket_blocks * bs
) -> jnp.ndarray:
    """Bulk-write a prefilled prompt's K (or V) rows into assigned blocks.

    ``kv`` covers the whole prefill bucket; rows past the true prompt
    length are garbage from padding and land either in the slot's own
    final block past its valid length (masked) or — for fully-unused
    bucket blocks — in the trash block.
    """
    nb = block_ids.shape[0]
    bs = pool.shape[1]
    chunks = kv.reshape(nb, bs, *kv.shape[1:])
    return pool.at[block_ids].set(chunks, mode="drop")
