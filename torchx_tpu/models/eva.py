"""EVA attention over an aligned window and pooled chunks (``LlamaConfig.eva_window``).

Position ``t`` lies in window ``t // W`` and chunk ``t // C`` (``W = eva_window``,
``C = eva_chunk``; a window is ``W / C`` whole chunks). It attends, in **one**
softmax, the exact keys of its own window up to itself and one pooled row of
every chunk of every earlier window. A finished chunk is pooled once, from its
roped keys, with two learned vectors a cache head (``eva_phi``, ``eva_mu_k``
``[kvh, hd]``)::

    a_j = softmax over the chunk's C positions j of (phi . k_j)
    k~  = sum_j a_j k_j + mu          v~ = sum_j a_j v_j

A chunk of the current window is seen exactly and never through its pooled row,
which becomes visible when the window ends.

The serving path keeps both in the one paged pool under one table: a pooled row
has a K/V row's shape, so a byte's **cache coordinate** :func:`cache_coord`
``t' = (W / C) (t // W) + t % W`` puts the pooled rows of the windows behind it
in front of its own window's rows, and the decode kernel that reads ``t' + 1``
rows of a slot's table reads exactly the set above. Rope takes ``t``; the pool
is written and read at ``t'``. :func:`pool_filled` is the one device op the
layer adds: it pools the blocks that a step filled (a block is a chunk:
``block_size == eva_chunk``) into rows of the slot's staging blocks, which
nothing reads until the host moves them into the table at the window's end
(:class:`torchx_tpu.serve.kv_pool.EvaTables`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced
from torchx_tpu.ops.paged_attention import TRASH_BLOCK, stack_of

_MASKED = -1e30


def cache_coord(cfg, t):  # noqa: ANN001, ANN201
    """The row of its slot's cache that position ``t`` is written to, behind
    the ``W / C`` pooled rows of each window that ended before it."""
    return (cfg.eva_window // cfg.eva_chunk) * (t // cfg.eva_window) + t % cfg.eva_window


def pooled(layer: dict, k: jnp.ndarray, v: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunks ``k``, ``v`` ``[..., C, kvh, hd]`` (roped keys) -> their pooled
    rows ``(k~, v~) [..., kvh, hd]``, weights and sums in float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jax.nn.softmax(jnp.einsum("...ckd,kd->...ck", kf, layer["eva_phi"].astype(jnp.float32)), axis=-2)
    k_pooled = jnp.einsum("...ck,...ckd->...kd", a, kf) + layer["eva_mu_k"].astype(jnp.float32)
    return k_pooled.astype(k.dtype), jnp.einsum("...ck,...ckd->...kd", a, vf).astype(v.dtype)


@jax.named_scope(hot.ATTN_KERNEL)
def attention_full(cfg, layer: dict, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:  # noqa: ANN001
    """The uncached layer: ``q [b, s, h, hd]``, ``k`` and ``v [b, s, kvh, hd]``
    roped -> ``[b, s, h, hd]``. Every whole chunk is pooled once; a mask ``[s,
    s]`` admits a query's own window up to itself and a mask ``[s, s / C]`` the
    chunks of the windows before it, and one softmax runs over both."""
    note_traced("eva", "full")
    b, s, h, hd = q.shape
    kvh, window, chunk = k.shape[2], cfg.eva_window, cfg.eva_chunk
    n_chunks = s // chunk  # a chunk the sequence leaves open lies in its last window: nobody sees it pooled
    whole = lambda x: x[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, kvh, hd)  # noqa: E731
    k_pooled, v_pooled = pooled(layer, whole(k), whole(v))
    t = jnp.arange(s)
    exact = (t[None, :] <= t[:, None]) & (t[None, :] >= window * (t[:, None] // window))
    remote = jnp.arange(n_chunks)[None, :] < (window // chunk) * (t[:, None] // window)
    keys, values = jnp.concatenate((k, k_pooled), axis=1), jnp.concatenate((v, v_pooled), axis=1)
    grouped = q.reshape(b, s, kvh, h // kvh, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, keys, preferred_element_type=jnp.float32) * hd**-0.5
    scores = jnp.where(jnp.concatenate((exact, remote), axis=1), scores, _MASKED)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, values).reshape(b, s, h, hd)


@jax.named_scope(hot.EVA_POOL)
def pool_filled(cfg, layer: dict, k_pool, v_pool, at, ends, full, table, stage):  # noqa: ANN001, ANN201
    """Pool the blocks a step filled, each into one row of its sequence's
    staging blocks. ``ends [b, m]`` are the last positions of ``m`` chunks a row
    of ``table [b, blocks_per_slot]`` (a decode step: each slot's one position;
    a chunk of a prompt: every ``C``-th of its positions) and ``full [b, m]``
    says which of those chunks this step completed; the others' rows go to the
    trash block. The chunk of ``ends`` is read out of the pool, where the step
    has just written it (one block: ``block_size == eva_chunk``), and its pooled
    row lands in ``stage [b, W / C / block_size]`` at row ``(t % W) // C`` of the
    window. The pools are the layer scan's stacks, read and written at layer
    ``at`` (:func:`~torchx_tpu.ops.paged_attention.stack_of`). -> the pools."""
    note_traced("eva", "paged")
    k_stack, index = stack_of(k_pool, at)
    v_stack, _ = stack_of(v_pool, at)
    bs = k_stack.shape[2]
    entry = jnp.clip(cache_coord(cfg, ends) // bs, 0, table.shape[1] - 1)
    src = jnp.take_along_axis(table, entry, axis=1).reshape(-1)
    row = (ends % cfg.eva_window) // cfg.eva_chunk
    dst = jnp.where(full, jnp.take_along_axis(stage, row // bs, axis=1), TRASH_BLOCK).reshape(-1)
    offsets = (row % bs).reshape(-1)
    k_row, v_row = pooled(layer, k_stack[index, src], v_stack[index, src])  # [b m, kvh, hd]
    k_stack = k_stack.at[index, dst, offsets].set(k_row, mode="drop")
    v_stack = v_stack.at[index, dst, offsets].set(v_row, mode="drop")
    return (k_stack[0], v_stack[0]) if at is None else (k_stack, v_stack)
