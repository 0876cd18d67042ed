"""Absorbed decode attention over a paged latent pool (multi-head latent attention).

The pool of one layer is ``[num_blocks, block_size, width]``: a token's
normed latent (``rank`` values) and its rotated rotary key side by side, one
row whatever the head count, zeros behind them up to ``width``, a whole
number of 128-value lanes (:mod:`torchx_tpu.models.mla`; 512 + 64 -> 640). Block tables, the trash block and
the allocator are those of :mod:`torchx_tpu.ops.paged_attention`; a row is
appended and scattered by that module's ``append_kv`` / ``scatter_kv_chunk``,
which take any row shape. As there, every function also takes a layer group's
pools as one stack ``[layers, num_blocks, block_size, width]`` with ``layer``,
the index to read at: the stack a layer scan carries, never sliced.

:func:`paged_mla_attention` takes each slot's query already absorbed, ``[h,
width]`` (``q_nope W_kvb[K]^T`` beside the rotated ``q_rope``), scores
it against the rows below the slot's length, and returns the
probability-weighted sum of the rows' latent part, ``[h, rank]``: every head
reads the same rows, which are both its keys and its values. On a TPU, where
:func:`kernel_eligible` allows, it is the ragged Pallas kernel of
:mod:`torchx_tpu.ops.paged_mla_kernel`, which copies only the blocks a slot
holds; elsewhere :func:`paged_mla_attention_xla`, which gathers the whole
window and masks, and is the reference the kernel is tested against.
``ops.attention.traced("attention")`` says which one a program lowered to
(``paged_mla_pallas`` / ``paged_mla_xla``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced
from torchx_tpu.ops.paged_attention import gather_kv


def kernel_eligible(
    q_shape: tuple[int, ...],  # [slots, h, width]
    pool_shape: tuple[int, ...],  # [num_blocks, bs, width]
    rank: int,
    q_dtype: jnp.dtype,
    pool_dtype: jnp.dtype,
    backend: str,
) -> bool:
    """Whether :func:`paged_mla_attention` lowers to the Pallas kernel: a pure
    function of shapes, dtypes and backend. The kernel needs a TPU, rows and
    their latent part of whole lanes (``width`` and ``rank`` multiples of
    128), query heads that fill a sublane tile, a block of whole packed
    tiles, and bf16 or float32 throughout."""
    _, h, _ = q_shape
    _, bs, width = pool_shape
    return (
        backend == "tpu"
        and rank % 128 == 0
        and width % 128 == 0
        and h % 8 == 0
        and bs % 16 == 0
        and q_dtype == pool_dtype
        and pool_dtype in (jnp.bfloat16, jnp.float32)
    )


@jax.named_scope(hot.PAGED_ATTENTION)
def paged_mla_attention(
    q: jnp.ndarray,  # [slots, h, width]: ONE absorbed query a slot
    pool: jnp.ndarray,  # [num_blocks, bs, width]
    tables: jnp.ndarray,  # [slots, blocks_per_slot] int32
    lengths: jnp.ndarray,  # [slots] int32: valid rows (incl. the current token's)
    rank: int,
    scale: float,
    layer=None,  # noqa: ANN001 — the pool is a stack [layers, num_blocks, bs, width]: attend this layer's
) -> jnp.ndarray:
    """-> ``[slots, h, rank]``: per head the softmax of ``scale * q . row``
    over the slot's rows below ``lengths[i]``, times the rows' first ``rank``
    values."""
    if kernel_eligible(q.shape, pool.shape[-3:], rank, q.dtype, pool.dtype, jax.default_backend()):
        from torchx_tpu.ops.paged_mla_kernel import paged_mla_pallas

        note_traced("attention", "paged_mla_pallas")
        return paged_mla_pallas(q, pool, tables, lengths, rank, scale, layer=layer)
    note_traced("attention", "paged_mla_xla")
    return paged_mla_attention_xla(q, pool, tables, lengths, rank, scale, layer)


def paged_mla_attention_xla(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    rank: int,
    scale: float,
    layer=None,  # noqa: ANN001
) -> jnp.ndarray:
    """:func:`paged_mla_attention` in plain XLA: gather every slot's whole
    window, mask by ``lengths``."""
    with jax.named_scope(hot.GATHER_KV):
        rows = gather_kv(pool, tables, layer)  # [slots, S, width]
    with jax.named_scope(hot.SCORES):
        logits = jnp.einsum("shc,stc->sht", q, rows, preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]  # [slots, S]
        probs = jax.nn.softmax(jnp.where(mask[:, None, :], logits, -1e30), axis=-1).astype(q.dtype)
    with jax.named_scope(hot.VALUES):
        return jnp.einsum("sht,str->shr", probs, rows[..., :rank])
