"""Multi-head latent attention (MLA): one layer's attention in its three forms.

A token's keys and values of all ``h`` heads are one latent ``c`` of
``kv_lora_rank`` values (RMS-normed) and one rotary key ``k_rope`` of
``qk_rope_dim`` values that every head shares::

    q = u W_q            as [h, qk_nope_dim + qk_rope_dim] = (q_nope, q_rope)
    u W_kva              as [kv_lora_rank + qk_rope_dim]   = (c, k_rope);  c = RMSNorm_kv(c)
    c W_kvb              as [h, qk_nope_dim + v_head_dim]  = (k_nope, v)
    k = (k_nope, k_rope for every head);  softmax(q.k * cfg.attn_scale) v;  W_o

With ``q_lora_rank > 0`` the query goes through a latent of its own, ``c_q =
RMSNorm_q(u W_qa)``, ``q = c_q W_qb``, and the tree holds ``W_kvb`` as the absorbed
decode multiplies it, its key and its value columns apart, each transposed with
the heads outermost: ``w_uk [h, qk_nope_dim, kv_lora_rank]`` and ``w_uv [h,
v_head_dim, kv_lora_rank]`` (a loader writes them so once; :func:`_head_blocks`
has the rehearsal's reason). ``cfg.attn_scale`` is ``(qk_nope_dim +
qk_rope_dim)^-0.5``, times the square of YaRN's ``mscale`` where the model
scales its rotary frequencies; all three forms below read that one number.

What a token leaves in the cache is ``(c, k_rope after rotation)``, one row a
layer whatever the head count, padded with zeros to ``cfg.cache_width`` (whole
lanes: 512 + 64 -> 640).

* :func:`attention_full`: the whole sequence at once (``llama.forward``,
  ``loss_fn``): K and V expanded from the latent.
* :func:`paged_prefill`: a chunk of query tokens over cached prefix + chunk
  in the paged latent pool, K and V expanded from the rows the walk gathers,
  a few blocks a step, no further than the chunk's last token reaches.
* :func:`paged_decode`: one token a slot, absorbed: ``W_kvb``'s key part is
  folded into the query (``q_lat = q_nope W_kvb[K]^T`` in ``[h, rank]``), the
  scores and the weighted sum run over the latent rows themselves
  (:func:`torchx_tpu.ops.paged_mla.paged_mla_attention`), and ``W_kvb``'s
  value part takes the result back to ``[h, v_head_dim]``. K and V are never
  expanded: the same mathematics, reassociated.

The rotary pairing is the program's (:mod:`torchx_tpu.ops.rope`: dimension
``i`` with ``i + rope/2``). A published checkpoint that pairs ``(2i, 2i+1)``
is loaded with the ``qk_rope_dim`` rotary columns of each head in ``W_q`` and
of ``W_kva`` reordered evens first, then odds; scores are unchanged by a
common reordering of the dimensions ``q`` and ``k`` are dotted over.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced, project_heads, xla_attention
from torchx_tpu.ops.norms import rms_norm
from torchx_tpu.ops.paged_attention import TRASH_BLOCK, append_kv, gather_kv, scatter_kv_chunk
from torchx_tpu.ops.paged_mla import paged_mla_attention
from torchx_tpu.ops.quant import maybe_matmul as mm
from torchx_tpu.ops.rope import rotate

#: query rows of one block of the prefill scores, and the cached rows one step
#: of its walk over the window expands and scores: ``[b, h, 512, 512]`` float32
#: a step, whatever ``max_seq`` is
_PREFILL_Q_ROWS = 512
_PREFILL_K_ROWS = 512
_MASKED = -1e30


def project(cfg, layer, u: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):  # noqa: ANN001
    """``u [..., d]`` -> ``q_nope [..., h, nope]``, ``q_rope [..., h, rope]``
    (rotated), and what the token leaves in the cache, ``[..., cache_width]``:
    the normed latent and the rotated key side by side, zeros behind them."""
    h, dn, dr, r = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    if "w_qa" in layer:
        with jax.named_scope(hot.MLA_Q_LATENT):
            c_q = rms_norm(mm(u, layer["w_qa"]), layer["q_latent_norm"], cfg.norm_eps)
        q = project_heads(c_q, layer["w_qb"], h, dn + dr)
    else:
        q = mm(u, layer["wq"]).reshape(*u.shape[:-1], h, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], cos, sin)
    with jax.named_scope(hot.MLA_LATENT):
        kva = mm(u, layer["w_kva"])
        c = rms_norm(kva[..., :r], layer["kv_norm"], cfg.norm_eps)
        k_rope = rotate(kva[..., None, r:], cos, sin)[..., 0, :]
        pad = jnp.zeros((*c.shape[:-1], cfg.cache_width - r - dr), c.dtype)
        cached = jnp.concatenate((c, k_rope, pad), axis=-1)
    return q_nope, q_rope, cached


def _expand(cfg, layer, cached: jnp.ndarray):  # noqa: ANN001
    """Cached rows ``[..., cache_width]`` -> every head's key ``[..., h, nope
    + rope]`` and value ``[..., h, v]``."""
    h, dn, dv, r, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank, cfg.qk_rope_dim
    k_rope = jnp.broadcast_to(cached[..., None, r : r + dr], (*cached.shape[:-1], h, dr))
    if "w_uk" in layer:  # split into heads as W_qb is: each weight read where it lies in its stack
        k_nope = project_heads(cached[..., :r], layer["w_uk"].reshape(h * dn, r).T, h, dn)
        v = project_heads(cached[..., :r], layer["w_uv"].reshape(h * dv, r).T, h, dv)
        return jnp.concatenate((k_nope, k_rope), axis=-1), v
    kv = mm(cached[..., :r], layer["w_kvb"]).reshape(*cached.shape[:-1], h, dn + dv)
    return jnp.concatenate((kv[..., :dn], k_rope), axis=-1), kv[..., dn:]


def attention_full(cfg, layer, u: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:  # noqa: ANN001
    """Causal attention of ``u [b, s, d]`` over itself, expanded: -> ``[b, s, d]``."""
    b, s, _ = u.shape
    q_nope, q_rope, cached = project(cfg, layer, u, cos, sin)
    k, v = _expand(cfg, layer, cached)
    note_traced("attention", "xla")
    with jax.named_scope(hot.ATTN_KERNEL):
        out = xla_attention(jnp.concatenate((q_nope, q_rope), axis=-1), k, v, causal=True, scale=cfg.attn_scale)
    return mm(out.reshape(b, s, cfg.n_heads * cfg.v_head_dim), layer["wo"])


def attend_chunk(
    cfg,  # noqa: ANN001
    layer,  # noqa: ANN001
    q_nope: jnp.ndarray,  # [b, t, h, nope]
    q_rope: jnp.ndarray,  # [b, t, h, rope], rotated
    positions: jnp.ndarray,  # [b, t] absolute cache positions
    valid: jnp.ndarray,  # [b, t] bool: real suffix tokens
    tables: jnp.ndarray,  # [b, blocks_per_slot]
    pool: jnp.ndarray,  # the latent pool, the chunk's rows already in it
) -> jnp.ndarray:
    """Each chunk token causally over cached prefix + chunk, K and V expanded
    from the latent rows. ``_PREFILL_Q_ROWS`` query rows at a time walk the window
    ``_PREFILL_K_ROWS`` cached rows a step, as far as the block's last real
    token reaches and no further (an online softmax: running maximum, sum and
    accumulator in float32), so a chunk costs what its rows hold and not
    ``max_seq``. -> ``[b, t, h, v]``, ahead of ``W_o``."""
    b, t, h, _ = q_nope.shape
    dv = cfg.v_head_dim
    pool_layer = layer.get("layer_index")
    note_traced("attention", "paged_mla_xla")
    with jax.named_scope(hot.PAGED_ATTENTION):
        q = jnp.concatenate((q_nope, q_rope), axis=-1)  # [b, t, h, nope + rope]
        bs, bpr = pool.shape[-2], tables.shape[1]
        step_blocks = max(1, min(bpr, _PREFILL_K_ROWS // bs))
        step_rows = step_blocks * bs
        # whole steps: the blocks past the table are the trash block, past every position
        tables = jnp.pad(tables, ((0, 0), (0, -bpr % step_blocks)), constant_values=TRASH_BLOCK)

        def rows(q_rows, pos_rows, valid_rows):  # noqa: ANN001, ANN202
            n_q = q_rows.shape[1]

            def step(c, carry):  # noqa: ANN001, ANN202
                m, l, acc = carry
                with jax.named_scope(hot.GATHER_KV):
                    held = jax.lax.dynamic_slice_in_dim(tables, c * step_blocks, step_blocks, axis=1)
                    k, v = _expand(cfg, layer, gather_kv(pool, held, pool_layer))  # [b, step_rows, h, .]
                with jax.named_scope(hot.SCORES):
                    s = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k, preferred_element_type=jnp.float32) * cfg.attn_scale
                    at = c * step_rows + jnp.arange(step_rows)
                    s = jnp.where((at[None, None, :] <= pos_rows[:, :, None])[:, None], s, _MASKED)
                    m_new = jnp.maximum(m, s.max(axis=-1))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new[..., None])
                    l = alpha * l + p.sum(axis=-1)
                with jax.named_scope(hot.VALUES):
                    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), v, preferred_element_type=jnp.float32)
                return m_new, l, alpha[..., None] * acc + pv

            # position 0 is below every query's, so the first step leaves a finite maximum
            last = jnp.max(jnp.where(valid_rows, pos_rows, 0))
            _, l, acc = jax.lax.fori_loop(
                0,
                last // step_rows + 1,
                step,
                (
                    jnp.full((b, h, n_q), _MASKED, jnp.float32),
                    jnp.zeros((b, h, n_q), jnp.float32),
                    jnp.zeros((b, h, n_q, dv), jnp.float32),
                ),
            )
            return jnp.moveaxis(acc / l[..., None], 1, 2).astype(q.dtype)  # [b, rows, h, v]

        n = max(1, t // _PREFILL_Q_ROWS)
        if n == 1:
            return rows(q, positions, valid)
        split = lambda x: jnp.moveaxis(x.reshape(b, n, t // n, *x.shape[2:]), 1, 0)  # noqa: E731
        out = jax.lax.map(lambda a: rows(*a), (split(q), split(positions), split(valid)))
        return jnp.moveaxis(out, 0, 1).reshape(b, t, h, dv)


def attend_decode(
    cfg,  # noqa: ANN001
    layer,  # noqa: ANN001
    q_nope: jnp.ndarray,  # [slots, h, nope]
    q_rope: jnp.ndarray,  # [slots, h, rope], rotated
    positions: jnp.ndarray,  # [slots] where each slot's newest row lies
    tables: jnp.ndarray,  # [slots, blocks_per_slot]
    pool: jnp.ndarray,  # the latent pool, the slots' rows already in it
) -> jnp.ndarray:
    """One token a slot, absorbed, over the latent rows its blocks hold.
    -> ``[slots, h, v]``, ahead of ``W_o``."""
    slots, h = q_nope.shape[:2]
    r = cfg.kv_lora_rank
    pool_layer = layer.get("layer_index")
    (w_k, k_axes), (w_v, v_axes) = _head_blocks(cfg, layer)
    with jax.named_scope(hot.MLA_ABSORB):
        q_lat = jnp.einsum(f"shn,{k_axes}->shr", q_nope, w_k)
    q_pad = jnp.zeros((slots, h, pool.shape[-1] - r - cfg.qk_rope_dim), q_lat.dtype)
    o_lat = paged_mla_attention(
        jnp.concatenate((q_lat, q_rope, q_pad), axis=-1), pool, tables, positions + 1, r, cfg.attn_scale, pool_layer,
    )  # [slots, h, rank]
    with jax.named_scope(hot.MLA_ABSORB):
        return jnp.einsum(f"shr,{v_axes}->shv", o_lat, w_v)


def paged_prefill(
    cfg,  # noqa: ANN001
    layer,  # noqa: ANN001
    u: jnp.ndarray,  # [b, t, d] normed chunk
    cos: jnp.ndarray,  # [b, t, rope/2]
    sin: jnp.ndarray,
    positions: jnp.ndarray,  # [b, t] absolute cache positions
    valid: jnp.ndarray,  # [b, t] bool: real suffix tokens
    tables: jnp.ndarray,  # [b, blocks_per_slot]
    pool: jnp.ndarray,  # [num_blocks, bs, cache_width] this layer's latent pool
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter the chunk's latent rows into the pool, then attend each chunk
    token causally over cached prefix + chunk (:func:`attend_chunk`). Under a layer scan (``llama.scan_layers``) ``pool``
    is the group's whole stack ``[layers, num_blocks, ...]``, written and
    gathered at ``layer["layer_index"]`` where it lies. The serving programs
    (``generate._paged_layer_step``) call the three pieces themselves, so that a
    step's decode rows and a chunk share one projection; this is the layer
    alone, as the tests hold it. -> (attention output ``[b, t, d]``, the pool)."""
    b, t, _ = u.shape
    q_nope, q_rope, cached = project(cfg, layer, u, cos, sin)
    with jax.named_scope(hot.APPEND_LATENT):
        pool = scatter_kv_chunk(pool, tables, positions, cached, valid, layer.get("layer_index"))
    out = attend_chunk(cfg, layer, q_nope, q_rope, positions, valid, tables, pool)
    return mm(out.reshape(b, t, cfg.n_heads * cfg.v_head_dim), layer["wo"]), pool


def paged_decode(
    cfg,  # noqa: ANN001
    layer,  # noqa: ANN001
    u: jnp.ndarray,  # [slots, 1, d] normed
    cos: jnp.ndarray,  # [slots, rope/2]
    sin: jnp.ndarray,
    positions: jnp.ndarray,  # [slots] where each token's row goes
    tables: jnp.ndarray,  # [slots, blocks_per_slot]
    pool: jnp.ndarray,  # [num_blocks, bs, cache_width]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Append each slot's latent row, then attend absorbed over the latent
    rows its blocks hold (:func:`attend_decode`); ``pool`` is one layer's, or
    under a layer scan the group's stack, as in :func:`paged_prefill`. ->
    (attention output ``[slots, 1, d]``, the pool)."""
    slots = u.shape[0]
    q_nope, q_rope, cached = project(cfg, layer, u[:, 0], cos, sin)
    with jax.named_scope(hot.APPEND_LATENT):
        pool = append_kv(pool, tables, positions, cached, layer.get("layer_index"))
    out = attend_decode(cfg, layer, q_nope, q_rope, positions, tables, pool)
    return mm(out.reshape(slots, 1, cfg.n_heads * cfg.v_head_dim), layer["wo"]), pool


def _head_blocks(cfg, layer):  # noqa: ANN001, ANN202
    """``W_kvb`` as the absorbed form multiplies it, a product a head: -> (its
    key part, that array's axes as an einsum names them: ``h`` heads, ``n`` nope,
    ``r`` rank), (its value part, its axes: ``v``). A product batched over the
    heads takes the heads outermost. A tree that holds the two parts so
    (``w_uk [h, nope, rank]``, ``w_uv [h, v, rank]``: the compressed query's, see
    the module's docstring) has each read where it lies, by its one product: a
    reshape between a layer's slice of the stack and the batched product is
    enough to have the slice written out first (rehearsal, PR 33). ``w_kvb
    [rank, h (nope + v)]`` is sliced out of its stack and written transposed in
    front of the products, every step (ROADMAP queue 1 item 6)."""
    if "w_uk" in layer:
        return (layer["w_uk"], "hnr"), (layer["w_uv"], "hvr")
    h, dn, dv, r = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    w = layer["w_kvb"].reshape(r, h, dn + dv)
    return (w[..., :dn], "rhn"), (w[..., dn:], "rhv")
