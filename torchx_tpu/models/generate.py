"""KV-cache autoregressive generation for the Llama family.

The inference half of the model stack: prefill runs the stacked-layer scan
once over the prompt while collecting per-layer K/V; decode steps then
attend a single query token against the cache (O(seq) per token instead of
O(seq²) re-forwarding). Everything is ``lax.scan``/``dynamic_update_slice``
— static shapes, one compile for any prompt length up to ``max_seq``.

Greedy decoding is exactly argmax-teacher-forcing (tested against the full
forward), temperature>0 samples from the softmax.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchx_tpu.models import eva, gdn, hyper, llama, mla, ssm
from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced, project_heads as _project_heads
from torchx_tpu.ops.norms import rms_norm
from torchx_tpu.ops.paged_attention import (
    append_kv,
    paged_attention,
    paged_attention_chunk,
    scatter_kv_chunk,
)
from torchx_tpu.ops.quant import maybe_matmul as mm
from torchx_tpu.ops.rope import apply_rope

KVCache = dict[str, jnp.ndarray]  # {"k": [L,b,S,kvh,hd], "v": ...}
# every leaf [L_group, num_blocks, block_size, ...]: {"k", "v"} of [.., kvh, hd],
# or one latent pool a layer group, {group: [.., cache_width]} (init_kv_pools);
# beside them under "ssm", where the layers have a mixer, its store a row a slot (ssm.init_store); under "gdn"
# the store of the linear layers (gdn.init_store), which have no blocks in the pools beside it
KVPools = dict[str, jnp.ndarray]


def init_kv_cache(
    cfg: llama.LlamaConfig, batch: int, max_seq: int
) -> KVCache:
    """Zeroed [layers, batch, max_seq, kv_heads, head_dim] K/V buffers."""
    if cfg.kv_lora_rank or cfg.ssm_heads or cfg.eva_window or cfg.gdn_heads:
        raise NotImplementedError(
            "latent attention, state-space and linear layers and EVA attention are served through the paged path (ServeEngine), not the dense cache"
        )
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": jnp.zeros(shape, dtype=cfg.dtype),
    }


def _cached_attention(
    q: jnp.ndarray,  # [b, t, h, d] (t = tokens this call)
    k_cache: jnp.ndarray,  # [b, S, kvh, d] — positions >= valid_len are zeros
    v_cache: jnp.ndarray,
    q_pos: jnp.ndarray,  # [t] absolute positions of the query tokens
) -> jnp.ndarray:
    b, t, h, d = q.shape
    S = k_cache.shape[1]
    n_rep = h // k_cache.shape[2]
    k = jnp.repeat(k_cache, n_rep, axis=2) if n_rep > 1 else k_cache
    v = jnp.repeat(v_cache, n_rep, axis=2) if n_rep > 1 else v_cache
    logits = (
        jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
        * d**-0.5
    )
    # causal vs absolute cache positions: key position s visible to query at
    # absolute position p iff s <= p
    mask = jnp.arange(S)[None, :] <= q_pos[:, None]  # [t, S]
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _layer_step(
    cfg: llama.LlamaConfig,
    cos: jnp.ndarray,  # [t, hd/2] rope slices for these positions
    sin: jnp.ndarray,
    q_pos: jnp.ndarray,  # [t]
    x: jnp.ndarray,  # [b, t, d]
    layer: llama.Params,
    k_cache: jnp.ndarray,  # [b, S, kvh, hd] this layer's cache
    v_cache: jnp.ndarray,
    start: jnp.ndarray,  # scalar: where these t tokens go in the cache
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    b, t = x.shape[:2]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def attend(stream_in):  # noqa: ANN001, ANN202
        with jax.named_scope(hot.NORM):
            attn_in = rms_norm(stream_in, llama.norm_gain(cfg, layer["attn_norm"]), cfg.norm_eps)
        with jax.named_scope(hot.ATTN):
            attn_in = llama.scaled(attn_in, cfg.attention_in_multiplier)
            q = apply_rope(_project_heads(attn_in, layer["wq"], h, hd), cos, sin)
            k = apply_rope(llama.scaled(_project_heads(attn_in, layer["wk"], kvh, hd), cfg.key_multiplier), cos, sin)
            v = _project_heads(attn_in, layer["wv"], kvh, hd)
            with jax.named_scope(hot.APPEND_KV):
                k_new = jax.lax.dynamic_update_slice(k_cache, k, (0, start, 0, 0))
                v_new = jax.lax.dynamic_update_slice(v_cache, v, (0, start, 0, 0))
            with jax.named_scope(hot.ATTN_KERNEL):
                attn = _cached_attention(q, k_new, v_new, q_pos)
            out = llama.scaled(mm(attn.reshape(b, t, h * hd), layer["wo"]), cfg.attention_out_multiplier)
            return out, (k_new, v_new)

    x, (k_cache, v_cache) = hyper.residual(cfg, layer, "attn", x, attend)
    x, _aux = hyper.residual(cfg, layer, "mlp", x, functools.partial(_feed_forward, cfg, layer))
    return x, k_cache, v_cache


def forward_with_cache(
    params: llama.Params,
    tokens: jnp.ndarray,  # [b, t]
    cache: KVCache,
    start: jnp.ndarray,  # scalar int: absolute position of tokens[:, 0]
    cfg: llama.LlamaConfig,
) -> tuple[jnp.ndarray, KVCache]:
    """-> (logits [b, t, vocab] f32, updated cache). Used for both prefill
    (t = prompt length) and decode (t = 1)."""
    b, t = tokens.shape
    S = cache["k"].shape[2]
    with jax.named_scope(hot.EMBED):
        x = hyper.expand(cfg, llama.scaled(params["embed"][tokens].astype(cfg.dtype), cfg.embedding_multiplier))
    q_pos = start + jnp.arange(t)
    cos_full, sin_full = llama.rope_table(cfg, S)
    cos = jax.lax.dynamic_slice_in_dim(cos_full, start, t, axis=0)
    sin = jax.lax.dynamic_slice_in_dim(sin_full, start, t, axis=0)

    def scan_step(carry, layer_and_cache):  # noqa: ANN001
        x = carry
        layer, k_c, v_c = layer_and_cache
        x, k_c, v_c = _layer_step(cfg, cos, sin, q_pos, x, layer, k_c, v_c, start)
        return x, (k_c, v_c)

    with jax.named_scope(hot.LAYERS):
        x, (k_new, v_new) = jax.lax.scan(
            scan_step, x, (params["layers"], cache["k"], cache["v"])
        )
    x = hyper.collapse(cfg, x)
    with jax.named_scope(hot.NORM):
        x = rms_norm(x, llama.norm_gain(cfg, params["final_norm"]), cfg.norm_eps)
    with jax.named_scope(hot.LM_HEAD):
        head = llama.next_token_head(params, cfg)
        if isinstance(head, dict):  # int8-quantized lm_head: keep f32 accum
            logits = mm(x, head, out_dtype=jnp.float32)
        else:
            logits = jnp.einsum(
                "btd,dv->btv", x, head, preferred_element_type=jnp.float32
            )
    return llama.scaled(logits, cfg.lm_head_multiplier), {"k": k_new, "v": v_new}


@jax.named_scope(hot.SAMPLE)
def _sample(logits_t: jnp.ndarray, key: jax.Array, temperature: float) -> jnp.ndarray:
    """Greedy at temperature 0, else categorical — the ONE sampling rule
    both the batch and streaming paths use (parity depends on it).

    ``key`` may be a single key (one sampling stream for the whole batch —
    the original behavior) or a ``[b, 2]`` stack of per-row keys, which
    draws each row from its own stream so requests with different seeds
    can share one device batch."""
    if temperature <= 0:
        return jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
    if key.ndim == 2:  # per-row keys
        draw = jax.vmap(lambda l, k: jax.random.categorical(k, l / temperature))
        return draw(logits_t, key).astype(jnp.int32)
    return jax.random.categorical(key, logits_t / temperature, axis=-1).astype(
        jnp.int32
    )


def _split_keys(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``jax.random.split`` that also accepts a ``[b, 2]`` stack of per-row
    keys (vmapped split, preserving one independent stream per row)."""
    if key.ndim == 2:
        ks = jax.vmap(jax.random.split)(key)  # [b, 2, 2]
        return ks[:, 0], ks[:, 1]
    k0, k1 = jax.random.split(key)
    return k0, k1


def _prefill(
    params: llama.Params,
    prompt: jnp.ndarray,
    cfg: llama.LlamaConfig,
    total: int,
    rng: jax.Array,
    temperature: float,
) -> tuple[KVCache, jnp.ndarray, jax.Array]:
    """Shared prompt pass: -> (cache, first sampled token, carried rng).
    Consumes a fresh subkey for token 0 and carries the unconsumed key, so
    step 0's draw is independent of step 1's."""
    cache = init_kv_cache(cfg, prompt.shape[0], total)
    logits, cache = forward_with_cache(params, prompt, cache, jnp.int32(0), cfg)
    rng, first_key = _split_keys(rng)
    return cache, _sample(logits[:, -1], first_key, temperature), rng


def generate(
    params: llama.Params,
    prompt: jnp.ndarray,  # [b, t0] int32
    cfg: llama.LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """-> [b, t0 + max_new_tokens]; greedy when temperature == 0.

    Works for dense and MoE configs alike (the cached layer dispatches to
    the GShard expert FFN when the config carries experts). Note MoE
    capacity is computed per call width, so aggressive ``capacity_factor``
    settings can drop different tokens at prefill vs full forward.

    ``rng`` may be a single PRNG key (one sampling stream shared by the
    batch) or a ``[b, 2]`` stack of per-row keys, giving every row its own
    stream — this is how requests with different seeds coalesce into one
    device batch. Row ``i`` of a stacked call draws the same tokens as a
    single-row call seeded with row ``i``'s key."""
    b, t0 = prompt.shape
    total = t0 + max_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt + new tokens ({total}) exceeds max_seq {cfg.max_seq}"
        )
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    cache, next_tok, rng = _prefill(params, prompt, cfg, total, rng, temperature)

    def sample(logits_t, key):  # noqa: ANN001
        return _sample(logits_t, key, temperature)
    out = jnp.zeros((b, max_new_tokens), dtype=jnp.int32)
    out = out.at[:, 0].set(next_tok)

    def step(carry, i):  # noqa: ANN001
        cache, tok, out, key = carry
        key, sub = _split_keys(key)
        logits, cache = forward_with_cache(
            params, tok[:, None], cache, t0 + i, cfg
        )
        nxt = sample(logits[:, -1], sub)
        # scan runs i in [0, max_new_tokens-2], so i+1 is always in range
        out = out.at[:, i + 1].set(nxt)
        return (cache, nxt, out, key), None

    if max_new_tokens > 1:
        (cache, _, out, _), _ = jax.lax.scan(
            step, (cache, next_tok, out, rng), jnp.arange(max_new_tokens - 1)
        )
    return jnp.concatenate([prompt, out], axis=1)


@functools.lru_cache(maxsize=64)
def _stream_fns(cfg: llama.LlamaConfig, total: int, temperature: float, chunk: int):
    """Jitted (prefill, decode_chunk) pair for one streaming shape — cached
    at module level so repeated streaming requests reuse the compiled
    programs instead of re-tracing per call (jax's own jit cache then
    handles distinct batch sizes under each entry)."""

    @jax.jit
    def prefill(params, prompt, rng):  # noqa: ANN001
        return _prefill(params, prompt, cfg, total, rng, temperature)

    @jax.jit
    def decode_chunk(params, cache, tok, rng, start):  # noqa: ANN001
        # always runs `chunk` steps (static shapes under jit); on the final
        # partial chunk the caller slices off the surplus tokens, whose
        # cache writes are never read again
        def step(carry, i):  # noqa: ANN001
            cache, tok, key = carry
            key, sub = _split_keys(key)
            logits, cache = forward_with_cache(params, tok[:, None], cache, start + i, cfg)
            nxt = _sample(logits[:, -1], sub, temperature)
            return (cache, nxt, key), nxt

        (cache, tok, rng), toks = jax.lax.scan(
            step, (cache, tok, rng), jnp.arange(chunk)
        )
        return cache, tok, rng, toks.swapaxes(0, 1)  # [b, chunk]

    return prefill, decode_chunk


def generate_stream(
    params: llama.Params,
    prompt: jnp.ndarray,  # [b, t0] int32
    cfg: llama.LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    chunk: int = 8,
):
    """Streaming :func:`generate`: yields ``[b, t]`` int32 arrays of NEW
    tokens as they decode (t <= ``chunk``), token-identical to the batch
    path at the same seed (shared ``_sample``/``_prefill``).

    Decode runs in jitted ``chunk``-step segments — one device dispatch +
    one host transfer per chunk; the compiled programs are cached across
    calls (:func:`_stream_fns`). Arguments are validated eagerly (this is
    a generator; callers see errors before any output is produced)."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    b, t0 = prompt.shape
    total = t0 + max_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt + new tokens ({total}) exceeds max_seq {cfg.max_seq}"
        )
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    prefill, decode_chunk = _stream_fns(cfg, total, float(temperature), chunk)

    def run():
        cache, tok, carried = prefill(params, prompt, rng)
        yield jax.device_get(tok)[:, None]
        produced = 1
        state = (cache, tok, carried)
        while produced < max_new_tokens:
            n = min(chunk, max_new_tokens - produced)
            cache, tok, carried, toks = decode_chunk(
                params, *state, jnp.int32(t0 + produced - 1)
            )
            state = (cache, tok, carried)
            yield jax.device_get(toks)[:, :n]
            produced += n

    return run()


# ---------------------------------------------------------------------------
# Paged-KV serving path (continuous batching; see torchx_tpu/serve/)
# ---------------------------------------------------------------------------
#
# Same layer math as the dense path above — rms_norm / rope / ffn / lm_head
# are shared, and the attention softmax masks exactly the positions the
# dense mask admits — but K/V live in a block-table pool
# ([L, num_blocks, block_size, kvh, hd]) instead of per-request
# [L, b, max_seq, ...] buffers, and every slot carries its own position,
# RNG stream, and temperature so unrelated requests share one jitted step.


def layer_group_sizes(cfg: llama.LlamaConfig) -> dict[str, int]:
    """Layers in each group of the parameter tree (``llama.layer_groups``),
    in the order they run."""
    nd = getattr(cfg, "n_dense_layers", 0)
    return {"dense_layers": nd, "layers": cfg.n_layers - nd} if nd else {"layers": cfg.n_layers}


def init_kv_pools(
    cfg: llama.LlamaConfig, num_blocks: int, block_size: int, num_window_blocks: Optional[int] = None, slots: int = 0
) -> KVPools:
    """Zeroed paged pools (block 0 is the trash block — see
    :mod:`torchx_tpu.ops.paged_attention`). Grouped-query attention: K and V,
    ``[layers, num_blocks, block_size, kvh, hd]`` each. Latent attention: one
    pool a layer group under the group's name, ``[group's layers, num_blocks,
    block_size, cache_width]``, a row a token. A stack that mixes sliding and
    full layers (``cfg.layer_types``): a K and a V a cache kind, ``{"full":
    {"k", "v"}, "window": {"k", "v"}}``, each over the layers of its kind in
    the order they run; the ``window`` pools have ``num_window_blocks`` blocks
    of their own, handed out by an allocator of their own, since a slot holds
    there only the blocks its window touches. Every leaf leads with ``[layers,
    blocks, block_size]``: the engine allocates, copies, exports and imports
    blocks over the tree a cache kind at a time. Layers with a state-space mixer
    (``cfg.ssm_heads``) keep its store beside them under ``"ssm"``, ``1 + slots``
    rows a layer (:func:`torchx_tpu.models.ssm.init_store`): addressed by slot,
    not by block, and no part of what is allocated, copied, exported or imported.
    Linear layers (``"linear"`` in ``cfg.layer_types``) have no blocks at all: the
    pools are the attending layers', and the linear layers' store lies beside them
    under ``"gdn"`` (:func:`torchx_tpu.models.gdn.init_store`), ``1 + slots`` rows each."""
    if cfg.ssm_heads:
        return {**kv_pools(cfg, num_blocks, block_size), "ssm": ssm.init_store(cfg, 1 + slots)}
    if cfg.gdn_heads:
        return {**kv_pools(cfg, num_blocks, block_size), "gdn": gdn.init_store(cfg, 1 + slots)}
    return kv_pools(cfg, num_blocks, block_size, num_window_blocks)


def kv_pools(cfg: llama.LlamaConfig, num_blocks: int, block_size: int, num_window_blocks: Optional[int] = None) -> KVPools:
    """:func:`init_kv_pools`' paged pools alone."""
    if cfg.kv_lora_rank:
        return {
            group: jnp.zeros((n, num_blocks, block_size, cfg.cache_width), dtype=cfg.dtype)
            for group, n in layer_group_sizes(cfg).items()
        }

    def kv(layers: int, blocks: int) -> KVPools:
        shape = (layers, blocks, block_size, *cfg.cache_row)
        return {"k": jnp.zeros(shape, dtype=cfg.dtype), "v": jnp.zeros(shape, dtype=cfg.dtype)}

    if not cfg.layer_types:
        return kv(cfg.n_layers, num_blocks)
    blocks = {"full": num_blocks, "window": num_window_blocks or num_blocks}
    return {kind: kv(cfg.layers_of(kind), blocks[kind]) for kind in ("full", "window") if cfg.layers_of(kind)}


def export_blocks(
    pools: KVPools, blocks: jnp.ndarray, kinds: tuple[str, ...] = (), window_blocks: Optional[jnp.ndarray] = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocks ``blocks`` of every layer as the ``(k, v)`` pair a hand-off
    carries (:class:`~torchx_tpu.serve.kv_transfer.KvPayload`): ``[L, n,
    block_size, ...]`` each. Latent pools travel as ``k``, the groups' layers
    one after another as they run, beside a ``v`` of no width. Pools a cache
    kind (``kinds``: ``cfg.cache_kinds``) travel in the order the layers run
    too: a full layer's blocks are ``blocks``, a sliding layer's
    ``window_blocks`` (as many; the trash block where the sender no longer
    holds one, below the window: the receiver does not read those)."""
    if "k" in pools:
        return pools["k"][:, blocks], pools["v"][:, blocks]
    if kinds:
        ids = {"full": blocks, "window": window_blocks}
        at = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]  # a layer's place in its kind's stack
        k, v = (jnp.stack([pools[kind][name][i][ids[kind]] for kind, i in zip(kinds, at)]) for name in ("k", "v"))
        return k, v
    k = jnp.concatenate([pool[:, blocks] for pool in pools.values()])
    return k, jnp.zeros((*k.shape[:3], 0), k.dtype)


def import_blocks(pools: KVPools, blocks: jnp.ndarray, k, v, kinds: tuple[str, ...] = (), window_blocks=None) -> KVPools:  # noqa: ANN001
    """Write a hand-off's ``(k, v)`` (:func:`export_blocks`) into ``blocks``
    (a sliding layer's into ``window_blocks``: the trash block for those below
    the window, which nothing reads)."""
    if "k" in pools:
        new = {"k": k, "v": v}
        return {name: pool.at[:, blocks].set(jnp.asarray(new[name], pool.dtype)) for name, pool in pools.items()}
    if kinds:
        ids, new = {"full": blocks, "window": window_blocks}, {"k": k, "v": v}
        layers = {kind: np.asarray([i for i, of in enumerate(kinds) if of == kind]) for kind in pools}
        return {
            kind: {name: pool.at[:, ids[kind]].set(jnp.asarray(new[name][layers[kind]], pool.dtype)) for name, pool in kv.items()}
            for kind, kv in pools.items()
        }
    out, at = {}, 0
    for name, pool in pools.items():
        out[name] = pool.at[:, blocks].set(jnp.asarray(k[at : at + pool.shape[0]], pool.dtype))
        at += pool.shape[0]
    return out


def _rope_rows(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """:func:`apply_rope` for one token per row at per-row positions:
    ``x`` [rows, heads, hd], ``cos``/``sin`` [rows, hd/2] (same float32
    rotation, so paged decode matches the dense path bit-for-bit)."""
    dtype = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[:, None, :]
    s = sin[:, None, :]
    return jnp.concatenate((x1 * c - x2 * s, x2 * c + x1 * s), axis=-1).astype(dtype)


@jax.named_scope(hot.SAMPLE)
def _sample_rows(
    logits: jnp.ndarray,  # [rows, vocab]
    keys: jnp.ndarray,  # [rows, 2] per-row PRNG keys
    temps: jnp.ndarray,  # [rows] — <= 0 means greedy for that row
) -> jnp.ndarray:
    """Per-row :func:`_sample` where temperature is data, not static: each
    row greedy-decodes or draws from its own stream at its own temperature
    (a continuous batch mixes requests with different sampling params)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
    draw = jax.vmap(lambda l, k: jax.random.categorical(k, l))
    sampled = draw(logits / safe_t, keys).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _table_of(tables, layer: llama.Params):  # noqa: ANN001, ANN202
    """The block table of ``layer``'s cache kind: one array serves a stack of
    one kind, ``{"full": .., "window": ..}`` a stack that mixes them. Where the
    layers have a state-space mixer the rows' state rows ride beside the one
    table, ``{"full": .., "state": [rows] int32}`` (:func:`_state_rows`; what a
    linear layer, whose cache kind is ``"state"``, gets as its table), and
    under EVA attention the rows' staging blocks, ``{"full": .., "stage": [rows,
    W / C / block_size]}`` (:meth:`_Rows.pool`)."""
    return tables[layer["attn_kind"]] if isinstance(tables, dict) else tables


def _state_rows(tables) -> Optional[jnp.ndarray]:  # noqa: ANN001
    """The row of the mixer's store each row of ``tables`` reads and writes (a
    slot's own, or the trash row 0): None for a model without a mixer."""
    return tables.get("state") if isinstance(tables, dict) else None


def _feed_forward(cfg: llama.LlamaConfig, layer: llama.Params, stream_in: jnp.ndarray):  # noqa: ANN202
    """The FFN sublayer of a cached or paged step, its norm included: the SAME
    dispatch as the training forward (dense SwiGLU or GShard MoE: static
    shapes hold at t=1); the balancing aux is training-only."""
    with jax.named_scope(hot.NORM):
        mlp_in = rms_norm(stream_in, llama.norm_gain(cfg, layer["mlp_norm"]), cfg.norm_eps)
    return llama.ffn(cfg, layer, mlp_in)


class _Rows(NamedTuple):
    """One part of the rows a paged step runs through the layer stack side by
    side: a decode step's rows, one query a slot at ``positions [slots]``
    (``valid`` None), or a chunk of consecutive prompt tokens a row of
    ``tables``, ``positions [b, t]`` flattened to ``b * t`` rows, ``valid [b, t]``
    saying which are real. The parts differ in how their rows reach the cache
    and attend it, and in nothing else. What is roped is a row's position in its
    sequence; what is written and read is its **cache index**, the same number
    but under EVA attention (``sequence_at``)."""

    cos: jnp.ndarray  # [rows, rope/2] rope rows at each row's position
    sin: jnp.ndarray
    positions: jnp.ndarray  # the cache index each row's token is written to, and as far as it reads
    tables: Any  # [slots or b, blocks_per_slot] int32 block tables, or one a cache kind (_table_of)
    valid: Optional[jnp.ndarray] = None
    #: the rows' positions in their sequences, shaped as ``positions``, where those are cache
    #: coordinates and not the positions themselves (eva.cache_coord); None: they are
    sequence_at: Optional[jnp.ndarray] = None

    @property
    def rows(self) -> int:
        return self.cos.shape[0]

    def cache(self, pool, tables, new, at, ring):  # noqa: ANN001, ANN201
        """``new [rows, ...]`` into ``pool`` at the rows' positions."""
        if self.valid is None:
            return append_kv(pool, tables, self.positions, new, at, ring=ring)
        return scatter_kv_chunk(pool, tables, self.positions, self._chunked(new), self.valid, at)

    def _chunked(self, x: jnp.ndarray) -> jnp.ndarray:
        return x.reshape(*self.positions.shape, *x.shape[1:])  # [rows, ...] -> [b, t, ...]

    def attend(self, q, k_pool, v_pool, tables, at, window):  # noqa: ANN001, ANN201
        """``q [rows, h, hd]`` over the K/V pools, the rows' own K/V already
        in them -> ``[rows, h, hd]``."""
        if self.valid is None:
            return paged_attention(q, k_pool, v_pool, tables, self.positions + 1, at, window)
        out = paged_attention_chunk(self._chunked(q), k_pool, v_pool, tables, self.positions, self.valid, at, window)
        return out.reshape(self.rows, *out.shape[2:])

    def pool(self, cfg, layer, k_pool, v_pool, table, at):  # noqa: ANN001, ANN201
        """EVA attention: the chunks these rows completed, read out of the blocks
        just written and pooled into their sequences' staging blocks: a decode
        row's where its position ends a chunk, a prompt chunk's every whole one
        (the chunk starts on a chunk's first position: the engine's chunks are
        whole blocks and a block is a chunk)."""
        c = cfg.eva_chunk
        if self.valid is None:
            ends, full = self.sequence_at[:, None], (self.sequence_at % c == c - 1)[:, None]
        else:
            ends, full = self.sequence_at[:, c - 1 :: c], self.valid[:, c - 1 :: c]
        return eva.pool_filled(cfg, layer, k_pool, v_pool, at, ends, full, table, self.tables["stage"])

    def attend_latent(self, cfg, layer, q_nope, q_rope, tables, pool):  # noqa: ANN001, ANN201
        """The same over a latent pool -> ``[rows, h, v]``."""
        if self.valid is None:
            return mla.attend_decode(cfg, layer, q_nope, q_rope, self.positions, tables, pool)
        out = mla.attend_chunk(
            cfg, layer, self._chunked(q_nope), self._chunked(q_rope), self.positions, self.valid, tables, pool
        )
        return out.reshape(self.rows, *out.shape[2:])

    def mix(self, cfg, layer, xbc, dt, store, at):  # noqa: ANN001, ANN201
        """The rows through the layer's mixer between its projections
        (``ssm.project``'s ``xbc`` and ``dt [rows, ...]``), each row from and to
        its state in ``store`` -> ``(y [rows, H P], store)``. A chunk that starts
        its sequence (position 0) starts from zeros: nothing else resets a row.
        The rows of the store ride beside the block tables (:func:`_state_rows`):
        a decode part's one a slot, a chunk part's one a sequence."""
        y, store = self._through(ssm, cfg, layer, (xbc, dt), store, at)
        return (y if self.valid is None else y.reshape(self.rows, -1)), store

    def mix_linear(self, cfg, layer, qkv, b, a, store, at):  # noqa: ANN001, ANN201
        """The same through a linear layer's Gated DeltaNet mixer (``gdn.project``'s
        ``qkv``, ``b`` and ``a [rows, ...]``) -> ``(o [rows, H, D], store)``."""
        o, store = self._through(gdn, cfg, layer, (qkv, b, a), store, at)
        return (o if self.valid is None else o.reshape(self.rows, *o.shape[-2:])), store

    def _through(self, mixer, cfg, layer, inputs, store, at):  # noqa: ANN001, ANN202
        state_rows = _state_rows(self.tables)
        if self.valid is None:
            return mixer.decode_rows(cfg, layer, *inputs, store, at, state_rows)
        fresh = self.positions[:, 0] == 0
        return mixer.chunk_rows(cfg, layer, *(self._chunked(x) for x in inputs), store, at, state_rows, fresh, self.valid)


def _cat(xs: list[jnp.ndarray]) -> jnp.ndarray:
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs)


def _paged_layer_step(
    cfg: llama.LlamaConfig,
    parts: tuple[_Rows, ...],
    cos: jnp.ndarray,  # [rows, rope/2]: the parts' rope rows one after another, joined once ahead of the layer loop
    sin: jnp.ndarray,
    x: jnp.ndarray,  # [rows, 1, d]: the parts' rows in the same order
    layer: llama.Params,
    # this layer's pool [num_blocks, bs, kvh, hd], or with layer["kind_index"]
    # (under _scan_groups) the stack of its cache kind [layers, num_blocks, ...]
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    store: Optional[ssm.Store],  # the mixer's store whole (read and written at layer["kind_index"]); None without a mixer
    # (a linear layer, whose mixer stands in attention's place, comes with its store and no pools)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Optional[ssm.Store]]:
    """One layer over every part's rows at once: norms, projections, the
    output projection, the feed-forward (every row a group of its own: a
    capacity of one a group and expert never drops a routing) and the residual
    mix run once over all rows, so a chunk that rides a decode step reads no
    weight a second time. Between the projections each part writes its rows to
    the pools and attends them its own way (:class:`_Rows`): all writes, then
    all reads, the parts' blocks being disjoint but for the trash block. A
    state-space mixer runs beside attention off the same norm and into the same
    residual add, each part's rows from and to their own rows of ``store``, the
    parts' rows being disjoint but for the trash row: a row has one writer. A
    linear layer (``layer["attn_kind"] == "state"``) is the one branch: its Gated
    DeltaNet mixer alone, from and to the store, and no pool is touched."""
    window = llama.window_of(cfg, layer)
    bounds = np.cumsum([0] + [part.rows for part in parts])
    split = lambda a: [a[lo:hi] for lo, hi in zip(bounds, bounds[1:])] if len(parts) > 1 else [a]  # noqa: E731
    tables = [_table_of(part.tables, layer) for part in parts]

    def normed(stream_in):  # noqa: ANN001, ANN202 - what the layer's mixer reads
        with jax.named_scope(hot.NORM):
            return rms_norm(stream_in, llama.norm_gain(cfg, layer["attn_norm"]), cfg.norm_eps)

    def mix_linear(stream_in):  # noqa: ANN001, ANN202
        attn_in = normed(stream_in)
        with jax.named_scope(hot.GDN):
            at = layer.get("kind_index")
            qkv, z, b, a = gdn.project(cfg, layer, attn_in[:, 0])
            new_store, read_out = store, []
            for part, qkv_rows, b_rows, a_rows in zip(parts, split(qkv), split(b), split(a)):
                o, new_store = part.mix_linear(cfg, layer, qkv_rows, b_rows, a_rows, new_store, at)
                read_out.append(o)
            mixed = gdn.finish(cfg, layer, _cat(read_out), z)
        return mixed[:, None, :], (k_pool, v_pool, new_store)

    def attend(stream_in):  # noqa: ANN001, ANN202
        attn_in = normed(stream_in)
        with jax.named_scope(hot.ATTN), hot.attn_kind_scope(cfg, layer):
            rows = attn_in[:, 0]  # [rows, d]
            if cfg.kv_lora_rank:  # one latent pool, handed through as k_pool
                at = layer.get("layer_index")
                q_nope, q_rope, cached = mla.project(cfg, layer, rows, cos, sin)
                pool = k_pool
                with jax.named_scope(hot.APPEND_LATENT):
                    for part, table, new in zip(parts, tables, split(cached)):
                        pool = part.cache(pool, table, new, at, False)
                out = _cat([
                    part.attend_latent(cfg, layer, qn, qr, table, pool)
                    for part, table, qn, qr in zip(parts, tables, split(q_nope), split(q_rope))
                ])  # fmt: skip
                pools = (pool, v_pool)
            else:
                at = layer.get("kind_index")
                h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
                scaled_rows = llama.scaled(rows, cfg.attention_in_multiplier)
                q, gate = llama.split_gate(cfg, _project_heads(scaled_rows, layer["wq"], h, cfg.query_width))
                k = _project_heads(scaled_rows, layer["wk"], kvh, hd)
                q, k = llama.norm_and_rotate(cfg, layer, q, k, cos, sin, _rope_rows)
                v = _project_heads(scaled_rows, layer["wv"], kvh, hd)
                if cfg.cache_row != (kvh, hd):  # a position's heads as the rows they lie as in the pool
                    k, v = k.reshape(-1, *cfg.cache_row), v.reshape(-1, *cfg.cache_row)
                k_new, v_new = k_pool, v_pool
                for part, table, k_rows, v_rows in zip(parts, tables, split(k), split(v)):
                    k_new = part.cache(k_new, table, k_rows, at, bool(window))
                    v_new = part.cache(v_new, table, v_rows, at, bool(window))
                if cfg.eva_window:  # into staging blocks that no read of this step touches
                    for part, table in zip(parts, tables):
                        k_new, v_new = part.pool(cfg, layer, k_new, v_new, table, at)
                out = _cat([
                    part.attend(q_rows, k_new, v_new, table, at, window)
                    for part, table, q_rows in zip(parts, tables, split(q))
                ])  # fmt: skip
                out = llama.gate_heads(out, gate)
                pools = (k_new, v_new)
            out = llama.scaled(mm(out.reshape(out.shape[0], 1, -1), layer["wo"]), cfg.attention_out_multiplier)
        if not cfg.ssm_heads:
            return out, (*pools, store)
        with jax.named_scope(hot.SSM):
            z, xbc, dt = ssm.project(cfg, layer, rows)
            new_store, read_out = store, []
            for part, xbc_rows, dt_rows in zip(parts, split(xbc), split(dt)):
                y, new_store = part.mix(cfg, layer, xbc_rows, dt_rows, new_store, at)
                read_out.append(y)
            mixed = ssm.finish(cfg, layer, _cat(read_out), z)
        return out + mixed[:, None, :], (*pools, new_store)

    mixer = mix_linear if layer.get("attn_kind") == "state" else attend
    x, (k_pool, v_pool, store) = hyper.residual(cfg, layer, "attn", x, mixer)
    x, _aux = hyper.residual(cfg, layer, "mlp", x, functools.partial(_feed_forward, cfg, layer))
    return x, k_pool, v_pool, store


def _scan_groups(step, x, params: llama.Params, pools: KVPools, cfg: llama.LlamaConfig):  # noqa: ANN001, ANN202
    """Run ``step(x, layer, k_pool, v_pool, store) -> (x, k_pool, v_pool, store)`` over every
    layer in the order the layers run, a group of the parameter tree at a time
    (``llama.scan_layers``: one scan a group of equal layers, a scan over whole
    periods where attention kinds alternate). The pools the group's layers
    touch ride the scan's carry whole, beside ``x``: ``k_pool`` and ``v_pool``
    are the stacks of the layer's cache kind ``[layers of that kind,
    num_blocks, ...]``, and the step writes and reads them at
    ``layer["kind_index"]`` where they lie, so nothing the size of a layer's
    pool is sliced out, copied or stacked back. A latent pool is its group's
    one array (read at ``layer["layer_index"]``) and goes through as ``k_pool``
    with no ``v_pool``. A mixer's store (``pools["ssm"]``, or the linear layers'
    ``pools["gdn"]``, else None) rides the carry whole beside them; a linear layer
    is handed the store and no pool. ``ops.attention.traced("kv_pools")`` answers ``carried``."""
    note_traced("kv_pools", "carried")
    latent, mixed = bool(cfg.kv_lora_rank), bool(cfg.layer_types)
    stored = next((name for name in ("ssm", "gdn") if name in pools), None)
    store = pools.get(stored)
    first = 0
    for group in llama.layer_groups(params):
        n = jax.tree.leaves(params[group])[0].shape[0]
        if latent:
            held = {group: (pools[group], None)}
        elif mixed:
            held = {kind: (pools[kind]["k"], pools[kind]["v"]) for kind in sorted(set(cfg.cache_kinds[first : first + n]) - {"state"})}
        else:
            held = {"full": (pools["k"], pools["v"])}

        def scan_step(carry, layer, group=group):  # noqa: ANN001
            x, held, store = carry
            key = group if latent else layer["attn_kind"]
            if key == "state":  # a linear layer: the store alone
                x, _, _, store = step(x, layer, None, None, store)
                return (x, held, store), None
            x, k_pool, v_pool, store = step(x, layer, *held[key], store)
            return (x, {**held, key: (k_pool, v_pool)}, store), None

        (x, held, store), _ = llama.scan_layers(cfg, scan_step, (x, held, store), params[group], first, params.get("mixers"))
        if latent:
            pools = {**pools, group: held[group][0]}
        elif mixed:
            pools = {**pools, **{kind: {"k": k, "v": v} for kind, (k, v) in held.items()}}
        else:
            pools = {**pools, **dict(zip(("k", "v"), held["full"]))}
        first += n
    return x, pools if store is None else {**pools, stored: store}


@jax.named_scope(hot.LM_HEAD)
def _lm_head_rows(params: llama.Params, x: jnp.ndarray, cfg: llama.LlamaConfig):
    # [rows, d] -> [rows, vocab] f32, same head dispatch as forward_with_cache
    head = llama.next_token_head(params, cfg)
    if isinstance(head, dict):  # int8-quantized lm_head: keep f32 accum
        return llama.scaled(mm(x, head, out_dtype=jnp.float32), cfg.lm_head_multiplier)
    return llama.scaled(jnp.einsum("rd,dv->rv", x, head, preferred_element_type=jnp.float32), cfg.lm_head_multiplier)


def _decode_rows(cfg: llama.LlamaConfig, positions: jnp.ndarray, tables) -> _Rows:  # noqa: ANN001
    cos, sin = llama.rope_table(cfg, cfg.max_seq)
    if cfg.eva_window:
        return _Rows(cos[positions], sin[positions], eva.cache_coord(cfg, positions), tables, sequence_at=positions)
    return _Rows(cos[positions], sin[positions], positions, tables)


def _chunk_rows(cfg: llama.LlamaConfig, prefix_lens: jnp.ndarray, suffix_lens: jnp.ndarray, t: int, tables) -> _Rows:  # noqa: ANN001
    """``t`` positions a row from ``prefix_lens [b]`` on, the first
    ``suffix_lens [b]`` of them real."""
    cos, sin = llama.rope_table(cfg, cfg.max_seq)
    positions = prefix_lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    pos_safe = jnp.clip(positions, 0, cfg.max_seq - 1).reshape(-1)  # padding may lie past the table
    valid = jnp.arange(t)[None, :] < suffix_lens[:, None]
    if cfg.eva_window:  # the real positions lie in one window (the engine cuts a chunk where a window ends)
        return _Rows(cos[pos_safe], sin[pos_safe], eva.cache_coord(cfg, positions), tables, valid, positions)
    return _Rows(cos[pos_safe], sin[pos_safe], positions, tables, valid)


def _paged_stream(params: llama.Params, tokens: jnp.ndarray, parts: tuple[_Rows, ...], pools: KVPools, cfg: llama.LlamaConfig):  # noqa: ANN202
    """``tokens [rows]``, the parts' one after another, through the layer
    stack -> (the stream ahead of the final norm ``[rows, d]``, the pools)."""
    with jax.named_scope(hot.EMBED):
        x = llama.scaled(params["embed"][tokens].astype(cfg.dtype), cfg.embedding_multiplier)
        x = hyper.expand(cfg, x[:, None, :])  # [rows, 1, d]
    cos, sin = _cat([part.cos for part in parts]), _cat([part.sin for part in parts])
    x, pools = _scan_groups(functools.partial(_paged_layer_step, cfg, parts, cos, sin), x, params, pools, cfg)
    return hyper.collapse(cfg, x)[:, 0, :], pools


def _head_rows(params: llama.Params, x: jnp.ndarray, cfg: llama.LlamaConfig, keys: jnp.ndarray, temps: jnp.ndarray) -> jnp.ndarray:
    """The stream at ``x [rows, d]`` -> a sampled token a row."""
    with jax.named_scope(hot.NORM):
        x = rms_norm(x, llama.norm_gain(cfg, params["final_norm"]), cfg.norm_eps)
    return _sample_rows(_lm_head_rows(params, x, cfg), keys, temps)


def paged_decode_step(
    params: llama.Params,
    tokens: jnp.ndarray,  # [slots] int32 — last sampled token per slot
    positions: jnp.ndarray,  # [slots] int32 — where each token's K/V goes
    tables,  # noqa: ANN001 — [slots, blocks_per_slot] int32 block tables; {"full": .., "window": [slots, ring]} where kinds mix
    pools: KVPools,
    cfg: llama.LlamaConfig,
    keys: jnp.ndarray,  # [slots, 2] per-slot PRNG keys for THIS position
    temps: jnp.ndarray,  # [slots] f32 — <= 0 greedy
) -> tuple[jnp.ndarray, KVPools]:
    """One continuous-batching decode step over the whole slot array.

    -> (next token [slots], updated pools). Every slot advances one token
    against its own block table at its own position; inactive slots
    (table all trash, position 0) compute garbage that lands in the trash
    block and is never read. Static shapes: one XLA compile per
    (slots, pool geometry), regardless of which requests occupy the slots.
    Jit with ``donate_argnums`` on ``pools`` so the pool updates in place: the
    pools ride the layer scan's carry whole (``_scan_groups``), each layer
    scatters its ``slots`` rows into the stack at its own index and the
    attention kernel reads the stack at that index, so a step moves the rows it
    appends and the blocks the slots hold, never a layer's pool. Nor a layer's
    weights: every matmul takes its layer's slice of the parameter stack inside
    its own fusion, the attention projections included (``_project_heads``).
    """
    x, pools = _paged_stream(params, tokens, (_decode_rows(cfg, positions, tables),), pools, cfg)
    return _head_rows(params, x, cfg, keys, temps), pools


def paged_decode_chunk_step(
    params: llama.Params,
    tokens: jnp.ndarray,  # [slots] int32, as in paged_decode_step
    positions: jnp.ndarray,  # [slots]
    tables,  # noqa: ANN001 — the slots' block tables
    chunk_tokens: jnp.ndarray,  # [width] int32: the next tokens of ONE prompt, right-padded
    chunk_start: jnp.ndarray,  # scalar int32: tokens of that prompt already in the pool
    chunk_len: jnp.ndarray,  # scalar int32: real tokens in the chunk (>= 1)
    chunk_tables,  # noqa: ANN001 — [1, blocks_per_slot] that request's table, block b at entry b; one a cache kind where kinds mix
    pools: KVPools,
    cfg: llama.LlamaConfig,
    keys: jnp.ndarray,  # [slots + 1, 2]: the slots' keys, then the chunk's last position's
    temps: jnp.ndarray,  # [slots + 1]
) -> tuple[jnp.ndarray, KVPools]:
    """A decode step that carries a chunk of a prompt: :func:`paged_decode_step`
    over the slots and :func:`paged_prefill_chunk` over one row of ``width``
    positions, in one pass over the layer stack.

    The slots' rows go through the decode attention and the chunk's through
    the chunk attention (cached prefix + this chunk through the request's own
    table: ``chunk_start`` is what a prefix hit's ``prefix_lens`` is), both
    writing the pools the scan carries; everything else of a layer (norms,
    projections, experts or MLP, the residual mix) runs once over ``slots +
    width`` rows, so the chunk pays no weight read of its own. The slot that
    holds the request whose prompt is being fed must not decode (its table row
    all trash), the chunk's blocks belong to nobody else. The head runs over
    ``slots + 1`` rows. -> (sampled ``[slots + 1]``: every slot's next token,
    then the token after the chunk's last real position, which is the request's
    first where the chunk ends its prompt; updated pools)."""
    slots, width = tokens.shape[0], chunk_tokens.shape[0]
    parts = (
        _decode_rows(cfg, positions, tables),
        _chunk_rows(cfg, chunk_start[None], chunk_len[None], width, chunk_tables),
    )
    x, pools = _paged_stream(params, jnp.concatenate((tokens, chunk_tokens)), parts, pools, cfg)
    last = jax.lax.dynamic_slice_in_dim(x, slots + chunk_len - 1, 1)
    return _head_rows(params, jnp.concatenate((x[:slots], last)), cfg, keys, temps), pools


def paged_prefill_chunk(
    params: llama.Params,
    tokens: jnp.ndarray,  # [b, t] int32 suffix tokens, right-padded
    prefix_lens: jnp.ndarray,  # [b] int32 — cached tokens already in the pool
    suffix_lens: jnp.ndarray,  # [b] int32 — real suffix lengths (>= 1)
    tables,  # noqa: ANN001 — [b, blocks_per_slot] full per-row block tables, one a cache kind where kinds mix
    pools: KVPools,
    cfg: llama.LlamaConfig,
    keys: jnp.ndarray,  # [b, 2] per-row PRNG keys for the first token
    temps: jnp.ndarray,  # [b] f32
) -> tuple[jnp.ndarray, KVPools]:
    """Prefill only the *uncached suffix* of each prompt against the pool.

    The prefix-cache fast path: row ``i``'s first ``prefix_lens[i]``
    tokens already sit in cached blocks referenced by ``tables[i]``; this
    computes K/V for the suffix chunk, scatters it into the row's freshly
    allocated blocks, and attends each suffix token causally over cached
    prefix + chunk through the same block tables. With ``prefix_lens = 0``
    it is a cold paged prefill, so cached and cold requests run the exact
    same program — reused prefix blocks hold bit-identical K/V to what
    the cold path would recompute, keeping decode parity exact. The engine
    feeds prompts through :func:`paged_decode_chunk_step`; this program has
    no caller there and stays as that step's reference in the tests.

    ``t`` is the suffix bucket width; samples the first output token from
    the logits at each row's last real suffix position.
    -> (first token [b], updated pools).
    """
    b, t = tokens.shape
    parts = (_chunk_rows(cfg, prefix_lens, suffix_lens, t, tables),)
    x, pools = _paged_stream(params, tokens.reshape(b * t), parts, pools, cfg)
    last = x.reshape(b, t, -1)[jnp.arange(b), suffix_lens - 1]  # [b, d]
    return _head_rows(params, last, cfg, keys, temps), pools
