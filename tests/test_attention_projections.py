"""The serving programs' q/k/v projections leave their matmuls as ``[rows, heads * hd]``
and are split into heads after (``generate._project_heads``), so that no layer's weight is
re-laid in front of a small-batch matmul. Two things are held here, on the CPU in float32:

(1) what a head is: head ``j`` of a projection is the product with columns ``[j * hd,
(j + 1) * hd)`` of the weight, whatever the leading shape of the activation;

(2) that the programs still compute the model: ``paged_prefill_chunk`` (cold, and behind a
cached prefix) and ``paged_decode_step`` give, token for token, what the uncached forward
(``llama.forward``, whose projections this PR does not touch) gives when it is re-run over
the whole sequence, at shapes where a wrong split cannot pass by luck: ``heads * hd !=
dim`` (``attn_head_dim``), groups of 1, 2, 3 and 8 query heads a K/V head, QK-norm with
gains that are not 1, and a stack of sliding and full layers with the rotary embedding
off on the full ones. The compiled programs are held to ``obs.hlo.program_moves`` in
``tests/test_paged_attention_kernel.py``.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama
from torchx_tpu.ops.paged_attention import TRASH_BLOCK

attn_ops = importlib.import_module("torchx_tpu.ops.attention")

BS, MAX_SEQ, VOCAB = 8, 64, 97
PER_SLOT = MAX_SEQ // BS


@pytest.mark.parametrize("lead", [(5,), (2, 7)], ids=["decode-rows", "prefill-chunk"])
@pytest.mark.parametrize("d,heads,hd", [(48, 4, 12), (48, 6, 16), (40, 1, 24)], ids=["square", "wider", "one-head"])
def test_a_head_is_a_column_block_of_the_product(lead, d, heads, hd, monkeypatch):
    monkeypatch.setattr(attn_ops, "TRACED", {})
    x = jax.random.normal(jax.random.PRNGKey(0), (*lead, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, heads * hd))
    got = jax.jit(lambda x, w: gen._project_heads(x, w, heads, hd))(x, w)
    assert got.shape == (*lead, heads, hd)
    assert attn_ops.traced("projections") == "in_place"
    for j in range(heads):
        want = np.asarray(x) @ np.asarray(w)[:, j * hd : (j + 1) * hd]
        np.testing.assert_allclose(np.asarray(got[..., j, :]), want, rtol=1e-5, atol=1e-5)


CASES = {
    # 4 heads of 32 over a model of 64: the products are twice as wide as the model
    "head-dim-not-dim-over-heads": dict(n_heads=4, n_kv_heads=2, attn_head_dim=32),
    "every-head-its-own-kv": dict(n_heads=4, n_kv_heads=4),
    "groups-of-three": dict(dim=96, n_heads=6, n_kv_heads=2),
    "groups-of-eight": dict(n_heads=8, n_kv_heads=1, attn_head_dim=16),
    "qk-norm": dict(n_heads=4, n_kv_heads=2, attn_head_dim=24, qk_norm=True),
    "sliding-and-full-layers-rotary-on-sliding-only": dict(
        n_layers=4, n_heads=4, n_kv_heads=2, attn_head_dim=24, qk_norm=True, rope_full_layers=False,
        layer_types=("sliding", "sliding", "sliding", "full"), sliding_window=12),
}  # fmt: skip


def _model(case):
    cfg = llama.llama_tiny(vocab_size=VOCAB, max_seq=MAX_SEQ, **CASES[case])
    assert cfg.n_heads * cfg.head_dim != cfg.dim or cfg.n_heads // cfg.n_kv_heads != 4  # not Mistral's luck
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    if cfg.qk_norm:  # gains that are not 1, so that a gain applied across the wrong axis shows
        for name, seed in (("q_norm", 1), ("k_norm", 2)):
            w = params["layers"][name]
            params["layers"][name] = w + 0.3 * jax.random.normal(jax.random.PRNGKey(seed), w.shape)
    return cfg, params


def _next_token(cfg, params, seq):
    """The uncached forward over the whole sequence: the token it would emit next."""
    logits = llama.forward(params, jnp.asarray([seq], jnp.int32), cfg)
    return int(jnp.argmax(logits[0, -1]))


def _tables(cfg, rows):
    """Row ``i`` owns blocks ``1 + i * PER_SLOT ...``; where kinds mix, a table a cache kind
    (the sliding layers' is as wide as the full one here, so its ring never wraps and the
    window alone decides what a sliding layer sees)."""
    table = np.full((rows, PER_SLOT), TRASH_BLOCK, np.int32)
    for i in range(rows):
        table[i] = 1 + i * PER_SLOT + np.arange(PER_SLOT)
    table = jnp.asarray(table)
    return {"full": table, "window": table} if cfg.layer_types else table


def _prefill(cfg, params, pools, rows, prefix_lens=None):
    """One round over ``rows`` (lists of tokens, each behind ``prefix_lens[i]`` tokens that
    are in the pools already) -> (first tokens, pools)."""
    n = len(rows)
    width = max(BS, 1 << (max(map(len, rows)) - 1).bit_length())
    toks = np.zeros((n, width), np.int32)
    for i, r in enumerate(rows):
        toks[i, : len(r)] = r
    pre = jnp.asarray(prefix_lens if prefix_lens is not None else [0] * n, jnp.int32)
    first, pools = gen.paged_prefill_chunk(
        params, jnp.asarray(toks), pre, jnp.asarray([len(r) for r in rows], jnp.int32), _tables(cfg, n), pools, cfg,
        jnp.zeros((n, 2), jnp.uint32), jnp.zeros((n,), jnp.float32),
    )  # fmt: skip
    return [int(t) for t in first], pools


def _prompts(n):
    rng = np.random.default_rng(5)
    return [list(map(int, rng.integers(1, VOCAB, size=k))) for k in (13, 22, 9)[:n]]


@pytest.mark.parametrize("case", list(CASES))
def test_a_prefill_round_gives_the_uncached_forwards_token(case):
    """Cold, two rows of different lengths in one round; then each row again as a cached
    prefix of one block and the rest behind it (the program a prefix hit runs)."""
    cfg, params = _model(case)
    prompts = _prompts(2)
    want = [_next_token(cfg, params, p) for p in prompts]
    pools = gen.init_kv_pools(cfg, 1 + 2 * PER_SLOT, BS)
    first, _ = _prefill(cfg, params, pools, prompts)
    assert first == want
    _, pools = _prefill(cfg, params, pools, [p[:BS] for p in prompts])
    first, _ = _prefill(cfg, params, pools, [p[BS:] for p in prompts], prefix_lens=[BS, BS])
    assert first == want


@pytest.mark.parametrize("case", list(CASES))
def test_decode_steps_give_the_uncached_forwards_tokens(case):
    """Three slots at different positions (the third inactive), 16 steps: past the window
    of the sliding layers and over a block boundary."""
    cfg, params = _model(case)
    seqs = _prompts(2)
    pools = gen.init_kv_pools(cfg, 1 + 3 * PER_SLOT, BS)
    first, pools = _prefill(cfg, params, pools, seqs)
    tables = _tables(cfg, 3)
    trash = jnp.full((PER_SLOT,), TRASH_BLOCK, jnp.int32)
    tables = jax.tree.map(lambda t: t.at[2].set(trash), tables)  # slot 2 holds nothing
    step = jax.jit(lambda tok, pos, pl: gen.paged_decode_step(
        params, tok, pos, tables, pl, cfg, jnp.zeros((3, 2), jnp.uint32), jnp.zeros((3,), jnp.float32)))  # fmt: skip
    emitted = []
    for i, t in enumerate(first):
        assert t == _next_token(cfg, params, seqs[i])
        seqs[i] = seqs[i] + [t]
    for _ in range(16):
        tok = jnp.asarray([seqs[0][-1], seqs[1][-1], 0], jnp.int32)
        pos = jnp.asarray([len(seqs[0]) - 1, len(seqs[1]) - 1, 0], jnp.int32)
        want = [_next_token(cfg, params, s) for s in seqs]
        nxt, pools = step(tok, pos, pools)
        assert [int(nxt[0]), int(nxt[1])] == want
        for i in range(2):
            seqs[i] = seqs[i] + [want[i]]
        emitted.append(tuple(want))
    assert len(set(emitted)) > 2  # the tokens move: not a constant answer
