"""Sliding and full attention layers mixed in one stack, QK-norm, rotary embedding on
sliding layers only, and a chip's share of a layer's experts, against the benchmark's
plain reference (``benchmark/reference/exaone_moe.py``: float32 at ``precision=HIGHEST``,
plain masks, every given expert on every token), on the CPU at tiny widths with seeded
weights: two periods ``L L L G``, layer 0 dense, 4 of 16 experts held (ids 4-7) behind
the 16-wide top-3 sigmoid router, a head width (32) that is not ``dim / n_heads`` (16).

Tolerances. Program and reference both compute in float32 here and differ in the order
of their sums (sorted rows against masked experts, a running softmax against a plain
one, a fused norm): logits of size ~4 agree to ``2e-4 + 2e-4 |x|``; two attention
functions over one pool to ``2e-5`` (float32 pools, ``precision=HIGHEST`` in the
kernel). A reference in bfloat16, or one wrong term (a full layer rotated, a window one
key wider, no QK-norm, an absent expert's routing computed by a held one), moves logits
by 1e-2 or more: `test_a_wrong_layer_is_caught` holds the comparison to that.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import models
from benchmark.reference import exaone_moe as ref
from benchmark.reference import model as ref_model
from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama, moe
from torchx_tpu.ops import paged_attention as pa
from torchx_tpu.ops import paged_attention_kernel as pak
from torchx_tpu.serve import kv_pool
from torchx_tpu.serve.engine import ServeEngine, ServeRequest
from torchx_tpu.serve.prefix_cache import PrefixCache

attn_ops = importlib.import_module("torchx_tpu.ops.attention")
LOGITS = dict(atol=2e-4, rtol=2e-4)
WINDOW, BS = 20, 8  # a window of two and a half blocks: a ring of 5, three blocks back for a prefix hit

CONFIG = {  # the published keys at test widths; what the kind reads and no more
    "model": "exaone_moe", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2, "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "sliding_windows": [WINDOW, WINDOW, WINDOW, 0] * 2, "sliding_window": WINDOW, "first_k_dense_replace": 1,
    "num_experts": 4, "published_num_experts": 16, "experts_held_from": 4, "num_experts_per_tok": 3,
    "num_shared_experts": 1, "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "routed_scaling_factor": 2.5, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"}, "tie_word_embeddings": False,
    "vocab_size": 256, "torch_dtype": "float32", "assumed_router_bias_std": 0.05,
}  # fmt: skip


@pytest.fixture(scope="module")
def model():
    cfg = models.program_config(CONFIG, max_seq=256, remat=False)
    params = models.make_weights(CONFIG, 2147483659)
    # gains that are not 1, so that a norm left out or a gain swapped shows
    for group in ("dense_layers", "layers"):
        for name, seed in (("q_norm", 1), ("k_norm", 2)):
            w = params[group][name]
            params[group][name] = w + 0.3 * jax.random.normal(jax.random.PRNGKey(seed), w.shape)
    return cfg, params


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, CONFIG["vocab_size"])


# -- (a) the uncached forward against the reference --------------------------------------


def test_the_config_is_the_models(model):
    cfg, params = model
    assert cfg.cache_kinds == ("window", "window", "window", "full") * 2 and cfg.layer_period == 4
    assert (cfg.head_dim, cfg.n_experts, cfg.n_experts_held, cfg.experts_held_from) == (32, 16, 4, 4)
    assert (cfg.qk_norm, cfg.rope_full_layers, cfg.n_dense_layers, cfg.capacity_factor) == (True, False, 1, 0.0)
    mine = jax.tree.map(lambda w: w.shape, llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    assert mine == jax.tree.map(lambda w: w.shape, params)  # the adapter's tree is the program's
    assert params["layers"]["w_gate"].shape[:2] == (7, 4) and params["layers"]["w_router"].shape == (7, 64, 16)
    assert cfg.param_count() == sum(w.size for w in jax.tree.leaves(params))


def test_forward_logits_match_the_reference(model):
    cfg, params = model
    toks = _tokens(1, (2, 64))  # three windows long: most queries have keys behind their window
    attn_ops.TRACED.pop("attention", None)
    np.testing.assert_allclose(moe.forward(params, toks, cfg), ref.logits(params, toks, CONFIG), **LOGITS)
    assert attn_ops.traced("attention") == "xla+xla_local"  # a window path answers by name


def test_loss_matches_the_references_mean_nll(model):
    cfg, params = model
    toks = _tokens(2, (2, 49))
    loss, aux = llama.loss_and_aux(params, {"tokens": toks}, dataclasses.replace(cfg, router_aux_coef=0.0))
    assert abs(float(loss) - float(ref.mean_nll(params, toks, CONFIG))) < 2e-5
    assert float(aux[llama.AUX_OVERFLOW]) == 0.0


@pytest.mark.parametrize("wrong", [
    "full_layers_rotated", "window_one_wider", "no_qk_norm", "every_layer_full", "held_from_0", "bf16_reference",
])  # fmt: skip
def test_a_wrong_layer_is_caught(model, wrong):
    cfg, params = model
    toks = _tokens(1, (2, 64))
    want = ref.logits(params, toks, CONFIG)
    if wrong == "full_layers_rotated":
        cfg = dataclasses.replace(cfg, rope_full_layers=True)
    elif wrong == "window_one_wider":
        cfg = dataclasses.replace(cfg, sliding_window=WINDOW + 1)
    elif wrong == "no_qk_norm":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    elif wrong == "every_layer_full":
        cfg = dataclasses.replace(cfg, layer_types=("full",) * 8, rope_full_layers=True)
    elif wrong == "held_from_0":  # the held weights taken for the experts 0-3
        cfg = dataclasses.replace(cfg, experts_held_from=0)
    else:  # the nearest precision below the one stated
        want = ref.logits(jax.tree.map(lambda w: w.astype(jnp.bfloat16), params), toks, CONFIG)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(moe.forward(params, toks, cfg), want, **LOGITS)


def test_splash_local_mask_is_the_window(model):
    """The chip's path for the uncached forward, in the interpreter: splash's local mask
    against the XLA function's (and so against the reference's)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 128)), jnp.float32)
    want = attn_ops.xla_attention(q, k, v, window=40)
    got = attn_ops.splash_attention(q, k, v, block_q=128, block_kv=128, interpret=True, window=40)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(want - attn_ops.xla_attention(q, k, v)).max()) > 1e-2  # and the window matters


# -- (c) the share ties to the model -----------------------------------------------------


def _uncut_layer(params, group="layers", i=0, seed=11):
    """One sparse layer with all 16 experts: the held four's weights at 4-7, fresh ones elsewhere."""
    lw = {k: w[i] for k, w in params[group].items()}
    rng = jax.random.PRNGKey(seed)
    full = {}
    for name in ("w_gate", "w_up", "w_down"):
        rng, sub = jax.random.split(rng)
        fresh = jax.random.normal(sub, (16, *lw[name].shape[1:])) * lw[name].shape[1] ** -0.5
        full[name] = fresh.at[4:8].set(lw[name])
    return lw, full


def test_the_eight_shares_sum_to_the_uncut_layer_of_the_reference(model):
    """The routed parts of all shares of a layer plus the shared expert counted once are
    the published layer: the reference's, then the program's share against the reference's."""
    cfg, params = model
    lw, full = _uncut_layer(params)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    c = dict(CONFIG, rope_theta=10000.0)
    uncut = ref.routed(u, dict(lw, **full), dict(c, experts_held_from=0), None)
    shares, mine = [], []
    for first in range(0, 16, 4):
        held = {k: w[first : first + 4] for k, w in full.items()}
        shares.append(ref.routed(u, dict(lw, **held), dict(c, experts_held_from=first), None))
        share_cfg = dataclasses.replace(cfg, experts_held_from=first)
        _, chosen, weights = moe._route(share_cfg, lw, u)
        mine.append(moe._dropless_experts(share_cfg, dict(lw, **held), u, chosen, weights))
        np.testing.assert_allclose(mine[-1], shares[-1], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(sum(shares), uncut, atol=2e-5, rtol=2e-5)
    assert all(float(jnp.abs(s).max()) > 1e-2 for s in shares)  # every share adds something
    # the uncut program (all 16 held) is the same layer, the shared expert added once by moe_ffn
    uncut_cfg = dataclasses.replace(cfg, experts_held=0, experts_held_from=0)
    whole, _ = moe.moe_ffn(uncut_cfg, dict(lw, **full), u)
    shared = ref_model.swiglu(u, lw["ws_gate"], lw["ws_up"], lw["ws_down"], None)
    np.testing.assert_allclose(whole, sum(mine) + shared, atol=2e-5, rtol=2e-5)


def test_rows_of_absent_experts_are_in_no_group(model):
    """Of 3 routings a token, 4/16 land here on average: the groups' sizes count those and no others."""
    cfg, params = model
    lw = {k: w[0] for k, w in params["layers"].items()}
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 200, 64))
    _, chosen, _ = moe._route(cfg, lw, u)
    here = int(((chosen >= 4) & (chosen < 8)).sum())
    assert 0 < here < chosen.size / 2
    seen = {}
    real = jax.lax.ragged_dot
    try:
        jax.lax.ragged_dot = lambda lhs, rhs, sizes: seen.setdefault("sizes", []).append(np.asarray(sizes)) or real(lhs, rhs, sizes)
        moe._dropless_experts(cfg, lw, u, chosen, jnp.ones(chosen.shape, jnp.float32))
    finally:
        jax.lax.ragged_dot = real
    assert all(s.shape == (4,) and int(s.sum()) == here for s in seen["sizes"]) and len(seen["sizes"]) == 3


# -- (d) the two attention programs over a pool ------------------------------------------


def _plain(q, k, v, window):
    """Plain masked attention of one sequence: q [t, h, hd] at the last t of s positions, k, v [s, kvh, hd]."""
    t, s = q.shape[0], k.shape[0]
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * q.shape[-1] ** -0.5
    i, j = jnp.arange(s - t, s)[:, None], jnp.arange(s)[None, :]
    mask = (j <= i) & (j > i - window) if window else j <= i
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision="highest")


def _ring_problem(lengths, window, ring, h=8, kvh=8, hd=128, bs=BS, layers=1, seed=0):
    """Each slot's whole history k, v [len, kvh, hd] a layer, and a pool that holds only
    the blocks its window touches, block b at entry b % ring; NaN wherever nothing lies."""
    rng = np.random.default_rng(seed)
    hist = [[(rng.standard_normal((n, kvh, hd)).astype(np.float32), rng.standard_normal((n, kvh, hd)).astype(np.float32))
             for n in lengths] for _ in range(layers)]  # fmt: skip
    nb = 1 + len(lengths) * ring
    k_pool = np.full((layers, nb, bs, kvh, hd), np.nan, np.float32)
    v_pool = np.full((layers, nb, bs, kvh, hd), np.nan, np.float32)
    k_pool[:, pa.TRASH_BLOCK] = v_pool[:, pa.TRASH_BLOCK] = 0.0
    tables = np.full((len(lengths), ring), pa.TRASH_BLOCK, np.int32)
    perm = rng.permutation(np.arange(1, nb))
    at = 0
    for i, n in enumerate(lengths):
        for b in range(max(0, n - window) // bs, -(-n // bs)):
            blk = perm[at]
            at += 1
            tables[i, b % ring] = blk
            rows = slice(b * bs, min(n, (b + 1) * bs))
            for layer in range(layers):
                k_pool[layer, blk, : rows.stop - rows.start] = hist[layer][i][0][rows]
                v_pool[layer, blk, : rows.stop - rows.start] = hist[layer][i][1][rows]
                k_pool[layer, blk, rows.stop - rows.start :] = v_pool[layer, blk, rows.stop - rows.start :] = 0.0
    q = rng.standard_normal((len(lengths), h, hd)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), hist


@pytest.mark.parametrize("lengths", [
    pytest.param([1, 8, 19, 20], id="inside-the-window"),
    pytest.param([21, 40, 41, 100, 163], id="past-it-ragged"),
])  # fmt: skip
def test_windowed_decode_kernel_and_xla_match_plain_masked_attention(lengths):
    """The Pallas kernel (interpreter) and the XLA function with ``window``, reading a
    ring table whose other blocks were given back (NaN where the kernel must not look),
    against plain masked attention over each slot's whole history."""
    ring = kv_pool.window_ring(WINDOW, BS)
    q, k_pool, v_pool, tables, lens, hist = _ring_problem(lengths, WINDOW, ring, layers=2)
    for layer in range(2):
        want = jnp.stack([_plain(q[i][None], *map(jnp.asarray, hist[layer][i]), WINDOW)[0] for i in range(len(lengths))])
        xla = pa.paged_attention_xla(q, k_pool, v_pool, tables, lens, jnp.int32(layer), WINDOW)
        got = pak.paged_attention_pallas(q, k_pool, v_pool, tables, lens, interpret=True, layer=jnp.int32(layer), window=WINDOW)
        np.testing.assert_allclose(xla, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_full_layer_kernel_is_as_it_was():
    """``window`` 0 on the same code: every position below the length, a table in sequence order."""
    q, k_pool, v_pool, tables, lens, hist = _ring_problem([5, 33, 40], 10**6, 5)
    want = jnp.stack([_plain(q[i][None], *map(jnp.asarray, hist[0][i]), 0)[0] for i in range(3)])
    got = pak.paged_attention_pallas(q, k_pool[0], v_pool[0], tables, lens, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "sliding"])
@pytest.mark.parametrize("prefix,suffix,k_rows,q_rows", [
    pytest.param([0, 0], [64, 23], 32, 16, id="cold-four-query-blocks"),
    pytest.param([32, 16], [32, 40], 16, 16, id="behind-a-cached-prefix"),
    pytest.param([48, 0], [16, 5], 32, 512, id="one-query-block"),
    pytest.param([16, 32], [33, 17], 512, 16, id="one-key-step-over-the-whole-table"),
])  # fmt: skip
def test_block_walk_prefill_matches_plain_masked_attention(monkeypatch, window, prefix, suffix, k_rows, q_rows):
    """``paged_attention_chunk`` (query blocks that walk the key blocks with a running
    softmax, from the window's first block to the last real token) against plain masked
    attention over prefix + suffix. A row's table holds the blocks its window touches and
    the trash block elsewhere, as the engine's does; the steps that lie wholly below a
    sliding layer's window hold NaN: the walk starts behind them."""
    monkeypatch.setattr(pa, "_PREFILL_K_ROWS", k_rows)
    monkeypatch.setattr(pa, "_PREFILL_Q_ROWS", q_rows)
    rows, bpr, t, h, kvh, hd = len(prefix), 10, 64, 4, 2, 32
    rng = np.random.default_rng(7)
    k_all = rng.standard_normal((rows, 80, kvh, hd)).astype(np.float32)
    v_all = rng.standard_normal((rows, 80, kvh, hd)).astype(np.float32)
    q_all = rng.standard_normal((rows, 80, h, hd)).astype(np.float32)
    pool_k = np.full((1 + rows * bpr, BS, kvh, hd), np.nan, np.float32)
    pool_v = pool_k.copy()
    pool_k[pa.TRASH_BLOCK] = pool_v[pa.TRASH_BLOCK] = 0.0
    own = np.arange(1, 1 + rows * bpr, dtype=np.int32).reshape(rows, bpr)
    tables = np.full_like(own, pa.TRASH_BLOCK)
    skipped = max(0, min(prefix) - window + 1) // k_rows * (k_rows // BS) if window else 0  # whole steps no row walks
    tables[:, :skipped] = own[:, :skipped]  # NaN there
    for r in range(rows):
        end = prefix[r] + suffix[r]
        first = max(0, prefix[r] - window + 1) // BS if window else 0  # blocks below went back to the pool
        for b in range(first, -(-end // BS)):
            tables[r, b] = own[r, b]
            n = min(end, (b + 1) * BS) - b * BS
            pool_k[tables[r, b]] = 0.0
            pool_v[tables[r, b]] = 0.0
            pool_k[tables[r, b], :n] = k_all[r, b * BS : b * BS + n]
            pool_v[tables[r, b], :n] = v_all[r, b * BS : b * BS + n]
    ahead = np.broadcast_to(np.arange(t, dtype=np.int32), (rows, t))
    pos = np.asarray(prefix)[:, None] + ahead
    valid = ahead < np.asarray(suffix)[:, None]
    q = np.take_along_axis(q_all, np.minimum(pos, 79)[..., None, None], axis=1)
    got = pa.paged_attention_chunk(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(tables), jnp.asarray(pos),
        jnp.asarray(valid), window=window)  # fmt: skip
    for r in range(rows):
        end = prefix[r] + suffix[r]
        want = _plain(jnp.asarray(q_all[r, prefix[r] : end]), jnp.asarray(k_all[r, :end]), jnp.asarray(v_all[r, :end]), window)
        np.testing.assert_allclose(got[r, : suffix[r]], want, atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)[valid]).all()


def test_traced_says_which_paged_attention_lowered(monkeypatch):
    q, k_pool, v_pool, tables, lens, _ = _ring_problem([5, 30], WINDOW, 5)
    for window, name in ((0, "paged_xla"), (WINDOW, "paged_xla_window")):
        attn_ops.TRACED.pop("attention", None)
        jax.make_jaxpr(lambda *a, w=window: pa.paged_attention(*a, window=w))(q, k_pool[0], v_pool[0], tables, lens)
        assert attn_ops.traced("attention") == name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attn_ops.TRACED.pop("attention", None)
    jax.make_jaxpr(lambda *a: pa.paged_attention(*a, window=WINDOW))(q, k_pool[0], v_pool[0], tables, lens)
    assert attn_ops.traced("attention") == "paged_pallas_window"
    attn_ops.TRACED.pop("attention", None)
    pos = jnp.zeros((2, 8), jnp.int32)
    jax.make_jaxpr(lambda qq: pa.paged_attention_chunk(qq, k_pool[0], v_pool[0], tables, pos, window=WINDOW))(jnp.zeros((2, 8, 8, 128)))
    assert attn_ops.traced("attention") == "paged_walk_window"


# -- (e) the pools' plan and the tables ---------------------------------------------------


def test_plan_pool_charges_a_sliding_layer_its_window():
    """The cell's geometry: what a further block costs is the two full layers' K/V; the
    six sliding layers cost a ring a slot and one staged round, whatever ``max_seq`` is."""
    kw = dict(vocab_size=19200, dim=6144, n_layers=8, n_heads=64, n_kv_heads=8, attn_head_dim=128, ffn_dim=18432,
              n_experts=128, experts_held=16, top_k=8, expert_ffn_dim=2048, n_shared_experts=1, n_dense_layers=1,
              capacity_factor=0.0, router_score="sigmoid", router_bias=True, max_seq=4224)  # fmt: skip
    mixed = moe.MoEConfig(**kw, layer_types=("sliding", "sliding", "sliding", "full") * 2, sliding_window=128)
    every = moe.MoEConfig(**kw)
    hbm = 16 * 2**30
    a = kv_pool.plan_pool(mixed, hbm_bytes=hbm, max_slots=64, max_prefill_batch=2)
    b = kv_pool.plan_pool(every, hbm_bytes=hbm, max_slots=64, max_prefill_batch=2)
    assert (a.window_ring, a.num_window_blocks) == (10, 1 + 2 * 264 + 64 * 10) and b.num_window_blocks == 0
    layer_block = 16 * 2 * 8 * 128 * 2
    window_bytes = 6 * a.num_window_blocks * layer_block
    assert a.kv_bytes == a.num_blocks * 2 * layer_block + window_bytes
    assert abs(a.num_blocks * 2 - (b.num_blocks * 8 - 6 * a.num_window_blocks)) <= 8  # the same budget, shared out anew
    assert a.num_blocks > 3.4 * b.num_blocks  # a token of context costs a quarter, less the windows' constant
    # twice the context: the sliding layers' charge a slot does not move, only the staged round's
    longer = kv_pool.plan_pool(dataclasses.replace(mixed, max_seq=8448), hbm_bytes=hbm, max_slots=64, max_prefill_batch=2)
    assert longer.num_window_blocks - a.num_window_blocks == 2 * 264 and longer.window_ring == 10


def test_window_tables_keep_a_ring_and_hand_back_the_oldest():
    t = kv_pool.WindowTables(2, 5)
    for b, blk in enumerate([11, 12, 13, 14]):
        t.assign(0, b, blk)
    assert t.tables[0].tolist() == [11, 12, 13, 14, 0] and t.held_blocks == 4
    assert t.release_below(0, 2) == [11, 12] and t.tables[0].tolist() == [0, 0, 13, 14, 0]
    t.assign(0, 4, 15), t.assign(0, 5, 16)  # block 5 takes entry 0
    assert t.tables[0].tolist() == [16, 0, 13, 14, 15] and t.has(0, 5) and not t.has(0, 1)
    with pytest.raises(ValueError, match="meets a held block"):
        t.assign(0, 8, 17)  # entry 3 still holds block 3
    assert sorted(t.release(0)) == [13, 14, 15, 16] and not t.tables.any()


def test_prefix_match_is_cut_back_to_where_the_window_blocks_last():
    full, window = kv_pool.BlockAllocator(32), kv_pool.BlockAllocator(32)
    cache = PrefixCache(full, 4, window_alloc=window, window_back=2)
    toks = list(range(100, 129))  # seven whole blocks and a token
    blocks, wblocks = full.alloc(7), window.alloc(7)
    # window blocks survive for blocks 0-3 and 6 only (4 and 5 went back while decoding)
    cache.insert(toks, blocks, {i: wblocks[i] for i in (0, 1, 2, 3, 6)})
    got, win, matched = cache.match_kinds(toks)
    assert matched == 16 and got == blocks[:4] and win == {2: wblocks[2], 3: wblocks[3]}  # not 28: 5 has none
    assert full.refcount(blocks[3]) == 3 and window.refcount(wblocks[3]) == 3 and window.refcount(wblocks[0]) == 2
    # the least recently used window blocks that the cache alone holds go first: block 6's (the match did
    # not reach it), then block 0's; a match needs only the last two blocks of its prefix, and still hits
    window.release(wblocks), window.release(list(win.values()))
    assert cache.evict_window(2) == 2 and window.refcount(wblocks[6]) == 0 and window.refcount(wblocks[0]) == 0
    got, win, matched = cache.match_kinds(toks)
    assert matched == 16 and win == {2: wblocks[2], 3: wblocks[3]}
    assert cache.evict_window(8) == 1 and window.refcount(wblocks[1]) == 0  # 2 and 3 are in use by that match
    assert cache.match(toks[:9]) == ([], 0)  # blocks 0 and 1 are cached, their window blocks are not: cut to nothing
    assert cache.cached_blocks == 7 and cache.stats()["window_evictions"] == 3
    full.release(blocks)  # the caller's own references
    assert cache.evict(7) == 3 and cache.cached_blocks == 4  # the leaves go; blocks 0-3 are in use by the two matches


# -- (b) the engine ----------------------------------------------------------------------


def _served_gaps(params, req):
    seq = list(req.prompt) + req.generated
    n_p, n_g = len(req.prompt), len(req.generated)
    lg = ref.logits(params, jnp.asarray([seq]), CONFIG)[0, n_p - 1 : n_p - 1 + n_g]
    got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(-1) - got)


def test_engine_serves_the_references_tokens_over_two_pools(model):
    """Prefill then decode through ``ServeEngine``: contexts that grow to five windows
    across many window-block releases, a preemption under full-pool pressure, prefix hits
    (one of them cut back by the window rule). Every served token has the reference's
    largest logit at its position or one within 1e-4 of it: logits, not tokens."""
    cfg, params = model
    shared = _tokens(7, (48,)).tolist()
    prompts = [shared + _tokens(10 + i, (5 + 7 * i,)).tolist() for i in range(5)]
    # 45 blocks of 8: five sequences of ~70 tokens growing by 60 do not fit together
    engine = ServeEngine(params, cfg, max_slots=4, block_size=BS, num_blocks=46, max_prefill_batch=2).start()
    try:
        stats = engine.stats()
        assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 4  # the two full layers' K and V
        assert stats["kv_bytes_per_slot_window"] == 5 * BS * 6 * 2 * 2 * 32 * 4 and engine.cache.window_ring == 5
        first = engine.generate(prompts[0], 6, timeout=300)  # primes the prefix cache
        reqs = [engine.submit(ServeRequest(p, max_new_tokens=60)) for p in prompts[1:]]
        assert all(r.wait(900) and not r.error for r in reqs)
        stats = engine.stats()
        assert stats["prefix_cache"]["hit_tokens"] >= 4 * 48 and stats["preemptions"] >= 1
        assert stats["window_blocks_released"] >= 4 * 6  # every slot's window moved over several blocks
        assert stats["kv_blocks_full"] == 0 and stats["kv_blocks_window"] == 0  # nothing left with the slots
        # a prompt that ends inside an earlier request's decoded part: its blocks there are
        # cached, their window blocks went back while decoding: the hit is cut back to the prompt's
        seq = reqs[0].tokens
        before = engine.stats()["prefix_cache"]["hit_tokens"]
        again = engine.generate(seq[: len(prompts[1]) + 40], 5, timeout=300)
        hit = engine.stats()["prefix_cache"]["hit_tokens"] - before
        assert hit == len(prompts[1]) // BS * BS, (hit, len(prompts[1]))
        assert again.generated == seq[len(prompts[1]) + 40 :][:5]
    finally:
        engine.stop()
    assert engine.cache.window_alloc.used_blocks <= engine.cache.prefix_cache.cached_blocks  # the slots gave all theirs back
    for req in [first, *reqs, again]:
        assert _served_gaps(params, req).max() < 1e-4


def test_a_hand_off_carries_both_kinds_of_blocks(model):
    """Prefill on one engine, decode on another from the exported blocks: a sliding
    layer's blocks travel beside the full layers' in the order the layers run, and the
    receiver keeps of them what the next query's window touches."""
    from torchx_tpu.serve.engine import serve_kv_payload
    from torchx_tpu.serve.kv_transfer import KvPayload

    cfg, params = model
    prompt = _tokens(21, (75,)).tolist()
    sender = ServeEngine(params, cfg, max_slots=2, block_size=BS).start()
    try:
        whole = sender.generate(prompt, 30, timeout=300)
        pre = sender.submit(ServeRequest(prompt, max_new_tokens=30, prefill_only=True))
        assert pre.wait(300) and pre.handoff is not None
        assert sender.cache.tables.held_blocks == 0 and sender.cache.window_tables.held_blocks == 0
    finally:
        sender.stop()
    payload = KvPayload.from_bytes(pre.handoff.to_bytes())
    assert payload.k.shape == (8, 10, BS, 2, 32) == payload.v.shape
    receiver = ServeEngine(params, cfg, max_slots=2, block_size=BS, enable_prefix_cache=False).start()
    try:
        reply = serve_kv_payload(receiver, payload, timeout=300)
        assert receiver.cache.window_alloc.used_blocks == 0 and receiver.cache.alloc.used_blocks == 0
    finally:
        receiver.stop()
    assert reply["tokens"] == whole.generated
    assert _served_gaps(params, ServeRequest(prompt, max_new_tokens=30, generated=reply["tokens"])).max() < 1e-4


def test_spans_and_scopes_name_the_kinds(model):
    from torchx_tpu.obs import hot

    cfg, params = model
    for name in ("ATTN_WINDOW", "ATTN_FULL", "QK_NORM"):
        assert getattr(hot, name) in hot.DEVICE_SCOPES
    engine = ServeEngine(params, cfg, max_slots=2, block_size=BS)
    tables = {"full": jnp.zeros((2, engine.cache.blocks_per_slot), jnp.int32), "window": jnp.zeros((2, 5), jnp.int32)}

    def decode(params, pools):
        z = jnp.zeros((2,), jnp.int32)
        return gen.paged_decode_step(params, z, z, tables, pools, cfg, jnp.zeros((2, 2), jnp.uint32), jnp.zeros((2,), jnp.float32))

    import re

    text = jax.jit(decode).lower(params, engine.pools).as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]+)"', text))
    for path in ("attn/attn_window/paged_attention/", "attn/attn_full/paged_attention/", "attn/attn_window/qk_norm/",
                 "attn/attn_full/qk_norm/", "attn/attn_window/append_kv/", "moe_experts/", "moe_shared/"):  # fmt: skip
        assert any(loc.startswith(path) or f"/{path}" in loc for loc in locs), path
    assert set(engine.cache.span_attrs(())) == {"kv_blocks_full", "kv_blocks_window", "window_blocks_released"}
    assert jax.tree.map(lambda p: p.shape, engine.pools) == {
        "full": {"k": (2, engine.cache.num_blocks, BS, 2, 32), "v": (2, engine.cache.num_blocks, BS, 2, 32)},
        "window": {"k": (6, engine.cache.num_window_blocks, BS, 2, 32), "v": (6, engine.cache.num_window_blocks, BS, 2, 32)},
    }
    assert engine.cache.num_window_blocks == 1 + 2 * 5 + 4 * engine.cache.blocks_per_slot
