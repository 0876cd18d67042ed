"""Latent attention, the sigmoid/bias router, the shared expert, leading dense layers and
the dropless dispatch against the benchmark's plain reference (``benchmark/reference/
mla_moe.py``: float32 at ``precision=HIGHEST``, every expert on every token, the published
interleaved rotary pairing), on the CPU at tiny widths with seeded weights and a selection
bias that is not zero.

Tolerances. Program and reference both compute in float32 here and differ in the order
of their sums (absorbed against expanded attention, sorted rows against masked experts,
a fused norm): logits of size ~1 agree to ``2e-4 + 2e-4 |x|`` and the loss to ``2e-5``.
A reference in bfloat16, or one missing term (the shared expert, the routed scale, a bias
that weighs), moves logits by 1e-2 or more: `test_a_wrong_layer_is_caught` holds the
comparison to that.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.lib import models
from benchmark.reference import mla_moe as ref
from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama, mla, moe
from torchx_tpu.ops import grouped_matmul as gm
from torchx_tpu.ops import paged_attention as pa
from torchx_tpu.ops import paged_mla as pm
from torchx_tpu.ops import paged_mla_kernel as pmk
from torchx_tpu.serve import kv_pool
from torchx_tpu.serve.engine import ServeEngine, ServeRequest, serve_kv_payload
from torchx_tpu.serve.kv_transfer import KvPayload

attn_ops = importlib.import_module("torchx_tpu.ops.attention")
LOGITS = dict(atol=2e-4, rtol=2e-4)

CONFIG = {  # the published keys at test widths; what the kind reads and no more
    "model": "mla_moe", "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4, "n_shared_experts": 2,
    "n_routed_experts": 8, "routed_scaling_factor": 2.446, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 3, "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "float32", "assumed_router_bias_std": 0.05,
}  # fmt: skip


@pytest.fixture(scope="module")
def model():
    cfg = models.program_config(CONFIG, max_seq=128, remat=False)
    params = models.make_weights(CONFIG, 2147483659)
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) > 0.01  # the bias is there to choose
    return cfg, params


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, CONFIG["vocab_size"])


# -- (a) forward and loss against the reference ---------------------------------------


def test_forward_logits_match_the_reference(model):
    cfg, params = model
    toks = _tokens(1, (2, 48))
    np.testing.assert_allclose(llama.forward(params, toks, cfg), ref.logits(params, toks, CONFIG), **LOGITS)


def test_loss_matches_the_references_mean_nll(model):
    cfg, params = model
    toks = _tokens(2, (2, 49))
    loss, aux = llama.loss_and_aux(params, {"tokens": toks}, dataclasses.replace(cfg, router_aux_coef=0.0))
    assert abs(float(loss) - float(ref.mean_nll(params, toks, CONFIG))) < 2e-5
    assert float(aux[llama.AUX_OVERFLOW]) == 0.0  # dropless: readable, and 0 by construction


def test_the_bias_changes_who_is_chosen(model):
    """Otherwise "the bias chooses, never weighs" is not exercised by these weights."""
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, cfg.dim))
    _, chosen, _ = moe._route(cfg, layer, x)
    _, unbiased, _ = moe._route(dataclasses.replace(cfg, router_bias=False), layer, x)
    assert (np.sort(chosen, -1) != np.sort(unbiased, -1)).any()


def test_rotary_pairing_agrees_under_the_stored_permutation(model):
    """One seeded weight set, the rotary columns in the published order for the
    reference's equations and evens-first for the program's: the same scores."""
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["dense_layers"])
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.dim))
    published = ref.mla(u, layer, CONFIG, None)
    cos, sin = llama.rope_frequencies(cfg.rope_dim, 24, cfg.rope_theta)
    np.testing.assert_allclose(mla.attention_full(cfg, layer, u, cos, sin), published, atol=2e-5, rtol=2e-5)
    # the reference reads the stored order: evens first, then odds
    x = jnp.arange(8.0)
    assert ref._published_order(jnp.asarray([0.0, 2, 4, 6, 1, 3, 5, 7])).tolist() == x.tolist()


# -- (e) a wrong layer is caught by (a) -------------------------------------------------


def _biased_weights(real):
    def route(cfg, layer, x):
        scores, chosen, _ = real(cfg, layer, x)
        picked = jnp.take_along_axis(scores + layer["router_bias"], chosen, axis=-1)
        return scores, chosen, picked / picked.sum(-1, keepdims=True) * cfg.routed_scale

    return route


@pytest.mark.parametrize("wrong", ["bias_weighs", "no_shared_expert", "no_routed_scale", "bf16_reference"])
def test_a_wrong_layer_is_caught(model, wrong, monkeypatch):
    cfg, params = model
    toks = _tokens(1, (2, 48))
    want = ref.logits(params, toks, CONFIG)
    if wrong == "bias_weighs":
        monkeypatch.setattr(moe, "_route", _biased_weights(moe._route))
    elif wrong == "no_shared_expert":
        cfg = dataclasses.replace(cfg, n_shared_experts=0)
    elif wrong == "no_routed_scale":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    else:  # the nearest precision below the one stated
        want = ref.logits(jax.tree.map(lambda w: w.astype(jnp.bfloat16), params), toks, CONFIG)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)


# -- (b) the engine: prefill, decode, prefix hit, preemption, hand-off ------------------


def _served_gaps(params, req):
    seq = list(req.prompt) + req.generated
    n_p, n_g = len(req.prompt), len(req.generated)
    lg = ref.logits(params, jnp.asarray([seq]), CONFIG)[0, n_p - 1 : n_p - 1 + n_g]
    got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(-1) - got)


def test_engine_serves_the_references_tokens_through_hit_preemption_and_handoff(model):
    """Every token the engine served, by whatever road, has the reference's largest
    logit at its position or one within 1e-4 of it (a near tie may fall either way)."""
    cfg, params = model
    shared = _tokens(7, (32,)).tolist()
    prompts = [shared + _tokens(10 + i, (9 + 5 * i,)).tolist() for i in range(5)]
    # 13 blocks of 16: five sequences of ~60 tokens growing by 24 do not fit together
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, num_blocks=13, max_prefill_batch=2).start()
    try:
        first = engine.generate(prompts[0], 6, timeout=300)  # primes the prefix cache
        reqs = [engine.submit(ServeRequest(p, max_new_tokens=24)) for p in prompts[1:]]
        assert all(r.wait(600) and not r.error for r in reqs)
        stats = engine.stats()
        assert stats["prefix_cache"]["hit_tokens"] >= 32 and stats["preemptions"] >= 1
        # hand-off: prefill here, decode on a second engine from the exported latent blocks
        pre = engine.submit(ServeRequest(prompts[2], max_new_tokens=8, prefill_only=True))
        assert pre.wait(300) and pre.handoff is not None
    finally:
        engine.stop()
    payload = KvPayload.from_bytes(pre.handoff.to_bytes())
    assert payload.k.shape[0] == cfg.n_layers and payload.k.shape[-1] == cfg.cache_width and payload.v.shape[-1] == 0
    decoder = ServeEngine(params, cfg, max_slots=2, block_size=16).start()
    try:
        reply = serve_kv_payload(decoder, payload, timeout=300)
    finally:
        decoder.stop()
    handed = ServeRequest(prompts[2], max_new_tokens=8, generated=reply["tokens"])
    assert reply["tokens"] == reqs[1].generated[:8]  # the same road's tokens as the unified engine's
    for req in [first, *reqs, handed]:
        assert _served_gaps(params, req).max() < 1e-4


# -- (c) absorbed against expanded, kernel against XLA -------------------------------


def _latent_problem(lengths, h=8, rank=128, rope=64, bs=16, bpr=6, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    width = -(-(rank + rope) // 128) * 128
    live = [-(-n // bs) for n in lengths]
    nb = 1 + sum(live)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.full((len(lengths), bpr), pa.TRASH_BLOCK, np.int32)
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = perm[at : at + n]
        at += n
    pool = rng.standard_normal((nb, bs, width)).astype(np.float32)
    pool[..., rank + rope :] = 0.0
    q = rng.standard_normal((len(lengths), h, width)).astype(np.float32)
    q[..., rank + rope :] = 0.0
    as_dtype = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    return as_dtype(q), as_dtype(pool), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), rank


def test_absorbed_decode_equals_expanded_attention_on_one_pool(model):
    """``mla.paged_decode`` (W_kvb folded into query and result, rows as they lie in
    the pool) against ``mla.paged_prefill`` of the same token (K and V expanded)."""
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["dense_layers"])
    slots, bs, bpr = 3, 16, 4
    lengths = jnp.asarray([5, 37, 64], jnp.int32)  # rows each slot holds, the new token's included
    pool = jnp.zeros((1 + slots * bpr, bs, cfg.cache_width))
    tables = jnp.arange(1, 1 + slots * bpr, dtype=jnp.int32).reshape(slots, bpr)
    cos_f, sin_f = llama.rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta)
    # fill the pool with each slot's history by one prefill of random hidden states
    hist = jax.random.normal(jax.random.PRNGKey(8), (slots, 64, cfg.dim))
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (slots, 64))
    valid = pos < (lengths - 1)[:, None]
    _, pool = mla.paged_prefill(cfg, layer, hist, cos_f[pos], sin_f[pos], pos, valid, tables, pool)
    u = jax.random.normal(jax.random.PRNGKey(9), (slots, 1, cfg.dim))
    at = lengths - 1
    absorbed, pool_a = mla.paged_decode(cfg, layer, u, cos_f[at], sin_f[at], at, tables, pool)
    expanded, pool_e = mla.paged_prefill(
        cfg, layer, u, cos_f[at][:, None], sin_f[at][:, None], at[:, None], jnp.ones((slots, 1), bool), tables, pool)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(pool_a, pool_e)  # and both leave the same row behind


@pytest.mark.parametrize("prefix,suffix,k_rows,q_rows", [
    pytest.param([0, 0], [64, 23], 32, 16, id="cold-four-query-blocks-two-key-steps"),
    pytest.param([32, 16], [32, 40], 16, 16, id="behind-a-cached-prefix-a-block-a-step"),
    pytest.param([48, 0], [16, 5], 32, 512, id="one-query-block"),
    pytest.param([16, 32], [33, 17], 512, 16, id="one-key-step-over-the-whole-table"),
])  # fmt: skip
def test_paged_prefill_walks_the_window_to_the_same_attention(model, monkeypatch, prefix, suffix, k_rows, q_rows):
    """``mla.paged_prefill`` (query blocks that walk the pool a few blocks a step, as
    far as their last real token, with a running softmax) against
    ``mla.attention_full`` over prefix + suffix at once. float32: sums in another order."""
    monkeypatch.setattr(mla, "_PREFILL_K_ROWS", k_rows)
    monkeypatch.setattr(mla, "_PREFILL_Q_ROWS", q_rows)
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["dense_layers"])
    rows, bs, bpr, t = len(prefix), 16, 5, 64  # a table of 80 rows: no whole number of 32-row steps
    prefix, suffix = np.asarray(prefix), np.asarray(suffix)
    u = jax.random.normal(jax.random.PRNGKey(12), (rows, 80, cfg.dim))
    cos_f, sin_f = llama.rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta)
    want = mla.attention_full(cfg, layer, u, cos_f[:80], sin_f[:80])
    # NaN wherever nothing was written: a step that reads past a row's tokens would show
    pool = jnp.full((1 + rows * bpr, bs, cfg.cache_width), jnp.nan).at[pa.TRASH_BLOCK].set(0.0)
    tables = jnp.arange(1, 1 + rows * bpr, dtype=jnp.int32).reshape(rows, bpr)
    ahead = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (rows, t))
    if prefix.any():  # the cached prefix, by a prefill of its own
        _, pool = mla.paged_prefill(
            cfg, layer, u[:, :t], cos_f[ahead], sin_f[ahead], ahead, ahead < prefix[:, None], tables, pool)
    pos = jnp.asarray(prefix)[:, None] + ahead
    chunk = jnp.take_along_axis(u, jnp.minimum(pos, 79)[..., None], axis=1)
    pool = jnp.nan_to_num(pool)  # a block's unwritten tail is masked, and multiplied by a probability of 0
    got, _ = mla.paged_prefill(
        cfg, layer, chunk, cos_f[jnp.minimum(pos, 79)], sin_f[jnp.minimum(pos, 79)], pos,
        ahead < suffix[:, None], tables, pool)
    for r in range(rows):
        np.testing.assert_allclose(
            got[r, : suffix[r]], want[r, prefix[r] : prefix[r] + suffix[r]], atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()  # padded query rows too: the next layer multiplies them


def _shrink_the_kernels_geometry(monkeypatch):
    """A chunk of 4 blocks in parts of 2 and groups of 1: a table of 6 blocks then spans two
    chunks, both buffers, several parts and groups."""
    for name, rows in (("_CHUNK_ROWS", 64), ("_PART_ROWS", 32), ("_GROUP_ROWS", 16)):
        monkeypatch.setattr(pmk, name, rows)


@pytest.mark.parametrize("lengths,dtype", [
    pytest.param([1, 16, 17, 96], jnp.float32, id="ragged-float32"),
    pytest.param([40, 3, 96, 64, 0], jnp.float32, id="a-slot-of-no-length"),
    pytest.param([33, 80, 7], jnp.bfloat16, id="bfloat16"),
])  # fmt: skip
def test_mla_kernel_matches_the_xla_function(lengths, dtype, monkeypatch):
    """The Pallas kernel in the interpreter, its chunk shrunk to 4 blocks in parts of 2
    and groups of 1, so that a slot spans several chunks and both buffers. Tolerances as for the K/V kernel
    (tests/test_paged_attention_kernel.py): float32 sums in another order; bfloat16
    rounds probabilities before the division where XLA rounds them after."""
    _shrink_the_kernels_geometry(monkeypatch)
    q, pool, tables, lens, rank = _latent_problem(lengths, dtype=dtype)
    # the trash block and blocks past a slot's length hold what a decode step left there: never read
    want = pm.paged_mla_attention_xla(q, pool, tables, lens, rank, 0.07)
    got = pmk.paged_mla_pallas(q, pool, tables, lens, rank, 0.07, interpret=True)
    live = np.asarray(lens) > 0  # a slot of no length attends nothing: either path returns numbers nobody reads
    tol = dict(atol=2e-6, rtol=1e-5) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live], **tol)
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_mla_kernel_reads_its_layer_out_of_the_stack(dtype, monkeypatch):
    """``layer=i`` on the stack ``[layers, num_blocks, bs, width]`` against the same call
    on ``stack[i]`` (bit for bit) and against the XLA function at that layer: ragged
    lengths, a slot of no length, an inactive slot whose table is all trash block."""
    _shrink_the_kernels_geometry(monkeypatch)
    lengths = [40, 0, 96, 17, 1]
    problems = [_latent_problem(lengths, seed=s, dtype=dtype) for s in range(3)]
    q, _, tables, lens, rank = problems[0]
    tables = tables.at[4].set(pa.TRASH_BLOCK)
    stack = jnp.stack([p[1] for p in problems])  # a layer's rows differ; the trash block holds numbers in each
    keep = np.asarray([0, 2, 3])  # what slots 1 (no length) and 4 (inactive) return is never read
    tol = dict(atol=2e-6, rtol=1e-5) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    outs = []
    for i in range(3):
        got = pmk.paged_mla_pallas(q, stack, tables, lens, rank, 0.07, interpret=True, layer=jnp.int32(i))
        one = pmk.paged_mla_pallas(q, stack[i], tables, lens, rank, 0.07, interpret=True)
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(one, np.float32))
        want = pm.paged_mla_attention_xla(q, stack, tables, lens, rank, 0.07, jnp.int32(i))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(pm.paged_mla_attention_xla(q, stack[i], tables, lens, rank, 0.07)))
        np.testing.assert_allclose(np.asarray(got, np.float32)[keep], np.asarray(want, np.float32)[keep], **tol)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        outs.append(np.asarray(got, np.float32)[keep])
    assert not np.array_equal(outs[0], outs[1]) and not np.array_equal(outs[1], outs[2])  # the layers differ


# Every edge the kernel's bookkeeping has, at its real geometry (two buffers of 1,024 rows, copied in
# groups of 128 and multiplied 512 at a time): lengths are rows, a table holds `bpr` blocks of 16.
EDGES = [
    pytest.param([0, 1, 40], 72, id="no-length-then-one-row"),
    pytest.param([127, 128, 129], 72, id="a-groups-edge-and-one-past"),
    pytest.param([512, 513], 72, id="a-parts-edge-and-one-past"),
    pytest.param([1024, 1025], 72, id="a-chunks-edge-and-one-past"),
    pytest.param([1152, 16], 72, id="every-block-of-the-table"),
    pytest.param([2100], 136, id="one-slot-more-chunks-than-buffers"),
    pytest.param([2000, 5], 136, id="long-then-short"),
    pytest.param([5, 2000], 136, id="short-then-long"),
    pytest.param([512, 1536, 1536, 512], 136, id="whole-parts-only"),
    pytest.param([1024, 2048, 1024, 2048], 136, id="whole-chunks-only-the-first-buffer-alternates"),
    pytest.param([600, 0, 0, 700], 72, id="slots-of-no-length-between"),
    pytest.param([1024, 700], 72, id="the-last-slot-ragged"),
    pytest.param([1], 72, id="one-slot-one-row"),
]


def _held_in_the_tpu_interpreter(capfd, dma, want, q, pool, tables, lens, rank, **layer):
    """``paged_mla_pallas`` in the TPU interpreter (semaphores simulated, scratch full of NaN)
    against ``want`` on every slot that has rows, and nothing left over or raced for."""
    got = pmk.paged_mla_pallas(
        q, pool, tables, lens, rank, 0.07, **layer, interpret=pltpu.InterpretParams(dma_execution_mode=dma, detect_races=True))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-6, rtol=1e-5)
    assert np.isfinite(np.asarray(got)[live]).all()
    out = capfd.readouterr().out
    assert "non-zero count" not in out and "RACE DETECTED" not in out, out


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
@pytest.mark.parametrize("lengths,bpr", EDGES)
def test_mla_kernel_bookkeeping_on_every_edge(lengths, bpr, dma, capfd):
    """The kernel in the TPU interpreter, which simulates the copies' semaphores and hands
    out scratch memory full of NaN, against the XLA function in float32. ``on_wait``: a copy
    lands only when its semaphore is waited for, so a chunk read before its wait, or a group
    started and never waited for, reads NaN or stale rows. ``eager``: every byte started
    must have been waited for when the kernel ends, or the interpreter says so. (A wait for
    bytes nobody started would hang here as on the chip.) The trash block holds NaN where
    no slot is empty: past its last live block a slot reads that block again, never the table."""
    q, pool, tables, lens, rank = _latent_problem(lengths, bpr=bpr)
    want = pm.paged_mla_attention_xla(q, pool, tables, lens, rank, 0.07)
    if min(lengths) > 0:
        pool = pool.at[pa.TRASH_BLOCK].set(jnp.nan)
    _held_in_the_tpu_interpreter(capfd, dma, want, q, pool, tables, lens, rank)


@pytest.mark.parametrize("layer", [0, 2], ids=["first-layer", "last-layer"])
def test_mla_kernel_bookkeeping_into_a_stack(layer, capfd):
    """The same accounting with the block ids moved into layer ``layer`` of a stack seen flat,
    up to the stack's last block."""
    lengths = [700, 0, 1025, 16]
    problems = [_latent_problem(lengths, bpr=72, seed=s) for s in range(3)]
    q, _, tables, lens, rank = problems[0]
    tables = tables.at[0, 0].set(problems[0][1].shape[0] - 1)  # the pool's last block is somebody's
    stack = jnp.stack([p[1] for p in problems])
    want = pm.paged_mla_attention_xla(q, stack, tables, lens, rank, 0.07, jnp.int32(layer))
    _held_in_the_tpu_interpreter(capfd, "eager", want, q, stack, tables, lens, rank, layer=jnp.int32(layer))


@pytest.mark.parametrize("shapes,backend,want", [
    (((64, 16, 640), (8449, 16, 640), 512), "tpu", True),  # kimi-vl-a3b-serve-backlog's
    (((128, 32, 640), (16897, 16, 640), 512), "tpu", True),  # xing4-serve-decode-long's
    (((64, 16, 640), (8449, 16, 640), 512), "cpu", False),
    (((64, 16, 576), (8449, 16, 576), 512), "tpu", False),  # rows of 4.5 lanes: the chip cannot slice them
    (((4, 4, 128), (9, 16, 128), 32), "tpu", False),  # a latent of a quarter lane, four heads
])  # fmt: skip
def test_mla_kernel_eligibility_is_a_function_of_shapes_and_backend(shapes, backend, want):
    q_shape, pool_shape, rank = shapes
    assert pm.kernel_eligible(q_shape, pool_shape, rank, jnp.bfloat16, jnp.bfloat16, backend) is want


def test_traced_says_which_latent_attention_lowered(model, monkeypatch):
    q, pool, tables, lens, rank = _latent_problem([5, 20])
    attn_ops.TRACED.pop("attention", None)
    jax.jit(lambda *a: pm.paged_mla_attention(*a, rank, 0.1)).lower(q, pool, tables, lens)
    assert attn_ops.traced("attention") == "paged_mla_xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attn_ops.TRACED.pop("attention", None)
    q16, pool16 = q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)
    attn_ops.TRACED.pop("paged_mla_geometry", None)
    jax.make_jaxpr(lambda *a: pm.paged_mla_attention(*a, rank, 0.1))(q16, pool16, tables, lens)
    assert attn_ops.traced("attention") == "paged_mla_pallas"
    # what the lowering chose from the shapes: a table of 6 blocks is one chunk, one part, one group
    assert attn_ops.traced("paged_mla_geometry") == "chunk 96 part 96 group 96 rows, 2 buffers"


def test_the_kernels_geometry_at_both_cells_shapes():
    """Blocks a chunk, a part and a group from the block size and the table alone: both
    latent cells (blocks of 16, tables of 264) copy 1,024 rows a buffer in groups of 128 and
    multiply 512 at a time; a short table shrinks all three."""
    assert pmk._geometry(16, 264) == (64, 32, 8)
    assert pmk._geometry(32, 132) == (32, 16, 4)
    assert pmk._geometry(16, 40) == (32, 32, 8)
    assert pmk._geometry(16, 3) == (3, 3, 3)


# -- (d) dropless routing under imbalance --------------------------------------------


def test_nothing_is_dropped_when_one_expert_gets_over_half_the_tokens(model):
    cfg, params = model
    layer = dict(jax.tree.map(lambda w: w[0], params["layers"]))
    # skew the router: expert 5's logit leads by far for most tokens
    layer["w_router"] = layer["w_router"].at[:, 5].multiply(0.0)
    layer["router_bias"] = layer["router_bias"].at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 64, cfg.dim))
    _, chosen, _ = moe._route(cfg, layer, x)
    assert (np.asarray(chosen) == 5).any(-1).mean() > 0.5
    out, aux = moe.moe_ffn(cfg, layer, x)
    np.testing.assert_allclose(out, ref.experts(x, layer, CONFIG, None), atol=2e-5, rtol=2e-5)
    assert float(aux[llama.AUX_OVERFLOW]) == 0.0
    # the capacity path at Mixtral's rule (capacity_factor = E / k) drops none either; at 1.0 it must
    held, over = moe._capacity_experts(dataclasses.replace(cfg, capacity_factor=8 / 3), layer, x, *moe._route(cfg, layer, x)[1:])
    assert float(over) == 0.0
    _, over = moe._capacity_experts(dataclasses.replace(cfg, capacity_factor=1.0), layer, x, *moe._route(cfg, layer, x)[1:])
    assert float(over) > 0.2


@pytest.mark.parametrize("sizes", [[128, 0, 96, 32], [0, 0, 256, 0], [64, 64, 64, 64], [1, 2, 3, 250]])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes):
    """The Pallas grouped matmul in the interpreter against ``jax.lax.ragged_dot``,
    empty groups and a group that spans row tiles among them."""
    rng = np.random.default_rng(sum(sizes[:2]))
    lhs = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.float32)
    g = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, g, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(gm.grouped_matmul(lhs, rhs, g, interpret=True), want, atol=1e-4, rtol=1e-4)
    attn_ops.TRACED.pop("grouped_matmul", None)
    np.testing.assert_allclose(gm.grouped_matmul(lhs, rhs, g), want, atol=1e-4, rtol=1e-4)
    assert attn_ops.traced("grouped_matmul") == "ragged_dot"


@pytest.mark.parametrize("m,k,n,chunks,sizes", [
    pytest.param(512, 128, 128, 1, [60, 300, 100, 52], id="a-group-crosses-two-tile-edges"),
    pytest.param(384, 256, 128, 2, [100, 0, 30, 20], id="groups-sum-to-less-than-m"),  # a chip's share: rows of no group behind
    pytest.param(256, 128, 128, 1, [0, 0, 0, 0], id="no-group-has-a-row"),
    # the step that carries a chunk at kimi's counts ((64 + 256) x 6 rows, 64 experts), cut in k and n
    pytest.param(1920, 256, 256, 2, None, id="64-groups-over-15-tiles"),
    pytest.param(512, 384, 512, 3, [128, 128, 1, 255], id="three-chunks-and-edges-on-tile-edges"),
])  # fmt: skip
def test_grouped_matmul_walks_the_groups_that_have_rows(m, k, n, chunks, sizes):
    """What the walk has that a grid over row tiles had not: a group's weights land once whatever
    the row tiles it reaches into, in chunks of ``k``; rows behind the last group are not computed."""
    rng = np.random.default_rng(m + k)
    if sizes is None:
        sizes = np.bincount(rng.integers(0, 64, m), minlength=64)
    g, here = len(sizes), int(np.sum(sizes))
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32)
    assert k // gm._tiling(m, k, n, 4, g)[1] == chunks  # of k: what a copy brings and a product multiplies by
    want = jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32), precision=jax.lax.Precision.HIGHEST)
    got = gm.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32), interpret=True)
    np.testing.assert_allclose(got[:here], want[:here], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
@pytest.mark.parametrize("ring_columns", [256, 128], ids=["one-column-tile", "two-column-tiles"])
def test_grouped_matmul_bookkeeping_in_the_tpu_interpreter(ring_columns, dma, capfd, monkeypatch):
    """The walk in the TPU interpreter, which simulates the copies' semaphores, hands out scratch full of
    NaN and watches for races: every chunk waited for before it is multiplied by, none started into a
    chunk still to be read, nothing left in flight behind the last pair; with the ring cut to half of
    ``n`` the walk runs once a column tile and the last pair of one starts the first group of the next."""
    rng = np.random.default_rng(5)
    m, k, n, sizes = 512, 384, 256, [60, 300, 0, 100]
    monkeypatch.setattr(gm, "_RING_BYTES", k * ring_columns * 4)
    assert gm._tiling(m, k, n, 4, 4) == (128, 128, ring_columns)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((2, 4, k, n)), jnp.float32)
    g = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(lhs, stack[1], g, precision=jax.lax.Precision.HIGHEST)
    got = gm.grouped_matmul(
        lhs, stack, g, jnp.int32(1), interpret=pltpu.InterpretParams(dma_execution_mode=dma, detect_races=True))  # fmt: skip
    np.testing.assert_allclose(np.asarray(got)[: sum(sizes)], np.asarray(want)[: sum(sizes)], atol=1e-4, rtol=1e-4)
    out = capfd.readouterr().out
    assert "non-zero count" not in out and "RACE DETECTED" not in out, out


def test_grouped_matmul_differentiates_as_ragged_dot_does():
    """A gradient through the interpreted kernel (its layer read out of a stack), with respect to the
    rows and to the stack, against the gradient through ``jax.lax.ragged_dot`` on that layer."""
    rng = np.random.default_rng(46)
    lhs = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((3, 4, 128, 256)), jnp.float32)
    g = jnp.asarray([100, 0, 28, 128], jnp.int32)
    weigh = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    kernel = lambda lhs, stack: (gm.grouped_matmul(lhs, stack, g, jnp.int32(2), interpret=True) * weigh).sum()  # noqa: E731
    plain = lambda lhs, stack: (jax.lax.ragged_dot(lhs, stack[2], g, precision=jax.lax.Precision.HIGHEST) * weigh).sum()  # noqa: E731
    got, want = jax.grad(kernel, (0, 1))(lhs, stack), jax.grad(plain, (0, 1))(lhs, stack)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    assert float(jnp.abs(got[1][:2]).max()) == 0.0 and float(jnp.abs(got[1][2]).max()) > 0.0  # the layer's experts alone


def test_grouped_matmul_is_traced_once_a_shape_not_once_a_call_site(monkeypatch):
    """The set-up budget (PR 46): seven call sites of one shape in one program, three with the layer's
    number a Python int and four written out in a ``lax.scan`` body with a traced one, as
    ``llama.scan_layers`` runs ``k-exaone``'s expert layers, trace the kernel's body once, and the lowered
    module holds one function with the kernel in it, called from the seven sites (jax lowers the scan
    body's calls to the same function as the others: one, not two). A ``pallas_call`` built in a plain
    function was traced and lowered at every site: 0.35 s a site on the benchmark machine, 42 sites an engine."""
    from torchx_tpu.ops import grouped_matmul_kernel as gk

    traces = []
    body = gk._kernel
    monkeypatch.setattr(gk, "_kernel", functools.wraps(body)(lambda *refs: traces.append(1) or body(*refs)))
    rng = np.random.default_rng(7)
    lhs = jnp.asarray(rng.standard_normal((384, 128)), jnp.float32)  # a shape no other test of this file walks
    stack = jnp.asarray(rng.standard_normal((7, 4, 128, 128)) * 0.1, jnp.float32)
    g = jnp.asarray([100, 0, 156, 128], jnp.int32)

    def program(x, stack, g):
        for i in range(3):
            x = gm.grouped_matmul(x, stack, g, i, interpret=True)

        def period(x, p):
            for j in range(4):
                x = gm.grouped_matmul(x, stack, g, 3 + 4 * p + j, interpret=True)
            return x, None

        return jax.lax.scan(period, x, jnp.arange(1, dtype=jnp.int32))[0]

    text = jax.jit(program).lower(lhs, stack, g).as_text()
    assert len(traces) == 1
    assert len(re.findall(r"func\.func private @walk\w*\(", text)) == 1
    assert len(re.findall(r"call @walk\w*\(", text)) == 7
    want = lhs
    for i in range(7):
        want = jax.lax.ragged_dot(want, stack[i], g, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(jax.jit(program)(lhs, stack, g), want, atol=1e-4, rtol=1e-4)
    assert len(traces) == 1


@pytest.mark.parametrize("m,k,n,want", [
    (384, 2048, 1408, (128, 256, 1408)),  # decode: 64 slots x 6 picks, 6 rows a group; an expert in 8 chunks of 0.7 MB
    (12288, 2048, 1408, (256, 256, 1408)),  # a prefill of 2,048 tokens: 192 rows a group
    (49152, 1408, 2048, (512, 128, 2048)),  # the widest prefill's down-projection: 768; 1,408 = 11 x 128 splits no other way
    (96, 2048, 1408, (0, 256, 1408)),  # rows that fill no tile: ragged_dot
])  # fmt: skip
def test_grouped_matmul_tiles_follow_the_shapes(m, k, n, want):
    assert gm._tiling(m, k, n, 2, groups=64) == want
    assert gm.kernel_eligible((m, k), (64, k, n), jnp.bfloat16, jnp.bfloat16, "tpu") is all(want)
    assert not gm.kernel_eligible((m, k), (64, k, n), jnp.bfloat16, jnp.bfloat16, "cpu")


# -- the tree, the pools and the plan ---------------------------------------------------


def test_two_layer_groups_and_their_pools(model):
    cfg, params = model
    assert llama.layer_groups(params) == ("dense_layers", "layers")
    assert gen.layer_group_sizes(cfg) == {"dense_layers": 1, "layers": 2}
    init, specs = llama.model_fns(cfg)
    own = init(cfg, jax.random.PRNGKey(0))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    assert jax.tree.structure(specs(cfg), is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) == jax.tree.structure(own)
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    pools = gen.init_kv_pools(cfg, 9, 16)
    assert {k: v.shape for k, v in pools.items()} == {"dense_layers": (1, 9, 16, 128), "layers": (2, 9, 16, 128)}
    k, v = gen.export_blocks(pools, jnp.asarray([3, 4]))
    assert k.shape == (3, 2, 16, 128) and v.shape == (3, 2, 16, 0)
    back = gen.import_blocks(pools, jnp.asarray([5, 6]), np.ones(k.shape, np.float32), v)
    assert float(back["layers"][1, 5].min()) == 1.0 and float(back["layers"][1, 4].max()) == 0.0
    with pytest.raises(NotImplementedError):
        gen.init_kv_cache(cfg, 1, 16)


def test_plan_pool_charges_a_latent_row_a_token_a_layer():
    cfg = moe.MoEConfig(
        vocab_size=163840, dim=2048, n_layers=9, n_heads=16, n_kv_heads=16, ffn_dim=11264, max_seq=4096,
        n_experts=64, top_k=6, expert_ffn_dim=1408, n_shared_experts=2, router_score="sigmoid", router_bias=True,
        n_dense_layers=1, capacity_factor=0.0, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128)
    plan = kv_pool.plan_pool(cfg, hbm_bytes=16 * 2**30, headroom=0.9, block_size=16)
    assert plan.kv_bytes == plan.num_blocks * 9 * 16 * 640 * 2  # 1,280 B a token a layer as laid out
    gqa = dataclasses.replace(cfg, kv_lora_rank=0)  # 16 K/V heads of 128 in its place: 8,192 B
    assert kv_pool.plan_pool(gqa, hbm_bytes=16 * 2**30, headroom=0.9, block_size=16).num_blocks < plan.num_blocks / 6



def test_grouped_matmul_reads_its_layer_out_of_the_stack():
    """``rhs`` as a whole stack of layers and the layer's number: the kernel is given every
    layer's groups, all empty but one layer's; the XLA path indexes the stack."""
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((3, 4, 128, 128)), jnp.float32)
    g = jnp.asarray([100, 0, 28, 128], jnp.int32)
    for layer in (0, 2):
        want = jax.lax.ragged_dot(lhs, stack[layer], g, precision=jax.lax.Precision.HIGHEST)
        at = jnp.int32(layer)
        np.testing.assert_allclose(gm.grouped_matmul(lhs, stack, g, at, interpret=True), want, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gm.grouped_matmul(lhs, stack, g, at), want, atol=1e-4, rtol=1e-4)


def test_eviction_order_is_least_recently_used_leaf_first():
    """The heap's order against a walk of the whole tree, through random inserts, matches,
    releases and evictions: each victim is the evictable leaf with the oldest stamp."""
    from torchx_tpu.serve.kv_pool import BlockAllocator
    from torchx_tpu.serve.prefix_cache import PrefixCache

    rng = np.random.default_rng(0)
    alloc = BlockAllocator(400)
    cache = PrefixCache(alloc, 4)
    held = []

    def oldest_evictable():
        best, stack = None, list(cache._root.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if not node.children and alloc.refcount(node.block) == 1 and (best is None or node.last_used < best.last_used):
                best = node
        return best

    for _ in range(300):
        op = rng.integers(0, 4)
        if op == 0 and alloc.free_blocks > 8:  # a sequence finishes: its blocks are indexed, then released
            toks = rng.integers(0, 3, rng.integers(4, 30)).tolist()
            blocks = alloc.alloc(len(toks) // 4) or []
            cache.insert(toks, blocks)
            alloc.release(blocks)
        elif op == 1:  # a request matches a prefix and keeps it for a while
            blocks, _ = cache.match(rng.integers(0, 3, rng.integers(4, 30)).tolist())
            held.append(blocks)
        elif op == 2 and held:
            alloc.release(held.pop(rng.integers(0, len(held))))
        else:
            want = oldest_evictable()
            before = cache.cached_blocks
            assert cache.evict(1) == (want is not None)
            if want is not None:
                assert not want.live and cache.cached_blocks == before - 1
    for blocks in held:
        alloc.release(blocks)
    cache.evict(10_000)
    assert cache.cached_blocks == 0 and alloc.used_blocks == 0
