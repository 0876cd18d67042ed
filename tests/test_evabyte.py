"""EVA attention over an aligned window and pooled chunks (EvaByte's layer) against the
benchmark's plain reference (``benchmark/reference/evabyte.py``: float32, no cache, no
blocks, no cache coordinate: two masks and one softmax), on the CPU at tiny widths with
seeded weights: window 32, chunk 4, block 4, 2 layers, 4 heads of 16, eight output heads.

Tolerances. Program and reference both compute in float32 here and differ in the order of
their sums (the window's rows and the pooled rows in one gathered table against two masked
score blocks): logits of size ~3 agree to ``2e-4 + 2e-4 |x|``, and a served token's logit
lies within ``1e-4`` of the reference's best. One wrong row of some hundreds moves a logit
by 1e-3 or more: the wrong layers and the two planted engine faults below are each caught.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import models
from benchmark.reference import evabyte as ref
from torchx_tpu.models import eva, moe
from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama
from torchx_tpu.obs import hot
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.ops.rope import apply_rope
from torchx_tpu.serve.engine import ServeEngine, ServeRequest
from torchx_tpu.serve.kv_pool import BlockAllocator, EvaTables
from torchx_tpu.serve.slot_cache import PagedCache

attn_ops = importlib.import_module("torchx_tpu.ops.attention")
LOGITS = dict(atol=2e-4, rtol=2e-4)
SERVED = 1e-4  # how far a served token's logit may lie below the reference's best: a near tie may fall either way

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests", "fixtures")
with open(os.path.join(FIXTURE, "configs", "tiny-evabyte.json")) as _f:
    CONFIG = json.load(_f)  # the published keys at test widths
W, C, BS, MAX_SEQ = CONFIG["window_size"], CONFIG["chunk_size"], 4, 160
VOCAB, HEADS = CONFIG["vocab_size"], CONFIG["num_pred_heads"]
CHUNK = 8  # the engine's chunk width here: two blocks, a quarter of a window


@pytest.fixture(scope="module")
def model():
    cfg = models.program_config(CONFIG, max_seq=MAX_SEQ, remat=False)
    return cfg, models.make_weights(CONFIG, 2147483659)


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, VOCAB)


# -- (a) the uncached forward -----------------------------------------------------------------


def test_forward_gives_the_references_logits_of_all_eight_heads(model):
    cfg, params = model
    toks = _tokens(5, (2, 150))  # four boundaries; the last chunk is left open
    want = ref.logits(params, toks, CONFIG)
    assert want.shape == (2, 150, HEADS * VOCAB)
    np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)
    assert float(jnp.std(want)) > 0.5 and attn_ops.traced("eva") != ""  # logits of order 1, through EVA's own path
    np.testing.assert_allclose(ref.head(ref.stream(params, toks, CONFIG), params, CONFIG), want[..., :VOCAB], **LOGITS)


def test_loss_is_the_references_mean_nll_of_head_0(model):
    cfg, params = model
    toks = _tokens(8, (2, 101))
    np.testing.assert_allclose(
        llama.loss_fn(params, {"tokens": toks}, cfg), ref.mean_nll(params, toks, CONFIG), atol=2e-4, rtol=2e-4
    )


def _wrong_attention(kind):
    """``eva.attention_full`` with one thing wrong."""

    def attention(cfg, layer, q, k, v):
        b, s, h, hd = q.shape
        kvh, n = k.shape[2], s // C
        whole = lambda x: x[:, : n * C].reshape(b, n, C, kvh, hd)  # noqa: E731
        if kind == "rope after pooling":  # the keys turned back, pooled, and the pooled row roped at its chunk's last position
            cos, sin = llama.rope_table(cfg, s)
            k_p, v_p = eva.pooled(dict(layer, eva_mu_k=jnp.zeros_like(layer["eva_mu_k"])), whole(apply_rope(k, cos, -sin)), whole(v))
            k_p = apply_rope(k_p, cos[C - 1 :: C][:n], sin[C - 1 :: C][:n]) + layer["eva_mu_k"]
        else:
            k_p, v_p = eva.pooled(layer, whole(k), whole(v))
        t, j, c = jnp.arange(s)[:, None], jnp.arange(s)[None, :], jnp.arange(n)[None, :]
        own, far = (j <= t) & (j >= W * (t // W)), c < (W // C) * (t // W)
        if kind == "pooled rows visible inside their own window":
            far = (c + 1) * C <= t
        elif kind == "a sliding window":
            own, far = (j <= t) & (j > t - W), (c + 1) * C <= t - W + 1
        score = lambda keys: jnp.einsum("bqhd,bkhd->bhqk", q, keys) * hd**-0.5  # noqa: E731 - h == kvh here
        s_own, s_far = jnp.where(own, score(k), -1e30), jnp.where(far, score(k_p), -1e30)
        if kind == "two softmaxes averaged":
            near = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_own, axis=-1), v)
            remote = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_far, axis=-1), v_p)
            return jnp.where((t >= W)[None, :, :, None], 0.5 * (near + remote), near)
        probs = jax.nn.softmax(jnp.concatenate((s_own, s_far), axis=-1), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs[..., :s], v) + jnp.einsum("bhqk,bkhd->bqhd", probs[..., s:], v_p)

    return attention


WRONG = ["no mu", "flat pooling", "pooled rows visible inside their own window", "a sliding window", "rope after pooling",
         "two softmaxes averaged"]  # fmt: skip


@pytest.mark.parametrize("wrong", [None, *WRONG])
def test_a_wrong_layer_is_caught(model, wrong, monkeypatch):
    """The comparison of the first test with the layer wrong in one place does not hold;
    the stand-in for ``eva.attention_full`` that the wrong ones are made from, with nothing
    wrong (None), does."""
    cfg, params = model
    toks = _tokens(5, (2, 150))
    want = ref.logits(params, toks, CONFIG)
    zeroed = {"no mu": "eva_mu_k", "flat pooling": "eva_phi"}.get(wrong)
    if zeroed:
        params = dict(params, layers=dict(params["layers"], **{zeroed: jnp.zeros_like(params["layers"][zeroed])}))
    else:
        monkeypatch.setattr(eva, "attention_full", _wrong_attention(wrong))
    got = llama.forward(params, toks, cfg)
    if wrong is None:
        return np.testing.assert_allclose(got, want, **LOGITS)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, **LOGITS)
    if wrong != "pooled rows visible inside their own window":  # the others have nothing to get wrong inside the first window
        np.testing.assert_allclose(got[:, :W], want[:, :W], **LOGITS)


# -- (b) the serving programs' own logits ------------------------------------------------------


class _Host:
    """What the engine does for a slot's tables, by hand: an allocator and
    :class:`EvaTables`, a window's blocks given back and its staging moved into the table
    when a write starts the next."""

    def __init__(self, rows, num_blocks):
        self.alloc, self.tables = BlockAllocator(num_blocks), EvaTables(rows, MAX_SEQ, W, C, BS)

    def ensure(self, row, position):
        if position // W > self.tables.window_of(row):
            self.alloc.release(self.tables.turn(row))
        if short := self.tables.short(row, position):
            self.tables.assign(row, self.alloc.alloc(short))

    def arg(self, rows=slice(None)):
        return {"full": jnp.asarray(self.tables.tables[rows]), "stage": jnp.asarray(self.tables.stage[rows])}


def test_chunks_then_decode_through_the_pool_give_the_references_logits(model, monkeypatch):
    """The serving programs themselves, their sampling replaced by the identity so that they
    hand back logits: two prompts (37 and 50 bytes: neither a multiple of the chunk, one
    crossing a boundary) fed in chunks of 8 that stop where a window ends, then both rows
    decoded to position 139, over four boundaries, every step's logits against the
    reference's full forward at that position. The blocks come from a pool no larger than
    the rows need once the windows behind them are given back, so a block is reused."""
    cfg, params = model
    monkeypatch.setattr(gen, "_sample_rows", lambda logits, keys, temps: logits)
    toks = _tokens(6, (2, 140))
    want = ref.logits(params, toks, CONFIG)[..., :VOCAB]
    host = _Host(2, 1 + 2 * (8 + 2 + 4 * 2))
    pools = gen.init_kv_pools(cfg, host.alloc.num_blocks, BS)
    keys, temps = jnp.zeros((2, 2), jnp.uint32), jnp.zeros((2,), jnp.float32)
    prompt = [37, 50]
    for row, n in enumerate(prompt):
        at = 0
        while at < n:
            m = min(CHUNK, n - at, W - at % W)
            host.ensure(row, at + m - 1)
            chunk = jnp.zeros((1, CHUNK), jnp.int32).at[0, :m].set(toks[row, at : at + m])
            lg, pools = gen.paged_prefill_chunk(
                params, chunk, jnp.asarray([at]), jnp.asarray([m]), host.arg(slice(row, row + 1)), pools, cfg, keys[:1], temps[:1])  # fmt: skip
            at += m
            np.testing.assert_allclose(lg[0], want[row, at - 1], **LOGITS)
    at = np.asarray(prompt)
    while at.max() < 140:
        for row in range(2):
            host.ensure(row, int(at[row]))
        lg, pools = gen.paged_decode_step(params, toks[jnp.arange(2), at], jnp.asarray(at), host.arg(), pools, cfg, keys, temps)
        for row in range(2):
            np.testing.assert_allclose(lg[row], want[row, at[row]], **LOGITS, err_msg=f"row {row} at {at[row]}")
        at = np.minimum(at + 1, 139) if at.min() == 139 else at + (at < 139)
        if (at == 139).all():
            break
    assert host.tables.window_of(0) == host.tables.window_of(1) == 4 and attn_ops.traced("eva").endswith("paged")


def test_the_cache_coordinate_and_the_tables(model):
    cfg, _ = model
    t = np.arange(MAX_SEQ)
    coords = np.asarray(eva.cache_coord(cfg, jnp.asarray(t)))
    np.testing.assert_array_equal(coords, (W // C) * (t // W) + t % W)
    tables = EvaTables(2, MAX_SEQ, W, C, BS)
    assert (tables.window_blocks, tables.pooled_blocks, tables.windows) == (8, 2, 5)
    assert tables.blocks_per_slot == 2 * 4 + 8 and tables.most_blocks == 18
    assert [tables.coord(int(p)) for p in t] == coords.tolist()
    assert [tables.rows(n) for n in (0, 1, 4, 32, 33, 70)] == [0, 1, 5, 40, 9, 8 + 8 + 6 + 1]
    tables.assign(0, list(range(1, 11)))  # staging first, then the window
    assert tables.stage[0].tolist() == [1, 2] and tables.tables[0, :8].tolist() == list(range(3, 11))
    assert tables.short(0, 31) == 0 and tables.held_pooled == 2 and tables.held_window == 8
    assert tables.turn(0) == list(range(3, 11))
    assert tables.tables[0].tolist() == [1, 2] + [TRASH_BLOCK] * 14 and tables.stage[0].tolist() == [TRASH_BLOCK] * 2
    assert tables.short(0, 32) == 3 and tables.window_of(0) == 1
    with pytest.raises(ValueError, match="not whole"):
        tables.turn(0)
    with pytest.raises(ValueError, match="one chunk"):
        EvaTables(2, MAX_SEQ, W, C, 8)
    assert sorted(tables.release(0)) == [1, 2] and tables.held_blocks == 0


# -- (c) the engine ------------------------------------------------------------------------------


def _served_gaps(params, req):
    seq = list(req.prompt) + req.generated
    n_p, n_g = len(req.prompt), len(req.generated)
    lg = ref.logits(params, jnp.asarray([seq]), CONFIG)[0, n_p - 1 : n_p - 1 + n_g, :VOCAB]
    got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(-1) - got)


def _spy(engine):
    """Every step the engine enqueues, in order: the decode part's positions and tables, and
    the chunk's ``(start, real tokens, slot or -1)`` where it carries one."""
    log = []

    def spied(real):
        def program(params, tokens, prev, positions, tables, pools, *rest):
            chunk = tuple(int(v) for v in np.asarray(rest[3])) if len(rest) > 2 else None
            log.append({"positions": np.asarray(positions), "tables": np.asarray(tables["full"]),
                        "stage": np.asarray(tables["stage"]), "chunk": chunk, "in_flight": engine._in_flight is not None})  # fmt: skip
            return real(params, tokens, prev, positions, tables, pools, *rest)

        return program

    engine._decode, engine._decode_chunk = spied(engine._decode), spied(engine._decode_chunk)
    return log


LENGTHS, NEW = [37, 20, 50, 33, 13, 41, 16], [60, 90, 30, 12, 80, 45, 70]
SEEDS = [20, 21, 22, 23, 34, 25, 26]  # request 4's prompt is one whose 52nd byte it has not served before: its EOS


def _requests():
    return [ServeRequest(_tokens(seed, (n,)).tolist(), max_new_tokens=m) for seed, n, m in zip(SEEDS, LENGTHS, NEW)]


def _serve(params, cfg, reqs, **kw):
    engine = ServeEngine(params, cfg, max_slots=3, block_size=BS, max_prefill_batch=2, chunk_width=CHUNK, **kw)
    log, turns = _spy(engine), []
    turn = engine.cache.tables.turn
    engine.cache.tables.turn = lambda slot: turns.append(slot) or turn(slot)
    for r in reqs:
        engine.submit(r)
    engine.start()
    try:
        for r in reqs:
            assert r.wait(600) and not r.error, r.error
        assert engine.drain(60)
        stats = engine.stats()
    finally:
        engine.stop()
    return engine, log, turns, stats


@pytest.fixture(scope="module")
def served(model):
    """Three slots, chunks of 8, seven requests: slots are reused by later requests, prompts
    of one to seven chunks (five of them no multiple of the chunk of 4) are fed while others
    decode, sequences cross up to three boundaries, the pool is short so that the youngest is
    preempted and fed again from 0, and request 4 stops at an EOS that the step at position
    63 samples: the step in flight behind it has started its third window."""
    cfg, params = model
    reqs = _requests()
    probe = ServeEngine(params, cfg, max_slots=1, block_size=BS, chunk_width=CHUNK).start()
    try:
        alone = probe.generate(reqs[4].prompt, 80, timeout=300).generated
    finally:
        probe.stop()
    at = 2 * W - LENGTHS[4]  # generated[at] is sampled by the step at position 2 W - 1
    assert alone[at] not in alone[:at]
    reqs[4].eos_id = alone[at]
    return (*_serve(params, cfg, reqs, num_blocks=27), reqs, at)


def test_engine_serves_the_references_tokens(served, model):
    """Every token served (a slot's first tenant or a later one, fed beside decoding slots,
    behind a boundary that fell with a step in flight, recomputed after a preemption) has
    the reference's largest logit at its position or one within 1e-4 of it."""
    _, params = model
    engine, log, turns, stats, reqs, at = served
    assert stats["requests_done"] == 7 > stats["max_slots"] and stats["preemptions"] >= 1
    assert len(reqs[4].generated) == at + 1 and stats["tokens_discarded"] >= 1  # the EOS, and the step behind it
    assert any(step["chunk"] and (step["positions"] > 0).sum() == 2 for step in log)  # one fed while two decode
    assert any(step["in_flight"] and (step["positions"] % W == 0)[step["positions"] > 0].any() for step in log)  # a boundary
    for req in reqs:
        assert _served_gaps(params, req).max() < SERVED
    assert max(len(r.prompt) + len(r.generated) for r in reqs) > 3 * W  # three boundaries in one sequence


def test_the_pool_is_empty_at_the_end_and_the_counts_are_as_reckoned(served):
    engine, log, turns, stats, reqs, _ = served
    assert engine.cache.alloc.used_blocks == 0 and engine.cache.tables.held_blocks == 0 and (engine.cache.tables.tables == TRASH_BLOCK).all()
    assert stats["kv_blocks_full"] == stats["kv_blocks_window"] == stats["kv_blocks_pooled"] == stats["cache_rows_held"] == 0
    assert stats["window_blocks_released"] == 8 * len(turns) and stats["pooled_blocks_promoted"] == 2 * len(turns)
    # a request turns once for every boundary its writes crossed: the prompt and all but its last token are written;
    # the step in flight behind request 4's EOS crossed one more; a preempted request crosses its boundaries again
    crossed = sum((len(r.prompt) + len(r.generated) - 2) // W for r in reqs) + 1
    assert len(turns) >= crossed and (stats["preemptions"] > 0 or len(turns) == crossed)
    assert "not its tokens" in stats["prefix_cache_off"] and engine.cache.prefix_cache is None
    assert stats["kv_bytes_per_token"] == 2 * 2 * 4 * 16 * 4 // C  # two layers' K and V of 4 heads of 16 in float32, a sixteenth... a fourth here


def test_a_chunk_stops_where_a_window_ends_and_the_open_chunk_is_pooled_in_decode(served):
    """No chunk of a prompt crosses a boundary; a decode step addresses a slot's staging
    blocks from the step that fills the chunk its prompt left open on."""
    _, log, *_ = served
    chunks = [step["chunk"] for step in log if step["chunk"]]
    assert all(start // W == (start + n - 1) // W and start % BS == 0 for start, n, _ in chunks)
    assert any((start + n) % W == 0 and n < CHUNK or (start + n) % W == 0 for start, n, _ in chunks)
    assert any(n % C for _, n, _ in chunks)  # a prompt that ends inside a chunk
    for step in log:
        live = step["positions"] > 0
        assert (step["stage"][live] != TRASH_BLOCK).all() and (step["stage"][~live] == TRASH_BLOCK).all()


def test_the_counts_of_one_request_alone(model):
    """No preemption, no EOS: 41 + 100 bytes write positions 0..139 and cross four boundaries."""
    cfg, params = model
    reqs = [ServeRequest(_tokens(40, (41,)).tolist(), max_new_tokens=100)]
    engine, log, turns, stats = _serve(params, cfg, reqs)
    assert len(turns) == 4 and stats["window_blocks_released"] == 32 and stats["pooled_blocks_promoted"] == 8
    assert stats["preemptions"] == 0 and engine.cache.alloc.used_blocks == 0
    assert engine.cache.num_blocks == 1 + 3 * (2 * 5 + 16 // 2)  # every pooled block a slot can hold and half a table
    assert _served_gaps(params, reqs[0]).max() < SERVED
    # the rows the decode kernel reads and the rows held, as the spans carry them
    assert engine.cache.tables.rows(140) == 4 * 8 + 12 + 3 and engine.cache.tables.coord(139) + 1 == 4 * 8 + 12


@pytest.mark.parametrize("fault", ["the chunk a prompt leaves open is never pooled", "the table is laid anew one step late"])
def test_a_planted_engine_fault_is_caught(model, fault, monkeypatch):
    """Two small faults that the comparison above does not let through: one pooled row of
    eight wrong in one window, and one step that reads the window before."""
    cfg, params = model
    reqs = [ServeRequest(_tokens(41, (37,)).tolist(), max_new_tokens=60)]
    if fault.startswith("the chunk"):
        real = gen._Rows.pool

        def pool(self, cfg, layer, k_pool, v_pool, table, at):  # a decode row pools nothing ahead of position 40
            if self.valid is None:
                self = self._replace(tables=dict(self.tables, stage=jnp.where(self.sequence_at[:, None] < 40, TRASH_BLOCK, self.tables["stage"])))
            return real(self, cfg, layer, k_pool, v_pool, table, at)

        monkeypatch.setattr(gen._Rows, "pool", pool)
    else:
        real = ServeEngine._make_writable

        def late(self, slot, write_pos):
            st = self._slots[slot]
            return real(self, slot, write_pos - 1 if st.feeding is None and write_pos % W == 0 else write_pos)

        monkeypatch.setattr(ServeEngine, "_make_writable", late)
    _serve(params, cfg, reqs)
    assert _served_gaps(params, reqs[0]).max() > 10 * SERVED


def test_what_this_cache_is_not_built_for_is_refused(model):
    cfg, params = model
    engine = ServeEngine(params, cfg, max_slots=2, block_size=BS, chunk_width=CHUNK)
    with pytest.raises(NotImplementedError, match="not its tokens"):
        engine.submit(ServeRequest([1, 2, 3], max_new_tokens=1, prefill_only=True))
    with pytest.raises(NotImplementedError, match="not its tokens"):
        engine.submit_prefilled(ServeRequest([1, 2, 3], max_new_tokens=2), np.zeros((2, 1, 4, 4, 16)), np.zeros((2, 1, 4, 4, 16)), 3, 7)
    with pytest.raises(NotImplementedError, match="paged path"):
        gen.generate(params, jnp.zeros((1, 4), jnp.int32), cfg, 2)
    with pytest.raises(ValueError, match="one chunk"):
        ServeEngine(params, cfg, max_slots=2, block_size=8)
    with pytest.raises(ValueError, match="cannot hold one max_seq sequence"):
        ServeEngine(params, cfg, max_slots=2, block_size=BS, num_blocks=18)
    eva_on = dict(eva_window=32, eva_chunk=4)
    for more in (dict(layer_types=("sliding", "full"), sliding_window=8), dict(hc_mult=2, hc_sinkhorn_iters=2), dict(qk_norm=True),
                 dict(kernels="pallas"), dict(use_ring_attention=True), dict(eva_chunk=5), dict(eva_chunk=0),
                 dict(ssm_heads=4, ssm_head_dim=8, ssm_state=16)):  # fmt: skip
        with pytest.raises(ValueError):
            llama.llama_tiny(**{**eva_on, **more})
    with pytest.raises(ValueError):
        moe.moe_tiny(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, **eva_on)
    for more in (dict(kernels="pallas"), dict(hc_mult=2, hc_sinkhorn_iters=2), dict(ssm_heads=4, ssm_head_dim=8, ssm_state=16)):  # (QK-norm: built since PR 49)
        with pytest.raises(ValueError, match="unit-offset"):
            llama.llama_tiny(norm_unit_offset=True, **more)
    with pytest.raises(ValueError, match="pred_heads"):
        llama.llama_tiny(pred_heads=0)
    for key, value in (("attention_class", "softmax"), ("attention_bias", True), ("rope_scaling", {"type": "yarn"}), ("fp32_logits", False)):
        with pytest.raises(ValueError):
            models.program_config(dict(CONFIG, **{key: value}))


def test_program_init_lays_out_the_kinds_tree(model):
    cfg, _ = model
    theirs = jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    mine = jax.tree.map(lambda leaf: leaf[0], models.weight_shapes(CONFIG), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    assert jax.tree.map(lambda w: tuple(w.shape), theirs) == mine
    assert set(llama.param_specs(cfg)["layers"]) == set(mine["layers"])
    assert cfg.param_count() == sum(int(np.prod(s)) for s in jax.tree.leaves(mine, is_leaf=lambda x: isinstance(x, tuple)))
    # the program's own init: gains about zero, and a forward that is a number
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    assert float(jnp.abs(params["final_norm"]).max()) == 0.0 and params["lm_head"].shape == (cfg.dim, HEADS * VOCAB)
    assert bool(jnp.isfinite(llama.forward(params, _tokens(1, (1, 40)), cfg)).all())


def test_the_unit_offset_and_the_float32_add_are_the_models_own():
    """A model of another kind with gains about zero and the adds in float32 serves what its
    forward gives; with neither it traces the jaxpr it traced before (below)."""
    cfg = llama.llama_tiny(max_seq=64, norm_unit_offset=True, fp32_skip_add=True, dtype=jnp.bfloat16)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    text = str(jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(params, jnp.zeros((1, 8), jnp.int32)))
    assert "f32[1,8,64]" in text and hot.EVA_POOL in hot.DEVICE_SCOPES
    ones = jax.tree.map(lambda w: w, params)
    ones["final_norm"] = jnp.ones_like(params["final_norm"])
    plain = llama.llama_tiny(max_seq=64, dtype=jnp.bfloat16)
    lifted = dict(ones, layers=dict(ones["layers"], attn_norm=ones["layers"]["attn_norm"] + 1, mlp_norm=ones["layers"]["mlp_norm"] + 1))
    toks = _tokens(2, (1, 12))
    np.testing.assert_allclose(llama.forward(params, toks, cfg), llama.forward(lifted, toks, plain), atol=0.05, rtol=0.05)


# -- (d) the five older kinds are the programs they were ------------------------------------------

OLDER = {
    "llama": lambda: llama.llama_tiny(max_seq=64),
    "moe": lambda: moe.moe_tiny(max_seq=64),
    "sliding_qk_norm": lambda: llama.llama_tiny(
        max_seq=64, n_layers=4, layer_types=("sliding", "sliding", "sliding", "full"), sliding_window=8, qk_norm=True,
        rope_full_layers=False),
    "mla_moe_hc": lambda: moe.moe_tiny(
        max_seq=64, n_layers=3, n_kv_heads=4, ffn_dim=96, n_experts=8, top_k=3, expert_ffn_dim=32, n_shared_experts=2,
        router_score="sigmoid", router_bias=True, routed_scale=2.446, n_dense_layers=1, capacity_factor=0.0,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, q_lora_rank=24, hc_mult=2, hc_sinkhorn_iters=3),
    "mixer": lambda: llama.llama_tiny(
        max_seq=64, ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=16, key_multiplier=0.5,
        mlp_multipliers=(0.7, 0.4)),
}  # fmt: skip
#: sha256 of the jaxprs below as the parent commit (PR 42's tree, 93ffc25) traced them: the defaults of the fields this
#: PR added leave a model without them alone. A PR that changes what these programs compute on purpose records its own.
#: PR 51 did, for the uncached forward of the grouped-query kinds alone (whole-head rotation, ``tests/test_rope_whole.py``);
#: every serving step's digest is the parent's.
AT_THE_PARENT = {
    "llama": ("0d238167015cc164", "49b8059943fd7a24"),
    "mixer": ("923e4ec10b0095e5", "85ad609ce1ecd246"),
    "mla_moe_hc": ("a6091627457f9312", "482c3837461f078d"),
    "moe": ("95d1f827f13bf2e5", "a4cba197c5f6b94c"),
    "sliding_qk_norm": ("178965a15efc42ed", "a7e55fa8d2330bd8"),
}


def _digests(cfg):
    """(the mixed serving step, the uncached forward) of ``cfg`` as jaxprs, hashed."""
    slots, bs, width = 3, 16, 32
    bps = cfg.max_seq // bs
    params = jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    pools = jax.eval_shape(lambda: gen.init_kv_pools(cfg, 1 + slots * bps, bs, 1 + slots * bps, slots))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def table(rows):
        if cfg.layer_types:
            return {"full": i32(rows, bps), "window": i32(rows, bps)}
        return {"full": i32(rows, bps), "state": i32(rows)} if cfg.ssm_heads else i32(rows, bps)

    step = jax.make_jaxpr(
        lambda p, tok, pos, tab, chunk, start, n, ctab, pl, keys, temps: gen.paged_decode_chunk_step(
            p, tok, pos, tab, chunk, start, n, ctab, pl, cfg, keys, temps)
    )(params, i32(slots), i32(slots), table(slots), i32(width), i32(), i32(), table(1), pools,
      jax.ShapeDtypeStruct((slots + 1, 2), jnp.uint32), jax.ShapeDtypeStruct((slots + 1,), jnp.float32))  # fmt: skip
    forward = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(params, i32(2, 24))
    return tuple(hashlib.sha256(str(j).encode()).hexdigest()[:16] for j in (step, forward))


@pytest.mark.parametrize("kind", sorted(OLDER))
def test_an_older_kind_traces_the_jaxpr_it_traced_at_the_parent(kind):
    assert _digests(OLDER[kind]()) == AT_THE_PARENT[kind]


def test_the_default_pool_is_the_older_kinds_own():
    """The engine's geometry for a model whose rows are its tokens is what it was."""
    cfg = llama.llama_tiny(max_seq=64)
    engine = ServeEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=2)
    assert type(engine.cache) is PagedCache and engine.cache.blocks_per_slot == 4 and engine.cache.num_blocks == 1 + 2 * 2
    assert "cache_rows_held" not in engine.stats() and math.isclose(engine.stats()["occupancy"], 0.0)
