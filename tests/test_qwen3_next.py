"""Three Gated DeltaNet layers for every gated attention layer, a share of the experts behind
the published router and a gated shared expert (Qwen3-Next's block) against the benchmark's
plain reference (``benchmark/reference/qwen3_next.py``: float32, the delta rule a ``lax.scan``
over positions, no chunks, no cache, no sorting), on the CPU at tiny widths with seeded weights:
every zero-centred gain drawn about 0, the gated norm's about 1 in size, ``A_log`` and ``dt_bias``
drawn so that heads forget at every rate.

Tolerances. Program and reference both compute in float32 here and differ in the order of
their sums (a sub-chunk's positions solved together against one at a time, the decays as
differences of running sums against products): the mixer's outputs of size ~1 agree to
``5e-5``, logits of size ~1 to ``2e-4 + 2e-4 |x|``. A reference in bfloat16 or one wrong piece
(no ``1 +``, the gate ahead of the norm, the whole head rotated, no ``beta``, the shared expert
ungated, the top-k's weights not renormalised) moves logits by 1e-2 or more:
``test_a_wrong_layer_is_caught`` holds the comparison to that.
"""

from __future__ import annotations

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import models
from benchmark.reference import qwen3_next as ref
from torchx_tpu.models import gdn, llama, moe
from torchx_tpu.models import generate as gen
from torchx_tpu.serve.engine import ServeEngine, ServeRequest

attn_ops = importlib.import_module("torchx_tpu.ops.attention")
LOGITS = dict(atol=2e-4, rtol=2e-4)
MIXER = dict(atol=5e-5, rtol=5e-5)

CONFIG = {  # the published keys at test widths; this chip holds experts 4-7 of 16
    "model": "qwen3_next", "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 32, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_value_head_dim": 16, "mlp_only_layers": [],
    "moe_intermediate_size": 32, "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "published_num_experts": 16, "experts_held_from": 4, "num_experts_per_tok": 3, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 10000.0, "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 512, "torch_dtype": "float32", "assumed_norm_gain_std": 0.1,
    "assumed_A_log_std": 2.0, "assumed_dt_bias_std": 3.0,
}  # fmt: skip
CHUNK = 16  # the engine's chunk width here; the mixer's sub-chunks are 8: a prompt of 40 crosses both


@pytest.fixture(scope="module")
def model():
    cfg = models.program_config(CONFIG, max_seq=128, remat=False, gdn_chunk=8)
    return cfg, models.make_weights(CONFIG, 2147483659)


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, CONFIG["vocab_size"])


def _layer(params, i):
    """Layer ``i``'s weights as the reference and the program's one-layer calls take them."""
    places = {at: (j, attending) for at, j, attending in ref._places(params, CONFIG)}
    j, attending = places[i]
    return ref._weights_of(params, i, j, attending)


def _empty(cfg, rows):
    return (jnp.zeros((rows, cfg.gdn_heads, cfg.gdn_head_dim, cfg.gdn_head_dim), jnp.float32),
            jnp.zeros((rows, cfg.gdn_conv - 1, cfg.gdn_conv_width), cfg.dtype))  # fmt: skip - as the two forms take them


def _chunked(cfg, layer, u, state, tail, valid=None):
    qkv, z, b, a = gdn.project(cfg, layer, u)
    o, state, tail = gdn.chunk_core(cfg, layer, qkv, b, a, state, tail, valid)
    return gdn.finish(cfg, layer, o, z), state, tail


def _stepped(cfg, layer, u, state, tail):
    qkv, z, b, a = gdn.project(cfg, layer, u)
    o, state, tail = gdn.step_core(cfg, layer, qkv, b, a, state, tail)
    return gdn.finish(cfg, layer, o, z), state, tail


# -- (a) the mixer's two forms and the reference's scan over positions ---------------------


@pytest.mark.parametrize("carried", [0, 5, 23])
def test_chunked_form_is_the_recurrence_is_the_references_scan(model, carried):
    """40 positions of two sequences: the first ``carried`` through the chunked form from an
    empty state, the rest from what that left (the state and the convolution's tail) through
    the chunked form with a padded tail and, apart, one position at a time. Each gives what
    the reference's mixer gives over all 40 from nothing, and both forms leave the same state."""
    cfg, params = model
    layer, t = _layer(params, 1), 40
    u = jax.random.normal(jax.random.PRNGKey(3), (2, t, cfg.dim), jnp.float32)
    want = ref.delta_net(u, layer, CONFIG, None)
    state, tail = _empty(cfg, 2)
    if carried:
        head, state, tail = _chunked(cfg, layer, u[:, :carried], state, tail)
        np.testing.assert_allclose(head, want[:, :carried], **MIXER)
    # the chunked form over what is left, right-padded: row 1 stops three positions early
    rest = jnp.pad(u[:, carried:], ((0, 0), (0, 7), (0, 0)), constant_values=9.0)
    real = jnp.asarray([t - carried, t - carried - 3])
    valid = jnp.arange(rest.shape[1])[None, :] < real[:, None]
    out, state_chunked, tail_chunked = _chunked(cfg, layer, rest, state, tail, valid)
    np.testing.assert_allclose(out[0, : t - carried], want[0, carried:], **MIXER)
    np.testing.assert_allclose(out[1, : t - carried - 3], want[1, carried : t - 3], **MIXER)
    s, tl, steps = state, tail, []
    for i in range(carried, t):  # one position at a time
        y, s, tl = _stepped(cfg, layer, u[:, i], s, tl)
        steps.append(y)
        if i == t - 4:  # where row 1's chunk stopped: the padding moved neither its state nor its tail
            np.testing.assert_allclose(state_chunked[1], s[1], **MIXER)
            np.testing.assert_allclose(tail_chunked[1], tl[1], **MIXER)
    np.testing.assert_allclose(jnp.stack(steps, axis=1), want[:, carried:], **MIXER)
    np.testing.assert_allclose(state_chunked[0], s[0], **MIXER)
    np.testing.assert_allclose(tail_chunked[0], tl[0], **MIXER)
    assert float(jnp.abs(s).max()) > 0.05 and float(jnp.abs(want).max()) > 0.01  # the state and the branch are there
    assert attn_ops.traced("gdn") == "chunk+step"


@pytest.mark.parametrize("c", [4, 16, 64])
def test_the_solve_is_the_inverse_whatever_the_keys(c):
    """``(I + A)^-1`` by blocks against ``numpy.linalg.inv`` in float64: random strictly lower ``A``, and
    the worst case for a power series, every key the same (``A`` all ones below the diagonal, whose
    powers reach 1e17 before they cancel)."""
    rng = np.random.default_rng(c)
    for a in (np.tril(rng.standard_normal((3, c, c)), -1), np.tril(np.ones((1, c, c)), -1)):
        got = gdn._inv_unit_lower(jnp.asarray(a, jnp.float32))
        np.testing.assert_allclose(got, np.linalg.inv(np.eye(c) + a), atol=2e-4 * max(1.0, np.abs(np.linalg.inv(np.eye(c) + a)).max()))
    with pytest.raises(ValueError, match="power of two"):
        gdn._inv_unit_lower(jnp.zeros((48, 48)))


def test_heads_forget_at_every_rate(model):
    """The drawn ``A_log`` and ``dt_bias`` at work: one position's mark on the state is gone in a
    few steps on some heads and all but whole 30 steps on on others."""
    cfg, params = model
    layer = _layer(params, 0)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 31, cfg.dim), jnp.float32)
    _, with_first, _ = _chunked(cfg, layer, u, *_empty(cfg, 1))
    _, after_first, tail = _chunked(cfg, layer, u[:, :1], *_empty(cfg, 1))
    _, without, _ = _chunked(cfg, layer, u[:, 1:], jnp.zeros_like(after_first), tail)
    kept = jnp.linalg.norm((with_first - without)[0], axis=(1, 2)) / jnp.linalg.norm(after_first[0], axis=(1, 2))
    assert float(kept.min()) < 1e-2 and float(kept.max()) > 0.3, kept


# -- (b) the attention, the experts' share, the uncached forward -----------------------------


def test_gated_attention_is_the_references(model):
    """One attending layer's mixer alone: the gate made beside the query, zero-centred norms over each
    head of ``q`` and ``k``, rotary over a head's first quarter, ``o * sigmoid(gate)``."""
    cfg, params = model
    layer = _layer(params, 3)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.dim), jnp.float32)
    cos, sin = llama.rope_table(cfg, 24)
    assert cos.shape == (24, 4) and cfg.rope_dim == 8 and cfg.head_dim == 32
    got = llama._gqa_attention(cfg, None, cos, sin, u, dict(layer, attn_kind="full"))
    np.testing.assert_allclose(got, ref.gated_attention(u, layer, CONFIG, None), **MIXER)


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """**The share tied to the model.** The feed-forward of one layer with all 16 experts given, by the
    reference, against the four chips' parts by the program (each holding 4 experts, ``experts_held_from``
    0, 4, 8, 12, all behind the one 16-wide router): the routed parts add up and the shared expert,
    which every chip computes alike, counts once."""
    cfg, params = model
    rng = jax.random.PRNGKey(11)
    d, f, E = cfg.dim, cfg.expert_width, 16
    whole = {
        name: jax.random.normal(jax.random.fold_in(rng, i), shape, jnp.float32) * fan_in**-0.5
        for i, (name, shape, fan_in) in enumerate((("w_gate", (E, d, f), d), ("w_up", (E, d, f), d), ("w_down", (E, f, d), f)))
    }
    layer = _layer(params, 2)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 9, d), jnp.float32)
    want = ref.routed(x, {**layer, **whole}, dict(CONFIG, experts_held_from=0), None) + ref.shared(x, layer, None)
    parts = []
    for first in (0, 4, 8, 12):
        share = models.program_config(dict(CONFIG, experts_held_from=first), max_seq=128, remat=False)
        held = {name: w[first : first + 4] for name, w in whole.items()}
        out, aux = moe.moe_ffn(share, {**layer, **held}, x)
        assert float(aux[llama.AUX_OVERFLOW]) == 0.0
        parts.append(out - ref.shared(x, layer, None))  # this chip's routed part
    np.testing.assert_allclose(sum(parts) + ref.shared(x, layer, None), want, **MIXER)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)  # every share adds something


def test_forward_logits_match_the_reference(model):
    cfg, params = model
    toks = _tokens(5, (2, 50))
    want = ref.logits(params, toks, CONFIG)
    np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)
    assert float(jnp.std(want)) > 0.5  # logits of order 1


def test_loss_matches_the_references_mean_nll(model):
    cfg, params = model
    toks = _tokens(8, (2, 41))
    np.testing.assert_allclose(
        llama.loss_fn(params, {"tokens": toks}, cfg) - cfg.router_aux_coef * llama.forward_features(params, toks[:, :-1], cfg)[1][llama.AUX_BALANCE],
        ref.mean_nll(params, toks, CONFIG), atol=2e-4, rtol=2e-4,
    )  # fmt: skip


WRONG = ["bfloat16", "no 1 +", "gate before norm", "rotary over the whole head", "no beta", "shared expert ungated",
         "top-k not renormalised", "state lost between sub-chunks", "no attention gate"]  # fmt: skip


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_layer_is_caught(model, wrong, monkeypatch):
    """The comparison above, with the program broken in one place or the reference given the
    nearest precision below, does not hold."""
    cfg, params = model
    toks = _tokens(5, (2, 50))
    want = ref.logits(params, toks, CONFIG)
    if wrong == "bfloat16":
        want = ref.logits(jax.tree.map(lambda w: w.astype(jnp.bfloat16), params), toks, CONFIG)
    elif wrong == "no 1 +":
        cfg = models.program_config(CONFIG, max_seq=128, remat=False, gdn_chunk=8, norm_unit_offset=False)
    elif wrong == "gate before norm":

        def gate_first(cfg, layer, o, z):
            o = o * jax.nn.silu(z.reshape(o.shape))
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps) * layer["gdn_norm"]
            return o.reshape(*o.shape[:-2], -1) @ layer["gdn_out"]

        monkeypatch.setattr(gdn, "finish", gate_first)
    elif wrong == "rotary over the whole head":
        cfg = models.program_config(CONFIG, max_seq=128, remat=False, gdn_chunk=8, rotary_dim=0)
    elif wrong == "no beta":
        rates = gdn._rates
        monkeypatch.setattr(gdn, "_rates", lambda layer, b, a: (jnp.ones_like(rates(layer, b, a)[0]), rates(layer, b, a)[1]))
    elif wrong == "shared expert ungated":
        params = dict(params, layers=dict(params["layers"], w_shared_gate=jnp.zeros_like(params["layers"]["w_shared_gate"])))
    elif wrong == "top-k not renormalised":
        # (ISSUE 49 asks for "softmax after top-k": with norm_topk_prob that is the published function itself, the softmax
        # over the chosen logits IS the chosen probabilities over their sum; the fault that can be planted is the missing sum)
        route = moe._route

        def not_renormalised(cfg, layer, x):
            scores, chosen, _ = route(cfg, layer, x)
            return scores, chosen, jnp.take_along_axis(scores, chosen, axis=-1)

        monkeypatch.setattr(moe, "_route", not_renormalised)
    elif wrong == "state lost between sub-chunks":
        whole = gdn.chunk_core

        def piece_by_piece(cfg, layer, qkv, b, a, state, tail, valid=None):  # each from the state the first began with
            outs = []
            for i in range(0, qkv.shape[1], 8):
                o, _, tail = whole(cfg, layer, qkv[:, i : i + 8], b[:, i : i + 8], a[:, i : i + 8], state, tail)
                outs.append(o)
            return jnp.concatenate(outs, axis=1), state, tail

        monkeypatch.setattr(gdn, "chunk_core", piece_by_piece)
    else:
        monkeypatch.setattr(llama, "gate_heads", lambda out, gate: out)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)


# -- (c) the serving programs and the engine --------------------------------------------------


def _tables(rows, bpr):
    return {"full": jnp.arange(1, 1 + rows * bpr, dtype=jnp.int32).reshape(rows, bpr), "state": jnp.arange(1, rows + 1, dtype=jnp.int32)}


def test_chunks_behind_a_carried_state_then_decode_give_the_references_logits(model, monkeypatch):
    """The serving programs themselves, their sampling replaced by the identity so that they hand
    back logits: two sequences' first chunks (from zeros: the rows are dirtied first), a chunk behind
    those, then three decode steps, each against the reference's full forward at the same position.
    The pools hold the two attending layers' K/V, the store the six linear layers' rows."""
    cfg, params = model
    monkeypatch.setattr(gen, "_sample_rows", lambda logits, keys, temps: logits)
    monkeypatch.setattr(attn_ops, "TRACED", {})
    rows, bs, bpr = 2, 16, 8
    toks = _tokens(6, (rows, 64))
    want = ref.logits(params, toks, CONFIG)
    pools = gen.init_kv_pools(cfg, 1 + rows * bpr, bs, slots=rows)
    assert pools["full"]["k"].shape == (2, 1 + rows * bpr, bs, 2, 32) and pools["gdn"]["state"].shape == (6, 3, 4, 16, 16)
    pools["gdn"] = jax.tree.map(lambda p: p + 3, pools["gdn"])  # a tenant before left its state behind
    tables = _tables(rows, bpr)
    keys, temps = jnp.zeros((rows, 2), jnp.uint32), jnp.zeros((rows,), jnp.float32)
    first = jnp.asarray([32, 16], jnp.int32)
    more = jnp.asarray([29, 40], jnp.int32)
    lg, pools = gen.paged_prefill_chunk(params, toks[:, :32], jnp.zeros_like(first), first, tables, pools, cfg, keys, temps)
    np.testing.assert_allclose(lg, want[jnp.arange(rows), first - 1], **LOGITS)
    chunk = jnp.take_along_axis(toks, jnp.minimum(first[:, None] + jnp.arange(48), 63), axis=1)
    lg, pools = gen.paged_prefill_chunk(params, chunk, first, more, tables, pools, cfg, keys, temps)
    at = first + more  # the position the next token goes to
    np.testing.assert_allclose(lg, want[jnp.arange(rows), at - 1], **LOGITS)
    for _ in range(3):
        lg, pools = gen.paged_decode_step(params, toks[jnp.arange(rows), at], at, tables, pools, cfg, keys, temps)
        np.testing.assert_allclose(lg, want[jnp.arange(rows), at], **LOGITS)
        at = at + 1
    np.testing.assert_array_equal(pools["gdn"]["state"][:, 0], 3.0)  # the trash row: nobody's
    assert attn_ops.traced("gdn") == "chunk+step"


def test_two_heads_of_256_lie_in_the_pool_as_four_rows_of_128(monkeypatch):
    """The published attention's cache heads (2 of 256) are too few for the decode kernel's tiles: the pool
    holds a position as 4 rows of 128 (``cache_row``), the XLA paths read them back as heads, and the served
    logits are the uncached forward's; the kernel itself is eligible at these shapes and only these."""
    from torchx_tpu.ops import paged_attention as pa

    cfg = moe.MoEConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2, attn_head_dim=256, ffn_dim=32, max_seq=64, dtype=jnp.float32,
        remat=False, layer_types=("linear", "linear", "linear", "full"), gdn_heads=2, gdn_key_heads=1, gdn_head_dim=8, gdn_chunk=8,
        rotary_dim=64, attn_output_gate=True, qk_norm=True, norm_unit_offset=True, norm_eps=1e-6, n_experts=4, top_k=2,
        expert_ffn_dim=16, n_shared_experts=1, shared_expert_gate=True, capacity_factor=0.0)  # fmt: skip
    assert cfg.cache_row == (4, 128) and llama.llama_tiny().cache_row == (2, 16)
    assert llama.llama_tiny(n_kv_heads=4, attn_head_dim=256, dim=1024).cache_row == (4, 256)  # enough heads: as they are
    params = llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda w: w + 0.1 * jax.random.normal(jax.random.PRNGKey(1), w.shape, w.dtype) if w.ndim == 2 else w, params)
    monkeypatch.setattr(gen, "_sample_rows", lambda logits, keys, temps: logits)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, 64)
    want = llama.forward(params, toks, cfg)
    pools = gen.init_kv_pools(cfg, 9, 16, slots=2)
    assert pools["full"]["k"].shape == (1, 9, 16, 4, 128)
    tables, n = _tables(2, 4), jnp.asarray([33, 20], jnp.int32)
    keys, temps = jnp.zeros((2, 2), jnp.uint32), jnp.zeros((2,), jnp.float32)
    lg, pools = gen.paged_prefill_chunk(params, toks[:, :36], jnp.zeros_like(n), n, tables, pools, cfg, keys, temps)
    np.testing.assert_allclose(lg, want[jnp.arange(2), n - 1], **LOGITS)
    lg, pools = gen.paged_decode_step(params, toks[jnp.arange(2), n], n, tables, pools, cfg, keys, temps)
    np.testing.assert_allclose(lg, want[jnp.arange(2), n], **LOGITS)
    bf16 = jnp.dtype(jnp.bfloat16)
    assert pa.kernel_eligible((128, 16, 256), (16897, 16, 4, 128), bf16, bf16, "tpu")
    assert not pa.kernel_eligible((128, 16, 256), (16897, 16, 2, 256), bf16, bf16, "tpu")  # as the heads are: two rows a position
    assert not pa.kernel_eligible((128, 16, 512), (16897, 16, 4, 128), bf16, bf16, "tpu")  # a head of four rows: not built
    assert pa.kernel_eligible((64, 20, 128), (8449, 16, 4, 128), bf16, bf16, "tpu")  # falcon-h1's, as before


def _served_gaps(params, req):
    seq = list(req.prompt) + req.generated
    n_p, n_g = len(req.prompt), len(req.generated)
    lg = ref.logits(params, jnp.asarray([seq]), CONFIG)[0, n_p - 1 : n_p - 1 + n_g]
    got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(-1) - got)


def _spy(engine):
    """Every step the engine enqueues, in order: the decode part's state rows, the chunk's ``(start,
    real tokens, slot or -1)`` and state row where it carries one, and the store's rows of the slots
    that were mid-prompt, before and after the program."""
    log = []

    def spied(real):
        def program(params, tokens, prev, positions, tables, pools, *rest):
            feeding = [i for i, st in enumerate(engine._slots) if st is not None and st.feeding is not None]
            rows = np.asarray([i + 1 for i in feeding], np.int32)
            before = jax.tree.map(lambda p: np.asarray(p[:, rows]), pools["gdn"])
            nxt, new = real(params, tokens, prev, positions, tables, pools, *rest)
            after = jax.tree.map(lambda p: np.asarray(p[:, rows]), new["gdn"])
            chunk = (tuple(int(v) for v in np.asarray(rest[3])), int(rest[4]["state"][0])) if len(rest) > 2 else None
            log.append({"decode_rows": np.asarray(tables["state"]), "chunk": chunk, "feeding": feeding, "before": before, "after": after})
            return nxt, new

        return program

    engine._decode, engine._decode_chunk = spied(engine._decode), spied(engine._decode_chunk)
    return log


@pytest.fixture(scope="module")
def served(model):
    """Three slots, chunks of 16, seven requests: slots are reused by later requests, prompts of one
    to three chunks are fed while others decode, the pool is short so that the youngest is preempted
    and fed again, and one request stops at an EOS with a step in flight."""
    cfg, params = model
    engine = ServeEngine(params, cfg, max_slots=3, block_size=16, num_blocks=10, max_prefill_batch=2, chunk_width=CHUNK)
    log = _spy(engine)
    lengths, new = [37, 20, 50, 33, 5, 41, 16], [20, 30, 10, 12, 9, 25, 14]
    reqs = [ServeRequest(_tokens(20 + i, (n,)).tolist(), max_new_tokens=m) for i, (n, m) in enumerate(zip(lengths, new))]
    # the third token request 4 would have drawn anyway ends it early: learnt with its next step already in flight
    probe = ServeEngine(params, cfg, max_slots=1, block_size=16, num_blocks=9, chunk_width=CHUNK).start()
    try:
        reqs[4].eos_id = probe.generate(reqs[4].prompt, 3, timeout=300).generated[2]
    finally:
        probe.stop()
    for r in reqs:
        engine.submit(r)
    engine.start()
    try:
        for r in reqs:
            assert r.wait(600) and not r.error, r.error
        stats = engine.stats()
    finally:
        engine.stop()
    return engine, reqs, log, stats


def test_engine_serves_the_references_tokens(served, model):
    """Every token served (a slot's first tenant or a later one, fed beside decoding slots, recomputed
    after a preemption) has the reference's largest logit at its position or one within 1e-4 of it."""
    _, params = model
    _, reqs, log, stats = served
    assert stats["requests_done"] == 7 > stats["max_slots"] and stats["preemptions"] >= 1
    assert len(reqs[4].generated) == 3 and stats["tokens_discarded"] >= 1  # the EOS, and the step behind it
    assert any(step["chunk"] and len(set(step["decode_rows"]) - {0}) == 2 for step in log)  # one fed while two decode
    for req in reqs:
        assert _served_gaps(params, req).max() < 1e-4


def test_a_state_row_has_one_writer_a_step(served):
    """A step's decode part addresses slot ``i``'s own row ``i + 1`` or the trash row 0; a slot that is
    mid-prompt is addressed by its chunk alone, and a step that carries no chunk of its prompt leaves its
    rows of the store as they were, bit for bit."""
    _, _, log, _ = served
    chunked = moved = 0
    for step in log:
        rows = step["decode_rows"]
        assert all(r in (0, i + 1) for i, r in enumerate(rows))
        assert not {i + 1 for i in step["feeding"]} & set(rows.tolist())
        written = step["chunk"][1] if step["chunk"] else None
        for at, slot in enumerate(step["feeding"]):
            same = all(np.array_equal(step["before"][k][:, at], step["after"][k][:, at]) for k in ("state", "conv"))
            assert same == (slot + 1 != written)
            moved += not same
        if step["chunk"]:
            (start, n, _), row = step["chunk"]
            assert row - 1 in step["feeding"] and row not in rows
            chunked += 1
    assert chunked == moved >= 14  # every chunk moved its slot's rows and nobody else's


def test_state_is_counted_over_the_layers_that_have_it_and_not_cached_or_handed_off(served, model):
    from torchx_tpu.serve.slot_cache import LinearStateCache

    cfg, params = model
    engine, _, _, stats = served
    per_slot = 6 * (4 * 16 * 16 * 4 + 3 * 128 * 4)  # six linear layers: S [4, 16, 16] float32 + three inputs of 2 x 32 + 64
    assert isinstance(engine.cache, LinearStateCache) and set(engine.cache.pools) == {"full", "gdn"}
    assert stats["state_bytes_per_slot"] == per_slot and stats["state_bytes"] == 4 * per_slot  # three slots + the trash row
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 4  # the two attending layers' alone
    assert engine.cache.span_attrs([])["state_bytes_per_slot"] == per_slot
    assert engine.cache.prefix_cache is None and "recurrent state" in stats["prefix_cache_off"] and "prefix_cache" not in stats
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.submit(ServeRequest([1, 2, 3], max_new_tokens=1, prefill_only=True))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.submit_prefilled(ServeRequest([1, 2, 3], max_new_tokens=2), np.zeros((2, 1, 16, 2, 32)), np.zeros((2, 1, 16, 2, 32)), 3, 7)
    with pytest.raises(NotImplementedError, match="paged path"):
        gen.generate(params, jnp.zeros((1, 4), jnp.int32), cfg, 2)


def test_what_linear_layers_do_not_stand_beside_is_refused():
    linear = dict(n_layers=4, layer_types=("linear", "linear", "linear", "full"), gdn_heads=4, gdn_key_heads=2, gdn_head_dim=8)
    llama.llama_tiny(**linear)
    for more in (dict(layer_types=("linear", "sliding", "linear", "full"), sliding_window=8), dict(hc_mult=2, hc_sinkhorn_iters=2),
                 dict(kernels="pallas"), dict(gdn_key_heads=3), dict(gdn_head_dim=0), dict(layer_types=("linear",) * 4),
                 dict(ssm_heads=4, ssm_head_dim=8, ssm_state=16), dict(gdn_heads=0)):  # fmt: skip
        with pytest.raises(ValueError):
            llama.llama_tiny(**{**linear, **more})
    with pytest.raises(ValueError, match="gdn_heads go together"):
        llama.llama_tiny(gdn_heads=4, gdn_key_heads=2, gdn_head_dim=8)
    with pytest.raises(ValueError, match="rotary_dim"):
        llama.llama_tiny(rotary_dim=7)
    with pytest.raises(ValueError, match="shared_expert_gate"):
        moe.moe_tiny(shared_expert_gate=True)
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [1]), ("rope_scaling", {"type": "yarn"}), ("linear_value_head_dim", 8)):
        with pytest.raises(ValueError):
            models.program_config(dict(CONFIG, **{key: value}))


def test_program_init_lays_out_the_kinds_tree(model):
    cfg, _ = model
    theirs = jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    mine = jax.tree.map(lambda leaf: leaf[0], models.weight_shapes(CONFIG), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    assert jax.tree.map(lambda w: tuple(w.shape), theirs) == mine
    specs = llama.model_fns(cfg)[1](cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict)) == jax.tree.structure(mine, is_leaf=lambda x: not isinstance(x, dict))
    assert cfg.param_count() == sum(int(np.prod(s)) for s in jax.tree.leaves(mine, is_leaf=lambda x: isinstance(x, tuple)))


# -- (d) the decode kernel in the interpreter ---------------------------------------------------


def test_the_step_kernel_is_the_jax_numpy_step():
    """``ops/gdn_step_kernel.py`` in the interpreter against ``gdn._advance``: a stack of two layers' rows,
    slots on their own rows and two on the trash row, at the narrowest shapes the kernel takes (128 x 128 a
    head, blocks of 8 value heads over 4 key heads). The layer it is not given and the rows nobody names
    keep every bit."""
    from torchx_tpu.ops.gdn_step_kernel import gdn_step_pallas

    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    store, rows = normal(2, 5, 16, 128, 128), jnp.asarray([1, 0, 3, 0], jnp.int32)
    decay, beta = (jnp.asarray(rng.uniform(0, 1, (4, 16)), jnp.float32) for _ in range(2))
    q, k, v = normal(4, 8, 128) * 128**-0.5, normal(4, 8, 128) * 128**-0.5, normal(4, 16, 128)
    assert gdn.kernel_eligible(store.shape, 8, "tpu") and not gdn.kernel_eligible(store.shape, 8, "cpu")
    assert not gdn.kernel_eligible((6, 4, 4, 16, 16), 2, "tpu")  # this file's test widths: jax.numpy's
    o, new = gdn_step_pallas(store, rows, decay, beta, q, k, v, layer=jnp.int32(1), interpret=True)
    rep = lambda x: jnp.repeat(x, 2, axis=1)  # noqa: E731
    want_o, want = gdn._advance(store[1, rows], decay, beta, rep(q), rep(k), v)
    np.testing.assert_allclose(o, want_o, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(new[1, jnp.asarray([1, 3])], want[jnp.asarray([0, 2])], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(new[0], store[0])
    np.testing.assert_array_equal(new[1, jnp.asarray([2, 4])], store[1, jnp.asarray([2, 4])])


def test_decode_rows_through_the_kernel_is_decode_rows_without(monkeypatch):
    """The decode part with the backend said to be a TPU and the kernel it then picks run in the
    interpreter, against the same call on the CPU's path: the read-out, and a store in which the slot on
    the trash row (mid-prompt: its chunk's to write) kept its own row."""
    from torchx_tpu.ops import gdn_step_kernel

    cfg = llama.llama_tiny(dim=128, n_layers=4, layer_types=("linear", "linear", "linear", "full"), gdn_heads=8, gdn_key_heads=4, gdn_head_dim=128)
    layer = {name: w[1] for name, w in llama.init_params(cfg, jax.random.PRNGKey(1))["mixers"]["state"].items()}
    store = jax.tree.map(lambda p: jax.random.normal(jax.random.PRNGKey(2), p.shape, jnp.float32).astype(p.dtype), gdn.init_store(cfg, 4))
    u = jax.random.normal(jax.random.PRNGKey(3), (3, cfg.dim), jnp.float32)
    rows = jnp.asarray([1, 0, 3], jnp.int32)
    qkv, _, b, a = gdn.project(cfg, layer, u)
    monkeypatch.setattr(attn_ops, "TRACED", {})
    want_o, want = gdn.decode_rows(cfg, layer, qkv, b, a, store, jnp.int32(1), rows)
    assert attn_ops.traced("gdn") == "step"
    rule = gdn.kernel_eligible
    monkeypatch.setattr(gdn, "kernel_eligible", lambda shape, key_heads, _backend: rule(shape, key_heads, "tpu"))
    real = gdn_step_kernel.gdn_step_pallas
    monkeypatch.setattr(gdn_step_kernel, "gdn_step_pallas", lambda *args, **kw: real(*args, **kw, interpret=True))
    got_o, got = gdn.decode_rows(cfg, layer, qkv, b, a, store, jnp.int32(1), rows)
    assert attn_ops.traced("gdn") == "step+step_pallas"
    keep = jnp.asarray([0, 2])
    np.testing.assert_allclose(got_o[keep], want_o[keep], atol=1e-4, rtol=1e-5)
    for name in ("state", "conv"):
        np.testing.assert_array_equal(got[name][0], store[name][0])  # the other layer
        np.testing.assert_array_equal(got[name][1, 2], store[name][1, 2])  # the slot that does not move
        np.testing.assert_allclose(got[name][1, jnp.asarray([1, 3])], want[name][1, jnp.asarray([1, 3])], atol=1e-5, rtol=1e-5)


# -- (e) a model without linear layers is the program it was ------------------------------------

OLDER = {
    "llama": lambda: llama.llama_tiny(max_seq=64),
    "moe": lambda: moe.moe_tiny(max_seq=64),
    "sliding_qk_norm": lambda: llama.llama_tiny(
        max_seq=64, n_layers=4, layer_types=("sliding", "sliding", "sliding", "full"), sliding_window=8, qk_norm=True,
        rope_full_layers=False),
    "sliding_moe_held": lambda: moe.moe_tiny(
        max_seq=64, n_layers=5, layer_types=("sliding", "sliding", "sliding", "full", "sliding"), sliding_window=8, qk_norm=True,
        rope_full_layers=False, n_experts=8, experts_held=2, experts_held_from=2, top_k=3, expert_ffn_dim=32, n_shared_experts=1,
        router_score="sigmoid", router_bias=True, routed_scale=2.5, n_dense_layers=1, capacity_factor=0.0),
    "mla_moe": lambda: moe.moe_tiny(
        max_seq=64, n_layers=3, n_kv_heads=4, ffn_dim=96, n_experts=8, top_k=3, expert_ffn_dim=32, n_shared_experts=2,
        router_score="sigmoid", router_bias=True, routed_scale=2.446, n_dense_layers=1, capacity_factor=0.0,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16),
    "mla_moe_hc": lambda: moe.moe_tiny(
        max_seq=64, n_layers=3, n_kv_heads=4, ffn_dim=96, n_experts=8, top_k=3, expert_ffn_dim=32, n_shared_experts=2,
        router_score="sigmoid", router_bias=True, routed_scale=2.446, n_dense_layers=1, capacity_factor=0.0,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, q_lora_rank=24, hc_mult=2, hc_sinkhorn_iters=3),
    "mixer": lambda: llama.llama_tiny(
        max_seq=64, ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=16, key_multiplier=0.5,
        mlp_multipliers=(0.7, 0.4)),
    "eva": lambda: llama.llama_tiny(
        max_seq=512, eva_window=256, eva_chunk=16, norm_unit_offset=True, fp32_skip_add=True, pred_heads=2),
}  # fmt: skip
#: sha256 of the jaxprs below as the parent commit (PR 48's tree, 9c2bd17) traced them: the defaults of the fields this
#: PR added leave the seven older kinds alone. A PR that changes what these programs compute on purpose records its own.
#: PR 51 did, for the uncached forward of the grouped-query kinds alone (whole-head rotation, ``tests/test_rope_whole.py``);
#: every serving step's digest is the parent's.
AT_THE_PARENT = {
    'eva': ('816a61834c32c31c', '75d99f0cd44cec73'),
    'llama': ('7d6f6b12458e6419', '49b8059943fd7a24'),
    'mixer': ('e9df3df047d7974b', '85ad609ce1ecd246'),
    'mla_moe': ('e2d4202dd2cccb37', '9656167500bbd0a9'),
    'mla_moe_hc': ('b3db464a6d2a482e', '482c3837461f078d'),
    'moe': ('5020f16ef7434133', 'a4cba197c5f6b94c'),
    'sliding_moe_held': ('76e2978bec3cf257', '269e363abcb99d4f'),
    'sliding_qk_norm': ('3547e1e74044f00e', 'a7e55fa8d2330bd8'),
}


def _digests(cfg):
    """(the mixed serving step, the uncached forward) of ``cfg`` as jaxprs, hashed."""
    from torchx_tpu.serve.slot_cache import slot_cache

    slots, bs, width = 3, 16, 32
    params = jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    made = {}

    def cache_pools():
        made["cache"] = slot_cache(cfg, max_slots=slots, block_size=bs, num_blocks=None, num_window_blocks=None, max_prefill_batch=2,
                                   prefix_cache=False, prefix_cache_reserve=0.0)  # fmt: skip
        return made["cache"].pools

    pools = jax.eval_shape(cache_pools)  # the engine's own cache of the kind: its pools, and the tables it hands the programs
    shapes = lambda tree: jax.tree.map(lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), tree)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    step = jax.make_jaxpr(
        lambda p, tok, pos, tab, chunk, start, n, ctab, pl, keys, temps: gen.paged_decode_chunk_step(
            p, tok, pos, tab, chunk, start, n, ctab, pl, cfg, keys, temps)
    )(params, i32(slots), i32(slots), shapes(made["cache"].step_tables([], [])), i32(width), i32(), i32(), shapes(made["cache"].chunk_tables(0)),
      pools, jax.ShapeDtypeStruct((slots + 1, 2), jnp.uint32), jax.ShapeDtypeStruct((slots + 1,), jnp.float32))  # fmt: skip
    forward = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(params, i32(2, 24))
    return tuple(hashlib.sha256(str(j).encode()).hexdigest()[:16] for j in (step, forward))


@pytest.mark.parametrize("kind", sorted(OLDER))
def test_an_older_kind_traces_the_jaxpr_it_traced_at_the_parent(kind):
    assert _digests(OLDER[kind]()) == AT_THE_PARENT[kind]
