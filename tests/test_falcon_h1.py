"""A Mamba-2 mixer beside attention in every layer (Falcon-H1's block) against the
benchmark's plain reference (``benchmark/reference/falcon_h1.py``: float32, the recurrence a
``lax.scan`` over positions, no chunks, no cache), on the CPU at tiny widths with seeded
weights, the published multipliers, and ``A_log``, ``dt_bias`` and the convolution's bias
drawn so that heads forget at every rate.

Tolerances. Program and reference both compute in float32 here and differ in the order of
their sums (a chunk's positions multiplied together against one at a time, the decays as
differences of running sums against products): the mixer's outputs of size ~1 agree to
``2e-5``, logits of size ~3 to ``2e-4 + 2e-4 |x|``. A reference in bfloat16 or one missing
piece (a state lost between two chunks, no gate, no skip ``D x``, a multiplier left out) moves logits by 1e-2 or more: ``test_a_wrong_layer_is_caught``
holds the comparison to that.
"""

from __future__ import annotations

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import models
from benchmark.reference import falcon_h1 as ref
from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama, moe, ssm
from torchx_tpu.serve.engine import ServeEngine, ServeRequest

attn_ops = importlib.import_module("torchx_tpu.ops.attention")
LOGITS = dict(atol=2e-4, rtol=2e-4)
MIXER = dict(atol=2e-5, rtol=2e-5)

CONFIG = {  # the published keys at test widths, the multipliers as published
    "model": "falcon_h1", "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "embedding_multiplier": 5.656854249492381, "lm_head_multiplier": 0.0078125,
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 0.0375, "key_multiplier": 0.011048543456039804,
    "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "tie_word_embeddings": False,
    "torch_dtype": "float32", "assumed_ssm_in_gain": 16.0, "assumed_w_down_gain": 4.0, "assumed_A_log_std": 2.0,
    "assumed_dt_bias_std": 3.0, "assumed_conv_bias_std": 0.1,
}  # fmt: skip
CHUNK = 16  # the engine's chunk width here, and the scan's: a prompt of 40 crosses both


@pytest.fixture(scope="module")
def model():
    cfg = models.program_config(CONFIG, max_seq=128, remat=False, ssm_chunk=CHUNK)
    return cfg, models.make_weights(CONFIG, 2147483659)


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, CONFIG["vocab_size"])


def _layer(params, i=0):
    return {name: w[i] for name, w in params["layers"].items()}


def _empty(cfg, rows):
    return (jnp.zeros((rows, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
            jnp.zeros((rows, cfg.ssm_conv - 1, cfg.ssm_conv_width), cfg.dtype))  # fmt: skip - as the two forms take them


# -- (a) the mixer's two forms and the reference's scan over positions ---------------------


@pytest.mark.parametrize("carried", [0, 5, 23])
def test_chunked_scan_is_the_recurrence_is_the_references_scan(model, carried):
    """40 positions of two sequences: the first ``carried`` through the chunked form
    from an empty state, the rest from what that left (the state and the convolution's
    tail) through the chunked form with a padded tail and, apart, one position at a time.
    Each gives what the reference's mixer gives over all 40 from nothing, and both forms
    leave the same state behind."""
    cfg, params = model
    layer, t = _layer(params, 1), 40
    u = jax.random.normal(jax.random.PRNGKey(3), (2, t, cfg.dim), jnp.float32)
    want = ref.mixer(u * CONFIG["ssm_in_multiplier"], layer, CONFIG, None) * CONFIG["ssm_out_multiplier"]
    state, tail = _empty(cfg, 2)
    if carried:
        head, state, tail = ssm.scan(cfg, layer, u[:, :carried], state, tail)
        np.testing.assert_allclose(head, want[:, :carried], **MIXER)
    # the chunked form over what is left, right-padded: row 1 stops three positions early
    rest = jnp.pad(u[:, carried:], ((0, 0), (0, 7), (0, 0)), constant_values=9.0)
    real = jnp.asarray([t - carried, t - carried - 3])
    valid = jnp.arange(rest.shape[1])[None, :] < real[:, None]
    out, state_scan, tail_scan = ssm.scan(cfg, layer, rest, state, tail, valid)
    np.testing.assert_allclose(out[0, : t - carried], want[0, carried:], **MIXER)
    np.testing.assert_allclose(out[1, : t - carried - 3], want[1, carried : t - 3], **MIXER)
    # one position at a time
    s, tl, steps = state, tail, []
    for i in range(carried, t):
        y, s, tl = ssm.step(cfg, layer, u[:, i], s, tl)
        steps.append(y)
        if i == t - 4:  # where row 1's chunk stopped: the padding moved neither its state nor its tail
            np.testing.assert_allclose(state_scan[1], s[1], **MIXER)
            np.testing.assert_allclose(tail_scan[1], tl[1], **MIXER)
    np.testing.assert_allclose(jnp.stack(steps, axis=1), want[:, carried:], **MIXER)
    np.testing.assert_allclose(state_scan[0], s[0], **MIXER)
    np.testing.assert_allclose(tail_scan[0], tl[0], **MIXER)
    assert float(jnp.abs(s).max()) > 0.1 and float(jnp.abs(want).max()) > 0.01  # the state and the branch are there


def test_heads_forget_at_every_rate(model):
    """The drawn ``A_log`` and ``dt_bias`` at work: one position's mark on the state is gone
    in a few steps on some heads and all but whole 30 steps on on others."""
    cfg, params = model
    layer = _layer(params)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 31, cfg.dim), jnp.float32)
    _, with_first, _ = ssm.scan(cfg, layer, u, *_empty(cfg, 1))
    _, after_first, tail = ssm.scan(cfg, layer, u[:, :1], *_empty(cfg, 1))
    _, without, _ = ssm.scan(cfg, layer, u[:, 1:], jnp.zeros_like(after_first), tail)
    kept = jnp.linalg.norm((with_first - without)[0], axis=(1, 2)) / jnp.linalg.norm(after_first[0], axis=(1, 2))
    assert float(kept.min()) < 1e-3 and float(kept.max()) > 0.5, kept


# -- (b) the uncached forward ----------------------------------------------------------------


def test_forward_logits_match_the_reference(model):
    cfg, params = model
    toks = _tokens(5, (2, 50))
    want = ref.logits(params, toks, CONFIG)
    np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)
    assert float(jnp.std(want)) > 0.5  # logits of order 1: the drawn head undoes its multiplier


def test_loss_matches_the_references_mean_nll(model):
    cfg, params = model
    toks = _tokens(8, (2, 41))
    np.testing.assert_allclose(
        llama.loss_fn(params, {"tokens": toks}, cfg), ref.mean_nll(params, toks, CONFIG), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize(
    "wrong", ["bfloat16", "state lost between chunks", "no gate", "no skip", "no ssm_in_multiplier", "no key_multiplier"]
)
def test_a_wrong_layer_is_caught(model, wrong, monkeypatch):
    """The comparison above, with the program broken in one place or the reference given
    the nearest precision below, does not hold."""
    cfg, params = model
    toks = _tokens(5, (2, 50))
    want = ref.logits(params, toks, CONFIG)
    if wrong == "bfloat16":
        want = ref.logits(jax.tree.map(lambda w: w.astype(jnp.bfloat16), params), toks, CONFIG)
    elif wrong == "state lost between chunks":
        whole = ssm.scan_core

        def chunk_by_chunk(cfg, layer, xbc, dt, state, tail, valid=None):  # each from the state the first began with
            ys = []
            for i in range(0, xbc.shape[1], CHUNK):
                y, _, tail = whole(cfg, layer, xbc[:, i : i + CHUNK], dt[:, i : i + CHUNK], state, tail)
                ys.append(y)
            return jnp.concatenate(ys, axis=1), state, tail

        monkeypatch.setattr(ssm, "scan_core", chunk_by_chunk)
    elif wrong == "no gate":
        gated = ssm.finish
        monkeypatch.setattr(ssm, "finish", lambda cfg, layer, y, z: gated(cfg, layer, y, jnp.full_like(z, 9.0)))
    elif wrong == "no skip":
        params = dict(params, layers=dict(params["layers"], ssm_D=jnp.zeros_like(params["layers"]["ssm_D"])))
    elif wrong == "no ssm_in_multiplier":
        cfg = models.program_config(CONFIG, max_seq=128, remat=False, ssm_chunk=CHUNK, ssm_in_multiplier=1.0)
    else:
        cfg = models.program_config(CONFIG, max_seq=128, remat=False, ssm_chunk=CHUNK, key_multiplier=1.0)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)


# -- (c) the serving programs and the engine --------------------------------------------------


def test_chunks_behind_a_carried_state_then_decode_give_the_references_logits(model, monkeypatch):
    """The serving programs themselves, their sampling replaced by the identity so that
    they hand back logits: two sequences' first chunks (from zeros: the rows are dirtied
    first), a chunk behind those, then three decode steps, each against the reference's full
    forward at the same position."""
    cfg, params = model
    monkeypatch.setattr(gen, "_sample_rows", lambda logits, keys, temps: logits)
    rows, bs, bpr = 2, 16, 8
    toks = _tokens(6, (rows, 64))
    want = ref.logits(params, toks, CONFIG)
    pools = gen.init_kv_pools(cfg, 1 + rows * bpr, bs, slots=rows)
    pools["ssm"] = jax.tree.map(lambda p: p + 3, pools["ssm"])  # a tenant before left its state behind
    tables = {"full": jnp.arange(1, 1 + rows * bpr, dtype=jnp.int32).reshape(rows, bpr), "state": jnp.asarray([1, 2], jnp.int32)}
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
    np.testing.assert_array_equal(pools["ssm"]["state"][:, 0], 3.0)  # the trash row: nobody's
    assert attn_ops.traced("ssm") == "scan+step"


def _served_gaps(params, req):
    seq = list(req.prompt) + req.generated
    n_p, n_g = len(req.prompt), len(req.generated)
    lg = ref.logits(params, jnp.asarray([seq]), CONFIG)[0, n_p - 1 : n_p - 1 + n_g]
    got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(-1) - got)


def _spy(engine):
    """Every step the engine enqueues, in order: the decode part's state rows, the
    chunk's ``(start, real tokens, slot or -1)`` and state row where it carries one, and
    the store's rows of the slots that were mid-prompt, before and after the program."""
    log = []

    def spied(real):
        def program(params, tokens, prev, positions, tables, pools, *rest):
            feeding = [i for i, st in enumerate(engine._slots) if st is not None and st.feeding is not None]
            rows = np.asarray([i + 1 for i in feeding], np.int32)
            before = jax.tree.map(lambda p: np.asarray(p[:, rows]), pools["ssm"])
            nxt, new = real(params, tokens, prev, positions, tables, pools, *rest)
            after = jax.tree.map(lambda p: np.asarray(p[:, rows]), new["ssm"])
            chunk = (tuple(int(v) for v in np.asarray(rest[3])), int(rest[4]["state"][0])) if len(rest) > 2 else None
            log.append({"decode_rows": np.asarray(tables["state"]), "chunk": chunk, "feeding": feeding, "before": before, "after": after})
            return nxt, new

        return program

    engine._decode, engine._decode_chunk = spied(engine._decode), spied(engine._decode_chunk)
    return log


@pytest.fixture(scope="module")
def served(model):
    """Three slots, chunks of 16, seven requests: slots are reused by later requests, prompts
    of one to three chunks are fed while others decode, the pool is short so that the youngest
    is preempted and fed again, and one request stops at an EOS with a step in flight."""
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
    """Every token served (a slot's first tenant or a later one, fed beside decoding slots,
    recomputed after a preemption) has the reference's largest logit at its position or one
    within 1e-4 of it (a near tie may fall either way)."""
    _, params = model
    _, reqs, log, stats = served
    assert stats["requests_done"] == 7 > stats["max_slots"] and stats["preemptions"] >= 1
    assert len(reqs[4].generated) == 3 and stats["tokens_discarded"] >= 1  # the EOS, and the step behind it
    assert any(step["chunk"] and len(set(step["decode_rows"]) - {0}) == 2 for step in log)  # one fed while two decode
    for req in reqs:
        assert _served_gaps(params, req).max() < 1e-4


def test_a_state_row_has_one_writer_a_step(served):
    """A step's decode part addresses slot ``i``'s own row ``i + 1`` or the trash row 0; a
    slot that is mid-prompt is addressed by its chunk alone, and a step that carries no
    chunk of its prompt leaves its rows of the store as they were, bit for bit."""
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


def test_state_is_counted_and_not_cached_or_handed_off(served, model):
    cfg, params = model
    engine, _, _, stats = served
    per_slot = cfg.n_layers * (4 * 8 * 16 * 4 + 3 * 96 * 4)  # S [4, 8, 16] float32 + three inputs of 32 + 2 x 2 x 16 a layer
    assert stats["state_bytes_per_slot"] == per_slot and stats["state_bytes"] == 4 * per_slot  # three slots + the trash row
    assert stats["kv_bytes_per_token"] == cfg.n_layers * 2 * 2 * 16 * 4  # attention's alone
    assert engine.cache.prefix_cache is None and "recurrent state" in stats["prefix_cache_off"] and "prefix_cache" not in stats
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.submit(ServeRequest([1, 2, 3], max_new_tokens=1, prefill_only=True))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.submit_prefilled(ServeRequest([1, 2, 3], max_new_tokens=2), np.zeros((3, 1, 16, 2, 16)), np.zeros((3, 1, 16, 2, 16)), 3, 7)
    with pytest.raises(NotImplementedError, match="paged path"):
        gen.generate(params, jnp.zeros((1, 4), jnp.int32), cfg, 2)
    # an engine of a model without a mixer says nothing of either
    plain = ServeEngine(llama.init_params(llama.llama_tiny(), jax.random.PRNGKey(0)), llama.llama_tiny(), max_slots=2)
    assert plain.cache.prefix_cache is not None and plain.stats()["state_bytes"] == 0 and "prefix_cache_off" not in plain.stats()


def test_the_contiguous_cache_takes_the_multipliers_too():
    """A model with the multipliers and no mixer through ``generate``'s own cache: greedy
    decoding is argmax teacher forcing over the uncached forward, which the reference holds."""
    cfg = llama.llama_tiny(embedding_multiplier=3.0, lm_head_multiplier=0.25, attention_in_multiplier=0.5,
                           attention_out_multiplier=0.3, key_multiplier=2.0, mlp_multipliers=(0.7, 0.4))  # fmt: skip
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    out = gen.generate(params, _tokens(9, (2, 11)), cfg, 6)
    want = jnp.argmax(llama.forward(params, out[:, :-1], cfg)[:, 10:], axis=-1)
    np.testing.assert_array_equal(out[:, 11:], want)
    plain = jnp.argmax(llama.forward(params, out[:, :-1], llama.llama_tiny())[:, 10:], axis=-1)
    assert not np.array_equal(want, plain)  # the multipliers are at work


def test_what_the_mixer_does_not_stand_beside_is_refused():
    mixer = dict(ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2)
    for more in (dict(layer_types=("sliding", "full"), sliding_window=8), dict(hc_mult=2, hc_sinkhorn_iters=2),
                 dict(kernels="pallas"), dict(ssm_groups=3), dict(ssm_state=0)):  # fmt: skip
        with pytest.raises(ValueError):
            llama.llama_tiny(**{**mixer, **more})
    with pytest.raises(ValueError, match="five entries"):
        llama.llama_tiny(ssm_multipliers=(1.0, 1.0))
    for key, value in (("mamba_proj_bias", True), ("mamba_norm_before_gate", True), ("rope_scaling", {"type": "yarn"}),
                       ("attn_layer_indices", [0]), ("mamba_d_ssm", 48)):  # fmt: skip
        with pytest.raises(ValueError):
            models.program_config(dict(CONFIG, **{key: value}))


def test_program_init_lays_out_the_kinds_tree(model):
    cfg, _ = model
    theirs = jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    mine = jax.tree.map(lambda leaf: leaf[0], models.weight_shapes(CONFIG), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    assert jax.tree.map(lambda w: tuple(w.shape), theirs) == mine
    assert set(llama.param_specs(cfg)["layers"]) == set(mine["layers"])
    assert cfg.param_count() == sum(int(np.prod(s)) for s in jax.tree.leaves(mine, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_step_kernel_is_the_jax_numpy_step():
    """``ops/ssm_step_kernel.py`` in the interpreter against ``ssm._advance``: a stack of two
    layers' rows, slots on their own rows and two on the trash row, at the narrowest shapes
    the kernel takes (128 channels a head, 128 states, groups of 8 heads). The layer it is not
    given and the rows nobody names keep every bit."""
    from torchx_tpu.ops.ssm_step_kernel import ssm_step_pallas

    cfg = llama.llama_tiny(ssm_heads=16, ssm_head_dim=128, ssm_state=128, ssm_groups=2)
    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    store, rows = normal(2, 5, 16, 128, 128), jnp.asarray([1, 0, 3, 0], jnp.int32)
    decay, fed, b, c = jnp.asarray(rng.uniform(0, 1, (4, 16)), jnp.float32), normal(4, 16, 128), normal(4, 2, 128), normal(4, 2, 128)
    assert ssm.kernel_eligible(store.shape, 2, "tpu") and not ssm.kernel_eligible(store.shape, 2, "cpu")
    assert not ssm.kernel_eligible((2, 5, 4, 16, 8), 2, "tpu")  # this file's test widths: jax.numpy's
    y, new = ssm_step_pallas(store, rows, decay, fed, b, c, layer=jnp.int32(1), interpret=True)
    want_y, want = ssm._advance(cfg, store[1, rows], decay, fed, b, c)
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(new[1, jnp.asarray([1, 3])], want[jnp.asarray([0, 2])], atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(new[0], store[0])
    np.testing.assert_array_equal(new[1, jnp.asarray([2, 4])], store[1, jnp.asarray([2, 4])])


def test_decode_rows_through_the_kernel_is_decode_rows_without(monkeypatch):
    """The decode part with the backend said to be a TPU and the kernel it then picks run in
    the interpreter, against the same call on the CPU's path: the read-out, and a store in which
    the slot on the trash row (mid-prompt: its chunk's to write) kept its own row."""
    from torchx_tpu.ops import ssm_step_kernel

    cfg = llama.llama_tiny(dim=128, ssm_heads=16, ssm_head_dim=128, ssm_state=128, ssm_groups=2)
    layer = {name: w[1] for name, w in llama.init_params(cfg, jax.random.PRNGKey(1))["layers"].items()}
    store = jax.tree.map(lambda p: jax.random.normal(jax.random.PRNGKey(2), p.shape, jnp.float32).astype(p.dtype), ssm.init_store(cfg, 4))
    u = jax.random.normal(jax.random.PRNGKey(3), (3, cfg.dim), jnp.float32)
    rows = jnp.asarray([1, 0, 3], jnp.int32)
    _, xbc, dt = ssm.project(cfg, layer, u)
    monkeypatch.setattr(attn_ops, "TRACED", {})
    want_y, want = ssm.decode_rows(cfg, layer, xbc, dt, store, jnp.int32(1), rows)
    assert attn_ops.traced("ssm") == "step"
    rule = ssm.kernel_eligible
    monkeypatch.setattr(ssm, "kernel_eligible", lambda shape, groups, _backend: rule(shape, groups, "tpu"))
    real = ssm_step_kernel.ssm_step_pallas
    monkeypatch.setattr(ssm_step_kernel, "ssm_step_pallas", lambda *a, **kw: real(*a, **kw, interpret=True))
    got_y, got = ssm.decode_rows(cfg, layer, xbc, dt, store, jnp.int32(1), rows)
    assert attn_ops.traced("ssm") == "step+step_pallas"
    keep = jnp.asarray([0, 2])
    np.testing.assert_allclose(got_y[keep], want_y[keep], atol=1e-4, rtol=1e-5)
    for name in ("state", "conv"):
        np.testing.assert_array_equal(got[name][0], store[name][0])  # the other layer
        np.testing.assert_array_equal(got[name][1, 2], store[name][1, 2])  # the slot that does not move
        np.testing.assert_allclose(got[name][1, jnp.asarray([1, 3])], want[name][1, jnp.asarray([1, 3])], atol=1e-6, rtol=1e-6)


# -- (d) a model without the mixer is the program it was ---------------------------------------

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
}  # fmt: skip
#: sha256 of the jaxprs below as the commit before the mixer traced them (PR 40's tree,
#: 8e40f2b): the defaults of the fields PR 41 added leave a model without them alone. A PR
#: that changes what these programs compute on purpose records its own, with :func:`_digests`. PR 51 did, for the
#: uncached forward of the three grouped-query kinds alone (``_gqa_attention`` rotates whole heads, ``ops/rope.py::
#: apply_rope_whole``: the values are ``apply_rope``'s to the bit, ``tests/test_rope_whole.py``); every serving step's is the parent's.
BEFORE_THE_MIXER = {
    "llama": ("0d238167015cc164", "49b8059943fd7a24"),
    "moe": ("95d1f827f13bf2e5", "a4cba197c5f6b94c"),
    "sliding_qk_norm": ("178965a15efc42ed", "a7e55fa8d2330bd8"),
    "mla_moe_hc": ("a6091627457f9312", "482c3837461f078d"),
}


def _digests(cfg):
    """(the mixed serving step, the uncached forward) of ``cfg`` as jaxprs, hashed."""
    slots, bs, width = 3, 16, 32
    bps = cfg.max_seq // bs
    params = jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
    pools = jax.eval_shape(lambda: gen.init_kv_pools(cfg, 1 + slots * bps, bs, 1 + slots * bps))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    table = lambda rows: {"full": i32(rows, bps), "window": i32(rows, bps)} if cfg.layer_types else i32(rows, bps)  # noqa: E731
    step = jax.make_jaxpr(
        lambda p, tok, pos, tab, chunk, start, n, ctab, pl, keys, temps: gen.paged_decode_chunk_step(
            p, tok, pos, tab, chunk, start, n, ctab, pl, cfg, keys, temps)
    )(params, i32(slots), i32(slots), table(slots), i32(width), i32(), i32(), table(1), pools,
      jax.ShapeDtypeStruct((slots + 1, 2), jnp.uint32), jax.ShapeDtypeStruct((slots + 1,), jnp.float32))  # fmt: skip
    forward = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(params, i32(2, 24))
    return tuple(hashlib.sha256(str(j).encode()).hexdigest()[:16] for j in (step, forward))


@pytest.mark.parametrize("kind", sorted(OLDER))
def test_a_model_without_the_mixer_traces_the_jaxpr_it_traced_before(kind):
    assert _digests(OLDER[kind]()) == BEFORE_THE_MIXER[kind]
