"""Hyper-connections (four residual streams mixed by Sinkhorn-normalised matrices round
every sublayer), the compressed query and YaRN's frequencies against the benchmark's plain
reference (``benchmark/reference/mla_moe_hc.py``: float32 at ``precision=HIGHEST``, the
published interleaved rotary pairing, every expert on every token), on the CPU at tiny
widths with seeded weights, biases that are not zero and ``a = 1``.

Tolerances. Program and reference both compute in float32 here and differ in the order of
their sums (the per-row norm applied behind the 24-wide projection and not ahead of it, the
mixes unrolled over the four streams against an einsum, absorbed against expanded attention,
sorted rows against masked experts): the coefficients agree to ``1e-5``, logits of size ~5
to ``2e-4 + 2e-4 |x|``. A reference in bfloat16 or one missing piece (2 Sinkhorn iterations
for 20, no YaRN scale on the softmax, plain frequencies, one stream read out for the sum of four)
moves logits by 1e-2 or more: ``test_a_wrong_layer_is_caught`` holds the comparison to that.
"""

from __future__ import annotations

import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import models
from benchmark.reference import mla_moe_hc as ref
from torchx_tpu.models import generate as gen
from torchx_tpu.models import hyper, llama, mla, moe
from torchx_tpu.obs import hot
from torchx_tpu.ops.rope import YarnScaling, rope_frequencies
from torchx_tpu.serve.engine import ServeEngine, ServeRequest

attn_ops = importlib.import_module("torchx_tpu.ops.attention")
LOGITS = dict(atol=2e-4, rtol=2e-4)

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64, "type": "yarn"}  # fmt: skip
CONFIG = {  # the published keys at test widths; what the kind reads and no more
    "model": "mla_moe_hc", "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 4, "n_shared_experts": 1,
    "n_routed_experts": 8, "routed_scaling_factor": 2.0, "kv_lora_rank": 32, "q_lora_rank": 24,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "qk_nope_head_dim": 16, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 3, "first_k_dense_replace": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "rope_scaling": YARN,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "tie_word_embeddings": False, "torch_dtype": "float32", "assumed_router_bias_std": 0.05, "assumed_hc_b_std": 1.5,
}  # fmt: skip


@pytest.fixture(scope="module")
def model():
    cfg = models.program_config(CONFIG, max_seq=128, remat=False)
    return cfg, models.make_weights(CONFIG, 2147483659)


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, CONFIG["vocab_size"])


def _stream(seed, *lead):
    """(the stream as the program carries it, flattened ``[..., n d]``; as the reference takes it, ``[..., n, d]``)"""
    x = jax.random.normal(jax.random.PRNGKey(seed), (*lead, CONFIG["hc_mult"], CONFIG["hidden_size"]))
    return x.reshape(*lead, -1), x


# -- (a) the coefficients ----------------------------------------------------------------


def test_coefficients_match_the_references(model):
    cfg, params = model
    layer = jax.tree.map(lambda w: w[1], params["layers"])
    x, x_rows = _stream(3, 2, 24)
    for sub in hyper.SUBLAYERS:
        got = hyper.coefficients(cfg, layer, sub, x)
        want = ref.hc_coefficients(x_rows, *(layer[f"hc_{sub}_{leaf}"] for leaf in ("phi", "b", "a")), CONFIG)
        for g, w in zip(got, want):
            assert g.dtype == jnp.float32
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


def test_write_back_matrix_is_doubly_stochastic_and_the_iterations_count(model):
    """Columns sum to 1 to rounding (the last normalisation is theirs), rows as nearly as 20
    iterations leave them; one iteration leaves rows visibly off and a visibly other matrix;
    the seeded biases leave no identity."""
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["dense_layers"])
    x, _ = _stream(4, 256)
    _, _, h20 = hyper.coefficients(cfg, layer, "attn", x)
    _, _, h1 = hyper.coefficients(dataclasses.replace(cfg, hc_sinkhorn_iters=1), layer, "attn", x)
    np.testing.assert_allclose(h20.sum(axis=-2), 1.0, atol=1e-5)
    assert float(jnp.abs(h20.sum(axis=-1) - 1.0).max()) < 0.05 and float(jnp.median(jnp.abs(h20.sum(axis=-1) - 1.0))) < 1e-5
    assert float(jnp.abs(h1.sum(axis=-1) - 1.0).max()) > 0.2
    assert float(jnp.median(jnp.abs(h20 - h1).max(axis=(-1, -2)))) > 0.02
    off_diagonal = h20 * (1.0 - jnp.eye(cfg.hc_mult))
    assert float(off_diagonal.sum(axis=(-1, -2)).mean()) > 1.0  # of 4: most of the mass moves between streams


def test_the_clamp_is_reached_and_holds(model):
    """``a_res`` of 100 drives the logits far past +-30: clipped, ``exp`` stays finite in float32
    and the matrix is still doubly stochastic; with the clamp wide open it is not finite."""
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["dense_layers"])
    layer["hc_attn_a"] = jnp.asarray([1.0, 1.0, 100.0])
    x, x_rows = _stream(5, 64)
    _, _, h = hyper.coefficients(cfg, layer, "attn", x)
    want = ref.hc_coefficients(x_rows, layer["hc_attn_phi"], layer["hc_attn_b"], layer["hc_attn_a"], CONFIG)[2]
    assert np.isfinite(np.asarray(h)).all()
    np.testing.assert_allclose(h, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h.sum(axis=-2), 1.0, atol=1e-5)
    _, _, open_ = hyper.coefficients(dataclasses.replace(cfg, hc_res_clamp=(-1e9, 1e9)), layer, "attn", x)
    assert not np.isfinite(np.asarray(open_)).all()


# -- (b) without streams the helper is the add -----------------------------------------------


def test_without_streams_the_helper_is_the_plain_add():
    cfg = llama.llama_tiny()
    f = lambda v: (jnp.tanh(v), None)  # noqa: E731
    x = jnp.ones((2, 3, cfg.dim))
    helper = jax.make_jaxpr(lambda x: hyper.residual(cfg, {}, "attn", x, f)[0])(x)
    plain = jax.make_jaxpr(lambda x: x + jnp.tanh(x))(x)
    assert str(helper) == str(plain)
    assert hyper.expand(cfg, x) is x and hyper.collapse(cfg, x) is x
    assert hyper.leaf_shapes(cfg) == {} and "hc_attn_phi" not in llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]


# -- (c) the whole model ----------------------------------------------------------------------


def test_forward_logits_match_the_reference(model):
    cfg, params = model
    toks = _tokens(1, (2, 48))
    np.testing.assert_allclose(llama.forward(params, toks, cfg), ref.logits(params, toks, CONFIG), **LOGITS)


def test_loss_matches_the_references_mean_nll(model):
    cfg, params = model
    toks = _tokens(2, (2, 49))
    loss, aux = llama.loss_and_aux(params, {"tokens": toks}, dataclasses.replace(cfg, router_aux_coef=0.0))
    assert abs(float(loss) - float(ref.mean_nll(params, toks, CONFIG))) < 2e-5
    assert float(aux[llama.AUX_OVERFLOW]) == 0.0


@pytest.mark.parametrize("wrong", ["two_iterations", "no_yarn_softmax_scale", "plain_frequencies", "one_stream_read_out",
                                   "bf16_reference"])  # fmt: skip
def test_a_wrong_layer_is_caught(model, wrong, monkeypatch):
    cfg, params = model
    toks = _tokens(1, (2, 48))
    want = ref.logits(params, toks, CONFIG)
    if wrong == "two_iterations":
        cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=2)
    elif wrong == "no_yarn_softmax_scale":
        cfg = dataclasses.replace(cfg, rope_scaling=dataclasses.replace(cfg.rope_scaling, mscale_all_dim=0.0, mscale=0.0))
    elif wrong == "plain_frequencies":
        monkeypatch.setattr(YarnScaling, "inv_freq", lambda self, hd, theta: theta ** (-jnp.arange(0, hd, 2) / hd))
    elif wrong == "one_stream_read_out":  # (the mean would pass: the final norm takes the scale out)
        monkeypatch.setattr(hyper, "collapse", lambda cfg, x: x[..., : cfg.dim])
    else:  # the nearest precision below the one stated
        want = ref.logits(jax.tree.map(lambda w: w.astype(jnp.bfloat16), params), toks, CONFIG)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(llama.forward(params, toks, cfg), want, **LOGITS)


def test_paged_prefill_behind_a_cached_prefix_then_decode_give_the_references_logits(model, monkeypatch):
    """The serving programs themselves, their sampling replaced by the identity so that
    they hand back logits: a cold chunk, a chunk behind that cached prefix, then three
    decode steps, each against the reference's full forward at the same position."""
    cfg, params = model
    monkeypatch.setattr(gen, "_sample_rows", lambda logits, keys, temps: logits)
    rows, bs, bpr = 2, 16, 8
    toks = _tokens(6, (rows, 64))
    want = ref.logits(params, toks, CONFIG)
    pools = gen.init_kv_pools(cfg, 1 + rows * bpr, bs)
    tables = jnp.arange(1, 1 + rows * bpr, dtype=jnp.int32).reshape(rows, bpr)
    keys, temps = jnp.zeros((rows, 2), jnp.uint32), jnp.zeros((rows,), jnp.float32)
    prefix = jnp.asarray([32, 16], jnp.int32)  # whole blocks, as the prefix cache hands them out
    suffix = jnp.asarray([29, 40], jnp.int32)
    lg, pools = gen.paged_prefill_chunk(params, toks[:, :32], jnp.zeros_like(prefix), prefix, tables, pools, cfg, keys, temps)
    np.testing.assert_allclose(lg, want[jnp.arange(rows), prefix - 1], **LOGITS)
    chunk = jnp.take_along_axis(toks, jnp.minimum(prefix[:, None] + jnp.arange(48), 63), axis=1)
    lg, pools = gen.paged_prefill_chunk(params, chunk, prefix, suffix, tables, pools, cfg, keys, temps)
    at = prefix + suffix  # the position the next token goes to
    np.testing.assert_allclose(lg, want[jnp.arange(rows), at - 1], **LOGITS)
    for _ in range(3):
        lg, pools = gen.paged_decode_step(params, toks[jnp.arange(rows), at], at, tables, pools, cfg, keys, temps)
        np.testing.assert_allclose(lg, want[jnp.arange(rows), at], **LOGITS)
        at = at + 1
    assert "hyper" in attn_ops.traced("residual")


def _served_gaps(params, req):
    seq = list(req.prompt) + req.generated
    n_p, n_g = len(req.prompt), len(req.generated)
    lg = ref.logits(params, jnp.asarray([seq]), CONFIG)[0, n_p - 1 : n_p - 1 + n_g]
    got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(-1) - got)


def test_engine_serves_the_references_tokens_through_a_prefix_hit_and_a_preemption(model):
    """Every token ``ServeEngine`` served, cold, behind a cached prefix or recomputed after a
    preemption, has the reference's largest logit at its position or one within 1e-4 of it
    (a near tie may fall either way)."""
    cfg, params = model
    shared = _tokens(7, (32,)).tolist()
    prompts = [shared + _tokens(10 + i, (9 + 5 * i,)).tolist() for i in range(5)]
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, num_blocks=13, max_prefill_batch=2).start()
    try:
        first = engine.generate(prompts[0], 6, timeout=300)  # primes the prefix cache
        reqs = [engine.submit(ServeRequest(p, max_new_tokens=24)) for p in prompts[1:]]
        assert all(r.wait(600) and not r.error for r in reqs)
        stats = engine.stats()
    finally:
        engine.stop()
    assert stats["prefix_cache"]["hit_tokens"] >= 32 and stats["preemptions"] >= 1
    assert stats["kv_bytes_per_token"] == cfg.n_layers * cfg.cache_width * 4
    for req in [first, *reqs]:
        assert _served_gaps(params, req).max() < 1e-4


# -- (d) YaRN and the softmax scale, by hand --------------------------------------------------


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """Xing4.0-29B-A4B's keys: 64 rotary columns, base 10,000, factor 64 over 4,096, beta 32 / 1."""
    y = YarnScaling(factor=64.0, original_max_seq=4096, beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000.0))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000.0))
    assert (math.floor(low), math.ceil(high)) == (10, 23) == y.ramp_bounds(64, 10000.0)
    inv = np.asarray(y.inv_freq(64, 10000.0), np.float64)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)  # turn fast enough: kept
    np.testing.assert_allclose(inv[23:], f[23:] / 64.0, rtol=1e-6)  # too slow for 4,096 positions: stretched 64 times
    np.testing.assert_allclose(inv[16], f[16] * ((1 - 6 / 13) + (6 / 13) / 64.0), rtol=1e-6)  # ramp (16 - 10) / (23 - 10)
    np.testing.assert_allclose(inv, np.asarray(ref.yarn_inv_freq(64, 10000.0, dict(YARN, original_max_position_embeddings=4096))), rtol=1e-6)
    m = 0.1 * 1 * math.log(64.0) + 1.0
    assert abs(m - 1.4159) < 1e-4 and y.attention_mscale == m and y.rotation_mscale == 1.0
    cfg = llama.llama_tiny(n_kv_heads=4, kv_lora_rank=32, q_lora_rank=24, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, rope_scaling=y)
    assert cfg.attn_scale == 192**-0.5 * m * m and abs(cfg.attn_scale - 0.14468) < 1e-5
    # without scaling the number is kimi's, to the bit; cos and sin of a plain table are what they were
    assert dataclasses.replace(cfg, rope_scaling=None).attn_scale == 192**-0.5
    cos, sin = rope_frequencies(64, 8, 10000.0, scaling=y)
    np.testing.assert_allclose(cos[5], np.cos(5 * inv), atol=1e-6)
    assert attn_ops.traced("rope").find("yarn") >= 0


def test_traced_says_which_residual_and_rope(model, monkeypatch):
    cfg, params = model
    monkeypatch.setattr(attn_ops, "TRACED", {})
    llama.forward(params, _tokens(1, (1, 8)), cfg)
    assert (attn_ops.traced("residual"), attn_ops.traced("rope")) == ("hyper", "yarn")
    monkeypatch.setattr(attn_ops, "TRACED", {})
    plain = llama.llama_tiny()
    llama.forward(llama.init_params(plain, jax.random.PRNGKey(0)), _tokens(1, (1, 8)), plain)
    assert (attn_ops.traced("residual"), attn_ops.traced("rope")) == ("add", "plain")


# -- (e) absorbed against expanded, with the compressed query -----------------------------------


def test_absorbed_decode_equals_expanded_attention_with_the_compressed_query(model):
    cfg, params = model
    layer = jax.tree.map(lambda w: w[0], params["dense_layers"])
    assert "wq" not in layer and layer["w_qa"].shape == (cfg.dim, cfg.q_lora_rank)
    slots, bs, bpr = 3, 16, 4
    lengths = jnp.asarray([5, 37, 64], jnp.int32)
    pool = jnp.zeros((1 + slots * bpr, bs, cfg.cache_width))
    tables = jnp.arange(1, 1 + slots * bpr, dtype=jnp.int32).reshape(slots, bpr)
    cos_f, sin_f = llama.rope_table(cfg, cfg.max_seq)
    hist = jax.random.normal(jax.random.PRNGKey(8), (slots, 64, cfg.dim))
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (slots, 64))
    _, pool = mla.paged_prefill(cfg, layer, hist, cos_f[pos], sin_f[pos], pos, pos < (lengths - 1)[:, None], tables, pool)
    u = jax.random.normal(jax.random.PRNGKey(9), (slots, 1, cfg.dim))
    at = lengths - 1
    absorbed, pool_a = mla.paged_decode(cfg, layer, u, cos_f[at], sin_f[at], at, tables, pool)
    expanded, pool_e = mla.paged_prefill(
        cfg, layer, u, cos_f[at][:, None], sin_f[at][:, None], at[:, None], jnp.ones((slots, 1), bool), tables, pool)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(pool_a, pool_e)
    # and the expanded form is the reference's attention (published rotary order, YaRN, the softmax's scale)
    want = ref.mla(hist, layer, CONFIG, None)
    np.testing.assert_allclose(mla.attention_full(cfg, layer, hist, cos_f[:64], sin_f[:64]), want, atol=2e-5, rtol=2e-5)


# -- (f) no loop of the device's under the residual path; (g) scopes ------------------------------


def _decode_program(cfg, params, slots=2):
    pools = gen.init_kv_pools(cfg, 9, 16)
    z = jnp.zeros((slots,), jnp.int32)
    return jax.jit(lambda p, pl: gen.paged_decode_step(
        p, z, z, jnp.zeros((slots, 4), jnp.int32), pl, cfg, jnp.zeros((slots, 2), jnp.uint32), jnp.zeros((slots,), jnp.float32)
    )), (params, pools)  # fmt: skip


def _loops_under(jaxpr, scopes, found):
    """The name stacks of the ``while`` and ``scan`` equations anywhere in ``jaxpr`` that lie under any of ``scopes``."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        if eqn.primitive.name in ("while", "scan") and set(scopes) & set(stack.split("/")):
            found.append(stack)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _loops_under(inner, scopes, found)
    return found


def test_no_loop_under_the_residual_paths_scopes_in_the_decode_program(model):
    cfg, params = model
    fn, args = _decode_program(cfg, params)
    hc = (hot.HC_PRE, hot.HC_SINKHORN, hot.HC_POST, hot.HC_HEAD)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _loops_under(jaxpr, hc, []) == []
    assert _loops_under(jaxpr, (hot.LAYERS,), [])  # the reader does find a loop where there is one: the layer scan
    # the lowered program says the same: a stablehlo.while's location never names an hc scope
    text = fn.lower(*args).as_text(debug_info=True)
    whiles = [ln for ln in text.splitlines() if "stablehlo.while" in ln]
    assert whiles and not [ln for ln in whiles if any(f"{s}/" in ln or f'{s}"' in ln for s in hc)]


def test_scopes_are_there_for_the_readers(model):
    import re

    cfg, params = model
    for name in ("HC_PRE", "HC_SINKHORN", "HC_POST", "HC_HEAD", "MLA_Q_LATENT"):
        assert getattr(hot, name) in hot.DEVICE_SCOPES
    fn, args = _decode_program(cfg, params)
    locs = set(re.findall(r'loc\("([^"]+)"', fn.lower(*args).as_text(debug_info=True)))
    for path in ("hc_pre/", "hc_sinkhorn/", "hc_post/", "hc_head/", "attn/mla_q_latent/", "attn/mla_latent/",
                 "attn/mla_absorb/", "attn/paged_attention/", "moe_experts/", "mlp/"):  # fmt: skip
        assert any(loc.startswith(path) or f"/{path}" in loc for loc in locs), path
    # the sublayer runs between its read-in and its write-back, not under them
    assert not any("hc_pre/attn" in loc or "hc_post/attn" in loc or "hc_pre/norm" in loc for loc in locs)


def test_program_init_lays_out_the_kinds_tree(model):
    """``moe.init_params`` (the trainer's, the compile tests') and the benchmark's seeded
    tree have the same leaves and shapes; the config counts them all."""
    cfg, params = model
    own = jax.eval_shape(lambda: moe.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(lambda a: a.shape, params)
    assert cfg.param_count() == sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    specs = moe.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: not isinstance(s, dict)) == jax.tree.structure(own)
