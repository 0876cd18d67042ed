"""Splash attention's backward on the CPU: one walk over the block pairs against two.

``ops/attention.py::splash_attention`` chooses the backward's form from its shapes (PR 50): fused (the library's
``dkv`` kernel makes ``dk``, ``dv`` and one ``dq`` partial a kv block from one ``S``, one ``P``, one ``dP`` a
block pair) while the partials stay at ``_DQ_PARTIALS`` times ``q``, else a ``dkv`` walk and a ``dq`` walk that
each make the scores again. The kernels run in Pallas's interpreter. Gaps are the largest absolute difference
over the largest absolute value of the float32 reference's gradient (``xla_attention`` on the same operands in
float32); the fused form may exceed the two-kernel form's gap by ``HAIR``: in float32 both sum the same
products in another order, in bf16 the partials are rounded to ``q``'s dtype before they are summed, as ``dq``
itself is on its way out.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name

S, D = 512, 128
#: the fused form's gap may exceed the two-kernel form's by this share of it, and this much
HAIR = {jnp.float32: (0.25, 2e-7), jnp.bfloat16: (0.25, 1e-3)}
#: a gradient's gap to the float32 reference, either form
LIMIT = {jnp.float32: 5e-6, jnp.bfloat16: 2e-2}

MASKS = {
    "causal": dict(h=4, kv_h=2),
    "window-128": dict(h=4, kv_h=2, window=128),
    "two-packed-sequences": dict(h=4, kv_h=2, packed=True),
    "gqa-4-to-1": dict(h=4, kv_h=1),
}


def _grads(q, k, v, w, fn):  # noqa: ANN001, ANN202
    return jax.grad(lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kv_blocks", [2, 4])
@pytest.mark.parametrize("mask", MASKS)
def test_one_walk_makes_the_gradients_the_two_walks_make(mask, kv_blocks, dtype, monkeypatch):
    case = MASKS[mask]
    rng = np.random.default_rng(7)
    draw = lambda heads: jnp.asarray(rng.standard_normal((2, S, heads, D), np.float32), dtype)  # noqa: E731
    q, k, v = draw(case["h"]), draw(case["kv_h"]), draw(case["kv_h"])
    w = jnp.asarray(rng.standard_normal(q.shape, np.float32))
    seg = jnp.asarray(np.arange(S)[None] >= np.array([[200], [384]]), jnp.int32) if case.get("packed") else None
    window = case.get("window", 0)

    def splash(q, k, v):  # noqa: ANN001, ANN202
        return attn_ops.splash_attention(q, k, v, block_q=128, block_kv=S // kv_blocks, segment_ids=seg, window=window, interpret=True)

    monkeypatch.setattr(attn_ops, "TRACED", {})
    fused = _grads(q, k, v, w, splash)
    assert attn_ops.traced("attention_bwd") == "fused"
    monkeypatch.setattr(attn_ops, "TRACED", {})
    monkeypatch.setattr(attn_ops, "_DQ_PARTIALS", 0)  # no partial is few enough: the two kernels
    split = _grads(q, k, v, w, splash)
    assert attn_ops.traced("attention_bwd") == "split"
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    want = _grads(f32(q), f32(k), f32(v), w, lambda q, k, v: attn_ops.xla_attention(q, k, v, segment_ids=seg, window=window))
    share, floor = HAIR[dtype]
    for name, a, b, ref in zip(("dq", "dk", "dv"), fused, split, want):
        assert a.dtype == b.dtype == dtype
        gap = lambda x: float(jnp.abs(f32(x) - ref).max() / jnp.abs(ref).max())  # noqa: E731
        assert gap(b) < LIMIT[dtype], (name, gap(b))
        assert gap(a) <= gap(b) * (1 + share) + floor, (name, gap(a), gap(b))


@pytest.mark.parametrize("s_k,d,bkv,want", [
    pytest.param(512, 128, 512, ("fused", 512, 512, 512), id="one-block"),
    pytest.param(2048, 128, 1024, ("fused", 1024, 1024, 1024), id="2k"),
    pytest.param(4096, 128, 1024, ("fused", 1024, 1024, 1024), id="the-train-cell"),
    pytest.param(4096, 64, 1024, ("fused", 1024, 1024, 1024), id="4k-heads-of-64"),
    pytest.param(4096, 256, 1024, ("fused", 512, 1024, 1024), id="4k-heads-of-256-keep-the-forwards-q-block"),
    pytest.param(4096, 128, 512, ("fused", 1024, 1024, 512), id="4k-at-the-fit's-512-blocks"),
    pytest.param(8192, 128, 1024, ("fused", 1024, 2048, 1024), id="8k-wider-blocks"),
    pytest.param(8192, 256, 1024, ("fused", 512, 2048, 1024), id="8k-heads-of-256"),
    pytest.param(6144, 128, 1024, ("fused", 1024, 2048, 1024), id="6k-three-blocks"),
    pytest.param(37 * 128, 128, 128, ("split", 128, 128, 128), id="a-length-only-128-divides"),
    pytest.param(33 * 128, 128, 128, ("fused", 384, 1408, 128), id="a-third-of-33-blocks"),
    pytest.param(16384, 128, 1024, ("split", 512, 1024, 1024), id="16k"),
    pytest.param(32768, 128, 1024, ("split", 512, 1024, 1024), id="32k"),
])  # fmt: skip
def test_the_backwards_form_follows_the_shapes(s_k, d, bkv, want):
    """The partials cost ``s_k / block_kv_dkv`` times ``q``: never more than the bound the module states, whatever
    the length; the scores are made the forward's ``bkv`` rows at a time in either form; the fused form's q block
    is 1,024 rows at heads of 128 or narrower where that divides the queries."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    fwd_bq = attn_ops._fit_block(512, s_k)  # as splash_attention fits it: 512 but at the two odd lengths
    got = attn_ops._backward_blocks(s_k, s_k, d, fwd_bq, bkv)
    form, bq, blk, compute = want
    said = "fused" if got.get("use_fused_bwd_kernel") else "split"
    assert (said, got["block_q_dkv"], got["block_kv_dkv"], got["block_kv_dkv_compute"]) == want
    sizes = BlockSizes(block_q=fwd_bq, block_kv=bkv, **got)  # the library takes it: no dq blocks beside the fused flag
    assert sizes.has_backward_blocks and s_k % blk == 0 and blk % compute == 0 and s_k % bq == 0
    if form == "fused":
        assert s_k // blk <= attn_ops._DQ_PARTIALS and blk <= attn_ops._BWD_BLOCK_KV
    else:
        assert got["block_q_dq"] == fwd_bq and got["block_kv_dq"] == bkv


@pytest.mark.parametrize("seq,form,partials", [(4096, "fused", 4), (8192, "fused", 4), (32768, "split", 0)])
def test_a_long_sequence_is_handed_no_partials_past_the_bound(seq, form, partials, monkeypatch):
    """As shapes, nothing run: the gradient's jaxpr at ``mistral7b-train-4k``'s heads holds one ``dq`` partial a kv
    block where the form is fused and none at a 32 k sequence, and ``traced("attention_bwd")`` says which."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    shape = lambda heads: jax.ShapeDtypeStruct((1, seq, heads, D), jnp.bfloat16)  # noqa: E731
    grad = jax.grad(lambda q, k, v: attn_ops.splash_attention(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(shape(32), shape(8), shape(8))
    assert attn_ops.traced("attention_bwd") == form
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [(1, seq, 32, D), (1, seq, 8, D), (1, seq, 8, D)]
    text = str(jaxpr)
    assert ("splash_mha_dq" in text) == (form == "split") and "splash_mha_dkv" in text
    assert (f"bf16[1,{partials},32,{seq},{D}]" in text) == (form == "fused")
    assert not any(f"bf16[1,{n},32,{seq},{D}]" in text for n in range(attn_ops._DQ_PARTIALS + 1, 65))
