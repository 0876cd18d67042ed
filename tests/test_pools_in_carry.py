"""The paged pools ride each group's layer scan in the carry, whole, and are written and
read at the layer's index where they lie (``generate._scan_groups``).

Two things are held here, on the CPU at tiny widths. (1) The values: ``paged_prefill_chunk``
and ``paged_decode_step`` give the same tokens and bit-equal pools as the same programs with
the layers run one at a time over ``pool[i]``, each op in its one-layer form (no layer
index): the reference below, kept in this file. (2) The compiled programs: with the pools
donated, nothing inside the layer loop of the optimized HLO copies, slices out or writes
back a buffer as large as one layer's pool, and ``ops.attention.traced("kv_pools")`` says
``carried``. The same check against the chip's compiler, with the Pallas kernels in the
program, is in ``tests/test_paged_attention_kernel.py`` (the file whose worker holds the
TPU's library).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama, moe
from torchx_tpu.obs.hlo import loop_moves
from torchx_tpu.ops.paged_attention import TRASH_BLOCK

attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name

SLOTS, BS, MAX_SEQ = 3, 16, 64
BPS = MAX_SEQ // BS

KINDS = {
    "llama": lambda: llama.llama_tiny(max_seq=MAX_SEQ),
    "moe": lambda: moe.moe_tiny(max_seq=MAX_SEQ),
    # latent attention, one dense layer ahead of two dropless expert layers: two groups, a pool each
    "mla_moe": lambda: moe.moe_tiny(
        max_seq=MAX_SEQ, n_layers=3, n_kv_heads=4, ffn_dim=96, n_experts=8, top_k=3, expert_ffn_dim=32,
        n_shared_experts=2, router_score="sigmoid", router_bias=True, routed_scale=2.446, n_dense_layers=1,
        capacity_factor=0.0, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16),
}  # fmt: skip


def _model(kind, num_blocks=1 + SLOTS * BPS):
    cfg = KINDS[kind]()
    params = llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(7))
    return cfg, params, gen.init_kv_pools(cfg, num_blocks, BS)


def _one_layer_at_a_time(step, x, params, pools, cfg):
    """What ``generate._scan_groups`` has to equal: a Python loop over the layers, each
    handed its slice of the weights, ``pool[i]`` and no layer number, so that every op
    (append, scatter, gather, attention, the experts' matmul) takes its one-layer form."""
    new = {name: [] for name in pools}
    for group in llama.layer_groups(params):
        for i in range(jax.tree.leaves(params[group])[0].shape[0]):
            layer = jax.tree.map(lambda w: w[i], params[group])  # noqa: B023
            if "k" in pools:
                x, k, v, _ = step(x, layer, pools["k"][i], pools["v"][i], None)
                new["k"].append(k)
                new["v"].append(v)
            else:
                x, pool, _, _ = step(x, layer, pools[group][i], None, None)
                new[group].append(pool)
    return x, {name: jnp.stack(layers) for name, layers in new.items()}


def _serve(cfg, params, pools):
    """Two prefill rounds (cold rows of two lengths; then a suffix behind a cached
    block) and four decode steps with a slot nobody holds: every token sampled and the
    pools after each program."""
    rng = np.random.default_rng(5)
    tables = np.full((SLOTS, BPS), TRASH_BLOCK, np.int32)
    tables[0], tables[1] = 1 + np.arange(BPS), 1 + BPS + np.arange(BPS)  # slot 2 stays inactive
    tables = jnp.asarray(tables)
    keys = lambda n: jnp.zeros((n, 2), jnp.uint32)  # noqa: E731
    greedy = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    prefill = jax.jit(
        lambda p, tok, pre, suf, tab, pl: gen.paged_prefill_chunk(p, tok, pre, suf, tab, pl, cfg, keys(tok.shape[0]), greedy(tok.shape[0])),
        donate_argnums=(5,))  # fmt: skip
    decode = jax.jit(
        lambda p, tok, pos, pl: gen.paged_decode_step(p, tok, pos, tables, pl, cfg, keys(SLOTS), greedy(SLOTS)),
        donate_argnums=(3,))  # fmt: skip
    tokens = lambda shape: jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32)  # noqa: E731
    i32 = lambda *x: jnp.asarray(x, jnp.int32)  # noqa: E731
    out, seen = [], []
    first, pools = prefill(params, tokens((2, 32)), i32(0, 0), i32(20, 16), tables[:2], pools)  # padded to the bucket
    out.append(np.asarray(first))
    seen.append(jax.tree.map(np.asarray, pools))
    second, pools = prefill(params, tokens((1, 16)), i32(16), i32(9), tables[1:2], pools)  # behind row 1's first block
    out.append(np.asarray(second))
    seen.append(jax.tree.map(np.asarray, pools))
    tok = jnp.asarray([first[0], second[0], 0], jnp.int32)
    for i in range(4):
        tok, pools = decode(params, tok, i32(20 + i, 25 + i, 0), pools)
        out.append(np.asarray(tok)[:2])  # what the inactive slot samples is never read
        seen.append(jax.tree.map(np.asarray, pools))
    return out, seen


@pytest.mark.parametrize("kind", list(KINDS))
def test_tokens_and_pools_equal_the_layers_run_one_at_a_time(kind, monkeypatch):
    cfg, params, pools = _model(kind)
    assert len(llama.layer_groups(params)) == (2 if kind == "mla_moe" else 1)
    got_tokens, got_pools = _serve(cfg, params, pools)
    monkeypatch.setattr(gen, "_scan_groups", _one_layer_at_a_time)
    want_tokens, want_pools = _serve(cfg, params, gen.init_kv_pools(cfg, 1 + SLOTS * BPS, BS))
    for got, want in zip(got_tokens, want_tokens):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(got_pools, want_pools):
        assert got.keys() == want.keys()
        for name in want:
            np.testing.assert_array_equal(got[name], want[name])
    assert len({tuple(t) for t in want_tokens[2:]}) > 1  # the tokens move: not a constant answer
    last = want_pools[-1]
    assert all(np.abs(pool[i]).max() > 0 for pool in last.values() for i in range(pool.shape[0]))  # every layer wrote


# -- the compiled programs -----------------------------------------------------------------


def _compiled(cfg, params, pools, program):
    shapes = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if program == "decode":
        fn = lambda p, tok, pos, tab, pl, keys, temps: gen.paged_decode_step(p, tok, pos, tab, pl, cfg, keys, temps)  # noqa: E731
        args = (shapes(params), i32(SLOTS), i32(SLOTS), i32(SLOTS, BPS), shapes(pools),
                jax.ShapeDtypeStruct((SLOTS, 2), jnp.uint32), jax.ShapeDtypeStruct((SLOTS,), jnp.float32))  # fmt: skip
    else:
        fn = lambda p, tok, pre, suf, tab, pl, keys, temps: gen.paged_prefill_chunk(p, tok, pre, suf, tab, pl, cfg, keys, temps)  # noqa: E731
        args = (shapes(params), i32(2, 32), i32(2), i32(2), i32(2, BPS), shapes(pools),
                jax.ShapeDtypeStruct((2, 2), jnp.uint32), jax.ShapeDtypeStruct((2,), jnp.float32))  # fmt: skip
    return jax.jit(fn, donate_argnums=(len(args) - 3,)).lower(*args).compile()  # the pools


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("kind", ["llama", "mla_moe"])
def test_no_pass_over_a_layers_pool_inside_the_layer_loop(kind, program, monkeypatch):
    """A pool far larger than any weight or activation of the tiny model, so that the
    size alone tells a pass over it from everything else the loop moves."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    cfg, params, pools = _model(kind, num_blocks=1025)
    layer_bytes = min(p[0].nbytes for p in pools.values())
    assert layer_bytes > 4 * max(w[0].nbytes for group in llama.layer_groups(params) for w in params[group].values())
    text = _compiled(cfg, params, pools, program).as_text()
    assert attn_ops.traced("kv_pools") == "carried"
    assert " while(" in text  # the layer loop is there to be read
    assert loop_moves(text, layer_bytes) == []


def test_the_reader_sees_a_pool_that_rides_as_xs_and_ys():
    """The form this replaced, in small: each layer's pool sliced out of the stack by the
    scan, updated, and stacked back. The check above must not pass for lack of eyes."""

    def ride(x, stack):
        return jax.lax.scan(lambda x, pool: (x + pool[0, 0], pool.at[0].set(x)), x, stack)

    stack = jax.ShapeDtypeStruct((4, 1024, 64), jnp.float32)
    text = jax.jit(ride, donate_argnums=(1,)).lower(jax.ShapeDtypeStruct((64,), jnp.float32), stack).compile().as_text()
    found = loop_moves(text, 1024 * 64 * 4)
    assert found and all("dynamic-" in line or "copy" in line for line in found)
    assert loop_moves(text, 4 * 1024 * 64 * 4 + 1) == []  # nothing larger than the stack itself
