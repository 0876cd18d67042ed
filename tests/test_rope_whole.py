"""The training path's rotation, ``ops/rope.py::apply_rope_whole`` (PR 51): ``x c + (x P) s`` over whole heads.

``llama._gqa_attention`` hands it to ``norm_and_rotate`` where the serving programs hand theirs; ``apply_rope``
and ``rotate`` stay what they were (the engines' jaxpr digests in ``tests/test_falcon_h1.py``,
``tests/test_evabyte.py`` and ``tests/test_qwen3_next.py`` hold them). Here: the rotation is ``apply_rope``'s to
the bit in float32 and in bf16 (whole and partial rotary, a table that starts at an offset, the local table a
sequence shard makes), its hand-written gradient is the inverse rotation (autodiff of ``apply_rope`` to the last
place, float64 to two), and a model's loss and gradients are those of the parent's form over what
``norm_and_rotate`` does ahead of it.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import llama
from torchx_tpu.ops.rope import apply_rope, apply_rope_whole, rope_frequencies

attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name


def _bits(a) -> np.ndarray:
    return np.asarray(a, np.float32)


def _draw(shape, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("hd,start", [(128, 0), (64, 0), (128, 4093), (8, 17)], ids=["hd128", "hd64", "hd128-from-4093", "hd8-from-17"])
def test_the_rotation_is_apply_ropes_to_the_bit(dtype, hd, start):
    x = _draw((2, 48, 4, hd), dtype)
    cos, sin = rope_frequencies(hd, 48, 1e6, start=start)
    want, got = jax.jit(apply_rope)(x, cos, sin), jax.jit(apply_rope_whole)(x, cos, sin)
    assert got.dtype == want.dtype == dtype and got.shape == x.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert np.any(_bits(got) != _bits(x))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("make", [
    pytest.param(lambda dt: llama.llama_tiny(dtype=dt, rotary_dim=8), id="partial-rotary"),
    pytest.param(lambda dt: llama.llama_tiny(dtype=dt, qk_norm=True, key_multiplier=0.5), id="normed-and-scaled-ahead"),
])  # fmt: skip
def test_norm_and_rotate_gives_the_same_heads_under_either_rotation(dtype, make):
    """Through ``llama.norm_and_rotate``: a head's first ``rotary_dim`` values turn and the rest pass, QK-norm and
    the key multiplier come first; the table is the one a sequence shard makes for itself (``_layer`` at ``cos is
    None``: ``rope_table(cfg, local, start)``), 32 positions from position 96."""
    cfg = make(dtype)
    layer = {"q_norm": 1.0 + 0.3 * _draw((cfg.head_dim,), dtype, 3), "k_norm": 1.0 - 0.2 * _draw((cfg.head_dim,), dtype, 4)}
    q, k = _draw((2, 32, cfg.n_heads, cfg.head_dim), dtype, 1), _draw((2, 32, cfg.n_kv_heads, cfg.head_dim), dtype, 2)
    cos, sin = llama.rope_table(cfg, 32, start=96)
    want = jax.jit(lambda q, k: llama.norm_and_rotate(cfg, layer, q, k, cos, sin, apply_rope))(q, k)
    got = jax.jit(lambda q, k: llama.norm_and_rotate(cfg, layer, q, k, cos, sin, apply_rope_whole))(q, k)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("dtype,ulp", [(jnp.float32, 2.0**-23), (jnp.bfloat16, 2.0**-7)], ids=["float32", "bf16"])
def test_the_gradient_is_the_inverse_rotation(dtype, ulp):
    """Written by hand (the same function at ``-sin``), so the backward splits no head either. Against autodiff of
    ``apply_rope`` it differs in the last place at most (the CPU contracts ``a b + c d`` into a fused multiply-add on
    one side or the other), against float64 by two; the tables get no gradient."""
    x, g = _draw((2, 48, 4, 128), dtype), _draw((2, 48, 4, 128), dtype, 1)
    cos, sin = rope_frequencies(128, 48, 1e6, start=11)
    pull = lambda rope: jax.jit(lambda x, c, s: jax.vjp(rope, x, c, s)[1](g))(x, cos, sin)  # noqa: E731
    (got, d_cos, d_sin), (want, _, _) = pull(apply_rope_whole), pull(apply_rope)
    assert got.dtype == dtype and not np.any(np.asarray(d_cos)) and not np.any(np.asarray(d_sin))
    scale = np.maximum(np.abs(_bits(want)), 1.0)
    assert np.max(np.abs(_bits(got) - _bits(want)) / scale) <= ulp
    g64, c64, s64 = np.asarray(g, np.float64), np.asarray(cos, np.float64)[None, :, None, :], np.asarray(sin, np.float64)[None, :, None, :]
    exact = np.concatenate((g64[..., :64] * c64 + g64[..., 64:] * s64, g64[..., 64:] * c64 - g64[..., :64] * s64), axis=-1)
    assert np.max(np.abs(_bits(got) - exact) / np.maximum(np.abs(exact), 1.0)) <= 2 * ulp  # two products and a sum, each rounded


CASES = [
    pytest.param(dict(n_heads=8, n_kv_heads=2), id="gqa-4-to-1"),
    pytest.param(dict(qk_norm=True), id="qk-norm-gains-not-1"),
    pytest.param(dict(layer_types=("sliding",) * 2, sliding_window=16), id="a-window"),
    pytest.param(dict(attn_output_gate=True), id="an-output-gate"),
    pytest.param(dict(key_multiplier=0.5, attention_in_multiplier=1.5), id="key-multiplier"),
    pytest.param(dict(rotary_dim=8), id="partial-rotary"),
    pytest.param(dict(dtype=jnp.bfloat16), id="bf16"),
]


@pytest.mark.parametrize("over", CASES)
def test_a_models_loss_and_gradients_are_the_parents_forms(over, monkeypatch):
    """``llama.loss_fn`` through ``_gqa_attention`` with this rotation and with ``apply_rope`` in its place (the
    parent's form): the loss to the bit, every gradient to the last place of its dtype."""
    cfg = llama.llama_tiny(**over)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if cfg.qk_norm:
        for i, name in enumerate(("q_norm", "k_norm")):
            gain = params["layers"][name]
            params["layers"][name] = gain + 0.3 * jax.random.normal(jax.random.PRNGKey(5 + i), gain.shape, gain.dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0, cfg.vocab_size)
    run = lambda: jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg)))(params)  # noqa: E731
    monkeypatch.setattr(attn_ops, "TRACED", {})
    loss, grads = run()
    assert attn_ops.traced("rotation") == "whole_heads"
    monkeypatch.setattr(llama, "apply_rope_whole", apply_rope)
    want_loss, want_grads = run()
    assert np.asarray(loss) == np.asarray(want_loss)
    tol = 2.0**-7 if cfg.dtype == jnp.bfloat16 else 2e-6
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        worst = np.max(np.abs(_bits(got) - _bits(want))) / max(np.max(np.abs(_bits(want))), 1e-30)
        assert worst <= tol, (jax.tree_util.keystr(path), worst)
