"""The layers' rematerialization without ``jax.checkpoint``'s CSE guard where a layer is a scan's turn (PR 51).

``models/llama.py::_remat(body, cfg, looped)``: where every layer runs as a turn of a ``lax.scan`` of two turns
or more, the forward and its recomputation lie in two loops that nothing can merge, and the guard
(``prevent_cse``: an optimization barrier round all the recomputation reads) is left out; the chip's compiler had
made a buffer of each of the barrier's operands, 1,040 MiB of pure movement a layer of ``mistral7b-train-4k``'s
backward loop (``tests/test_paged_attention_kernel.py`` holds the compiled step to what is left). Here, on the CPU:
the loss and every gradient are the guarded form's to the bit over what ``llama._layer`` runs (grouped-query
attention 4 : 1, QK-norm with gains that are not 1, sliding layers, an output gate, partial rotary, a key
multiplier, experts, the three policies, bf16), and a layer outside a loop, in a loop of one turn or in a stack
of mixed kinds keeps the guard.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import llama, moe

attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name


def _tiny(**over):
    return llama.llama_tiny(remat=True, n_layers=3, **over)


def _moe_tiny(**over):
    return moe.MoEConfig(vocab_size=512, dim=64, n_layers=3, n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq=128,
                         dtype=jnp.float32, remat=True, n_experts=4, top_k=2, **over)  # fmt: skip


CASES = [
    pytest.param(lambda: _tiny(n_heads=8, n_kv_heads=2), id="gqa-4-to-1"),
    pytest.param(lambda: _tiny(qk_norm=True), id="qk-norm-gains-not-1"),
    pytest.param(lambda: _tiny(layer_types=("sliding",) * 3, sliding_window=16), id="a-window"),
    pytest.param(lambda: _tiny(attn_output_gate=True), id="an-output-gate"),
    pytest.param(lambda: _tiny(key_multiplier=0.5, attention_in_multiplier=1.5), id="key-multiplier"),
    pytest.param(lambda: _tiny(rotary_dim=8), id="partial-rotary"),
    pytest.param(lambda: _tiny(remat_policy="dots"), id="policy-dots"),
    pytest.param(lambda: _tiny(remat_policy="dots_attn"), id="policy-dots-attn"),
    pytest.param(lambda: _tiny(dtype=jnp.bfloat16), id="bf16"),
    pytest.param(lambda: _moe_tiny(), id="experts"),
    pytest.param(lambda: _moe_tiny(capacity_factor=0.0), id="experts-dropless"),
]


def _params(cfg):
    params = llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0))
    if cfg.qk_norm:  # gains that are not 1, so that the norm's gradient is not the identity's
        key = jax.random.PRNGKey(1)
        for name in ("q_norm", "k_norm"):
            gain = params["layers"][name]
            params["layers"][name] = gain + 0.3 * jax.random.normal(key, gain.shape, gain.dtype)
    return params


def _value_and_grads(cfg, params, tokens, monkeypatch, guarded: bool):
    with monkeypatch.context() as patch:
        patch.setattr(attn_ops, "TRACED", {})
        if guarded:  # the parent's form: the guard whatever the layers run in
            remat = llama._remat
            patch.setattr(llama, "_remat", lambda body, cfg, looped=False: remat(body, cfg, False))
        out = jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg)))(params)
        return out, attn_ops.traced("remat")


@pytest.mark.parametrize("make", CASES)
def test_the_loss_and_every_gradient_are_the_guarded_forms_to_the_bit(make, monkeypatch):
    cfg = make()
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0, cfg.vocab_size)
    (loss, grads), said = _value_and_grads(cfg, params, tokens, monkeypatch, guarded=False)
    (want_loss, want_grads), parent_said = _value_and_grads(cfg, params, tokens, monkeypatch, guarded=True)
    assert (said, parent_said) == ("in_loop", "guarded")
    assert np.asarray(loss) == np.asarray(want_loss) and np.isfinite(np.asarray(loss))
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32), err_msg=jax.tree_util.keystr(path))
        assert np.any(np.asarray(got, np.float32) != 0), jax.tree_util.keystr(path)


@pytest.mark.parametrize("make,why", [
    pytest.param(lambda: llama.llama_tiny(remat=True, n_layers=1), "a loop of one turn is unrolled: forward and recomputation in one computation", id="one-layer"),
    pytest.param(lambda: llama.llama_tiny(remat=True, n_layers=4, layer_types=("sliding", "full") * 2, sliding_window=16),
                 "a stack of mixed kinds runs layers outside its scan", id="mixed-kinds"),
])  # fmt: skip
def test_a_layer_that_is_no_turn_of_a_long_scan_keeps_the_guard(make, why, monkeypatch):
    cfg = make()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, cfg.vocab_size)
    (loss, _), said = _value_and_grads(cfg, _params(cfg), tokens, monkeypatch, guarded=False)
    assert said == "guarded", why
    assert np.isfinite(np.asarray(loss))


def test_the_guard_is_gone_from_the_scans_body_and_nowhere_else(monkeypatch):
    """The jaxpr says it: the layers' ``checkpoint`` carries ``prevent_cse=False`` under the scan, and a model
    without rematerialization traces no ``checkpoint`` and says nothing."""
    cfg = _tiny()
    tokens = jnp.zeros((2, 33), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg)))(_params(cfg)))
    assert "prevent_cse=False" in text and "prevent_cse=True" not in text
    monkeypatch.setattr(attn_ops, "TRACED", {})
    plain = llama.llama_tiny(n_layers=3)
    jax.make_jaxpr(lambda p: llama.forward(p, tokens[:, :-1], plain))(_params(plain))
    assert attn_ops.traced("remat") == ""
