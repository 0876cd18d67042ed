"""Parity suite for the fused Pallas kernels (ops/fused.py).

Every test runs the kernels in the Pallas interpreter (CPU), comparing
against the reference ops in ops/attention.py / ops/norms.py — forward
AND backward, f32/bf16/int8-adjacent legs, and under a sharded 8-device
mesh. The flash comparisons are tight-allclose (tiled online softmax
cannot be bitwise against a monolithic softmax); the fused-norm forward
is checked bitwise (identical op sequence).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.ops import fused
from torchx_tpu.ops.attention import xla_attention
from torchx_tpu.ops.norms import _rms_norm_fwd_math
from torchx_tpu.parallel.mesh import MeshConfig, make_mesh


def _qkv(key, b, s, h, kv_h, d, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype=dtype)
    k = jax.random.normal(kk, (b, s, kv_h, d), dtype=dtype)
    v = jax.random.normal(kv, (b, s, kv_h, d), dtype=dtype)
    return q, k, v


class TestFlashForward:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
    def test_matches_xla(self, dtype, tol):
        q, k, v = _qkv(jax.random.PRNGKey(0), 2, 256, 2, 2, 64, dtype)
        out = fused.flash_attention(q, k, v, causal=True, kernels="interpret")
        assert out is not None and out.dtype == dtype
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
        )

    def test_non_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 128, 2, 2, 64, jnp.float32)
        out = fused.flash_attention(q, k, v, causal=False, kernels="interpret")
        ref = xla_attention(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_gqa_kv_repeat(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), 2, 256, 4, 2, 64, jnp.float32)
        out = fused.flash_attention(q, k, v, causal=True, kernels="interpret")
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_multiple_kv_blocks(self):
        """seq > block: the online-softmax recurrence actually iterates."""
        q, k, v = _qkv(jax.random.PRNGKey(3), 1, 512, 2, 2, 64, jnp.float32)
        out = fused.flash_attention(
            q, k, v, causal=True, kernels="interpret", block_q=128, block_kv=128
        )
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_gating_returns_none(self):
        # head_dim 16 is not lane-tileable
        q, k, v = _qkv(jax.random.PRNGKey(4), 1, 128, 2, 2, 16, jnp.float32)
        assert fused.flash_attention(q, k, v, kernels="interpret") is None
        # ragged sequence
        q, k, v = _qkv(jax.random.PRNGKey(5), 1, 100, 2, 2, 64, jnp.float32)
        assert fused.flash_attention(q, k, v, kernels="interpret") is None
        # reference never enters the module
        q, k, v = _qkv(jax.random.PRNGKey(6), 1, 128, 2, 2, 64, jnp.float32)
        assert fused.flash_attention(q, k, v, kernels="reference") is None
        # pallas off-TPU resolves to reference
        assert fused.flash_attention(q, k, v, kernels="pallas") is None
        assert fused.resolve_kernels("pallas") == "reference"
        assert fused.resolve_kernels("interpret") == "interpret"
        assert fused.resolve_kernels("reference") == "reference"


class TestFlashBackward:
    @pytest.mark.parametrize(
        "dtype,tol", [(jnp.float32, 5e-4), (jnp.bfloat16, 5e-2)]
    )
    def test_grads_match_xla(self, dtype, tol):
        q, k, v = _qkv(jax.random.PRNGKey(7), 2, 256, 2, 2, 64, dtype)
        dy = jax.random.normal(jax.random.PRNGKey(8), q.shape, dtype)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * dy.astype(jnp.float32))

        flash = functools.partial(
            fused.flash_attention, causal=True, kernels="interpret",
            block_q=128, block_kv=128,
        )
        ref = functools.partial(xla_attention, causal=True)
        g_flash = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(functools.partial(loss, ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(
                a.astype(jnp.float32), b.astype(jnp.float32), rtol=tol, atol=tol
            )

    def test_gqa_grads_sum_over_repeats(self):
        """kv-head cotangents fold the query-group contributions back."""
        q, k, v = _qkv(jax.random.PRNGKey(9), 1, 128, 4, 1, 64, jnp.float32)
        dy = jax.random.normal(jax.random.PRNGKey(10), q.shape)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v) * dy)

        flash = functools.partial(
            fused.flash_attention, causal=True, kernels="interpret"
        )
        ref = functools.partial(xla_attention, causal=True)
        g_flash = jax.grad(functools.partial(loss, flash), argnums=(1, 2))(q, k, v)
        g_ref = jax.grad(functools.partial(loss, ref), argnums=(1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            assert a.shape == (1, 128, 1, 64)
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


class TestFlashSharded:
    def test_sharded_mesh_matches_unsharded(self):
        """Full-manual shard_map over the 8-device mesh: dp*fsdp on batch,
        tp on heads — same values as the single-device kernel."""
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        q, k, v = _qkv(jax.random.PRNGKey(11), 4, 128, 4, 2, 64, jnp.float32)
        out = fused.flash_attention(
            q, k, v, causal=True, kernels="interpret", mesh=mesh
        )
        assert out is not None
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_sharded_grads(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        q, k, v = _qkv(jax.random.PRNGKey(12), 4, 128, 2, 2, 64, jnp.float32)
        dy = jax.random.normal(jax.random.PRNGKey(13), q.shape)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v) * dy)

        flash = functools.partial(
            fused.flash_attention, causal=True, kernels="interpret", mesh=mesh
        )
        ref = functools.partial(xla_attention, causal=True)
        g_flash = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(functools.partial(loss, ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)

    def test_undividable_mesh_raises(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        # 3 heads do not divide tp=2: the kernel was asked for and cannot
        # run, so the call fails instead of quietly taking another path
        q, k, v = _qkv(jax.random.PRNGKey(14), 4, 128, 3, 3, 64, jnp.float32)
        with pytest.raises(ValueError, match="do not divide the mesh"):
            fused.flash_attention(q, k, v, kernels="interpret", mesh=mesh)


class TestRmsNormResidual:
    def test_forward_bitwise(self):
        """The fused forward is the same op sequence as the reference —
        bitwise, not just close."""
        x = jax.random.normal(jax.random.PRNGKey(20), (2, 16, 128))
        r = jax.random.normal(jax.random.PRNGKey(21), (2, 16, 128))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(22), (128,))
        y, s = fused.rms_norm_residual(x, r, w, kernels="interpret")
        y_ref = _rms_norm_fwd_math(x + r, w, 1e-5)
        assert np.array_equal(np.asarray(s), np.asarray(x + r))
        assert np.array_equal(np.asarray(y), np.asarray(y_ref))

    def test_forward_bitwise_bf16(self):
        x = jax.random.normal(jax.random.PRNGKey(23), (4, 8, 256), jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(24), (4, 8, 256), jnp.bfloat16)
        w = jnp.ones((256,), jnp.bfloat16)
        y, s = fused.rms_norm_residual(x, r, w, kernels="interpret")
        y_ref = _rms_norm_fwd_math(x + r, w, 1e-5)
        assert np.array_equal(
            np.asarray(y, dtype=np.float32), np.asarray(y_ref, dtype=np.float32)
        )

    def test_reference_mode_identical(self):
        x = jax.random.normal(jax.random.PRNGKey(25), (2, 8, 128))
        r = jax.random.normal(jax.random.PRNGKey(26), (2, 8, 128))
        w = jnp.ones((128,))
        y_f, s_f = fused.rms_norm_residual(x, r, w, kernels="interpret")
        y_r, s_r = fused.rms_norm_residual(x, r, w, kernels="reference")
        assert np.array_equal(np.asarray(y_f), np.asarray(y_r))
        assert np.array_equal(np.asarray(s_f), np.asarray(s_r))

    def test_grads_match_reference(self):
        x = jax.random.normal(jax.random.PRNGKey(27), (2, 16, 128))
        r = jax.random.normal(jax.random.PRNGKey(28), (2, 16, 128))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(29), (128,))
        dy = jax.random.normal(jax.random.PRNGKey(30), x.shape)

        def loss(kernels, x, r, w):
            y, s = fused.rms_norm_residual(x, r, w, kernels=kernels)
            # use both outputs so the s-cotangent path is exercised
            return jnp.sum(y * dy) + jnp.sum(s)

        g_f = jax.grad(functools.partial(loss, "interpret"), argnums=(0, 1, 2))(x, r, w)
        g_r = jax.grad(functools.partial(loss, "reference"), argnums=(0, 1, 2))(x, r, w)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)

    def test_sharded_mesh(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        x = jax.random.normal(jax.random.PRNGKey(31), (8, 16, 128))
        r = jax.random.normal(jax.random.PRNGKey(32), (8, 16, 128))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(33), (128,))
        y, s = fused.rms_norm_residual(x, r, w, kernels="interpret", mesh=mesh)
        y_ref = _rms_norm_fwd_math(x + r, w, 1e-5)
        np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(s, x + r, rtol=0, atol=0)

    def test_sharded_grads(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=4, tp=1, sp=1))
        x = jax.random.normal(jax.random.PRNGKey(34), (8, 16, 128))
        r = jax.random.normal(jax.random.PRNGKey(35), (8, 16, 128))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(36), (128,))
        dy = jax.random.normal(jax.random.PRNGKey(37), x.shape)

        def loss(kernels, m, x, r, w):
            y, s = fused.rms_norm_residual(x, r, w, kernels=kernels, mesh=m)
            return jnp.sum(y * dy) + 0.5 * jnp.sum(s)

        g_f = jax.grad(
            functools.partial(loss, "interpret", mesh), argnums=(0, 1, 2)
        )(x, r, w)
        g_r = jax.grad(
            functools.partial(loss, "reference", None), argnums=(0, 1, 2)
        )(x, r, w)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)

    def test_untileable_falls_back(self):
        # d=64 is not lane-aligned: reference math, same result shape
        x = jax.random.normal(jax.random.PRNGKey(38), (2, 8, 64))
        r = jax.random.normal(jax.random.PRNGKey(39), (2, 8, 64))
        w = jnp.ones((64,))
        y, s = fused.rms_norm_residual(x, r, w, kernels="interpret")
        y_ref = _rms_norm_fwd_math(x + r, w, 1e-5)
        assert np.array_equal(np.asarray(y), np.asarray(y_ref))


class TestInt8Leg:
    def test_flash_with_int8_model_dtypes(self):
        """int8 training keeps activations bf16 at the attention boundary
        (quantization lives in the matmuls); the kernel must stay exact
        on the bf16 leg it actually sees under --int8."""
        q, k, v = _qkv(jax.random.PRNGKey(40), 2, 128, 2, 2, 64, jnp.bfloat16)
        out = fused.flash_attention(q, k, v, causal=True, kernels="interpret")
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), rtol=2e-2, atol=2e-2
        )


class TestModelRouting:
    """cfg.kernels routes the llama layer through the fused kernels."""

    def _cfg(self, kernels):
        from torchx_tpu.models import llama

        # dim=128 (lane-aligned norm), head_dim=64 (flash-tileable)
        return llama.llama_tiny(
            dim=128, n_heads=2, n_kv_heads=1, ffn_dim=256, kernels=kernels
        )

    def test_interpret_matches_reference_loss_and_grads(self):
        from torchx_tpu.models import llama

        tokens = jax.random.randint(jax.random.PRNGKey(50), (2, 129), 0, 512)
        batch = {"tokens": tokens}
        cfg_ref = self._cfg("reference")
        cfg_fused = self._cfg("interpret")
        params = llama.init_params(cfg_ref, jax.random.PRNGKey(51))
        l_ref, g_ref = jax.value_and_grad(llama.loss_fn)(params, batch, cfg_ref)
        l_fused, g_fused = jax.value_and_grad(llama.loss_fn)(
            params, batch, cfg_fused
        )
        np.testing.assert_allclose(l_fused, l_ref, rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)

    def test_pallas_request_off_tpu_matches_reference_bitwise(self):
        # "pallas" on a CPU backend must take the reference path exactly
        from torchx_tpu.models import llama

        tokens = jax.random.randint(jax.random.PRNGKey(52), (1, 129), 0, 512)
        batch = {"tokens": tokens}
        params = llama.init_params(self._cfg("reference"), jax.random.PRNGKey(53))
        l_ref = llama.loss_fn(params, batch, self._cfg("reference"))
        l_pal = llama.loss_fn(params, batch, self._cfg("pallas"))
        assert np.asarray(l_pal).tobytes() == np.asarray(l_ref).tobytes()

    def test_invalid_kernels_rejected(self):
        with pytest.raises(ValueError):
            self._cfg("mosaic")
