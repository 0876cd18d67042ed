"""Int8 weight-only quantization tests (ops/quant.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama
from torchx_tpu.ops import quant


class TestQuantOps:
    def test_roundtrip_error_small(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        q, scale = quant.quantize(w)
        back = quant.dequantize(q, scale, dtype=jnp.float32)
        rel = float(jnp.abs(back - w).max() / jnp.abs(w).max())
        assert rel < 0.01  # 127-level symmetric grid

    def test_per_layer_scales_on_stacked_weights(self):
        # two layers with wildly different magnitudes must not share scales
        w = jnp.stack(
            [jnp.ones((8, 4)) * 0.01, jnp.ones((8, 4)) * 100.0]
        )  # [L=2, in, out]
        q, scale = quant.quantize(w)
        assert scale.shape == (2, 1, 4)
        back = quant.dequantize(q, scale, dtype=jnp.float32)
        np.testing.assert_allclose(back, w, rtol=0.01)

    def test_int8_matmul_matches_dequant(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), dtype=jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
        q, scale = quant.quantize(w)
        got = quant.int8_matmul(x, q, scale)
        want = x @ quant.dequantize(q, scale, dtype=jnp.float32)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_maybe_matmul_both_forms(self):
        x = jnp.ones((2, 8))
        w = jax.random.normal(jax.random.PRNGKey(3), (8, 4))
        q, scale = quant.quantize(w)
        plain = quant.maybe_matmul(x, w)
        quantized = quant.maybe_matmul(x, {"q": q, "scale": scale})
        np.testing.assert_allclose(plain, quantized, rtol=0.02, atol=0.02)


class TestQuantizedModel:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = llama.llama_tiny(max_seq=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
        return cfg, params, prompt

    def test_quantize_params_halves_projection_bytes(self, setup):
        cfg, params, _ = setup
        qparams = quant.quantize_params(params)
        # projections dominate; total must shrink substantially
        assert quant.size_bytes(qparams) < 0.75 * quant.size_bytes(params)
        # embeddings/norms stay exact
        assert qparams["embed"].dtype == params["embed"].dtype

    def test_quantized_decode_close_to_fp(self, setup):
        cfg, params, prompt = setup
        qparams = quant.quantize_params(params)
        cache = gen.init_kv_cache(cfg, 2, 16)
        logits_fp, _ = gen.forward_with_cache(
            params, prompt, cache, jnp.int32(0), cfg
        )
        cache2 = gen.init_kv_cache(cfg, 2, 16)
        logits_q, _ = gen.forward_with_cache(
            qparams, prompt, cache2, jnp.int32(0), cfg
        )
        # int8 weight-only: logits track fp closely at tiny scale
        err = float(
            jnp.abs(logits_q - logits_fp).mean() / jnp.abs(logits_fp).mean()
        )
        assert err < 0.05, err

    def test_quantized_generate_runs(self, setup):
        cfg, params, prompt = setup
        qparams = quant.quantize_params(params)
        out = gen.generate(params, prompt, cfg, max_new_tokens=4)
        qout = gen.generate(qparams, prompt, cfg, max_new_tokens=4)
        assert qout.shape == out.shape
        # greedy decode from near-identical logits: most tokens agree
        agree = float((qout == out).mean())
        assert agree > 0.8, agree


class TestInt8TrainingMatmul:
    """AQT int8 TRAINING matmuls (fwd+bwd quantized, STE backward) —
    the training-side counterpart of weight-only serving quant."""

    def test_close_to_bf16_and_grads_flow(self):
        pytest.importorskip("aqt")
        import jax

        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (64, 128), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (128, 96), jnp.bfloat16)

        y_fp = quant.maybe_matmul(x, w)
        y_i8 = quant.maybe_matmul(x, w, int8_training=True)
        assert y_i8.dtype == y_fp.dtype
        err = float(
            jnp.abs(y_i8.astype(jnp.float32) - y_fp.astype(jnp.float32)).mean()
            / jnp.abs(y_fp.astype(jnp.float32)).mean()
        )
        assert err < 0.05, err

        def loss(w):
            return quant.maybe_matmul(x, w, int8_training=True).astype(
                jnp.float32
            ).sum()

        g = jax.grad(loss)(w)
        assert g.shape == w.shape
        assert float(jnp.abs(g.astype(jnp.float32)).mean()) > 0

    def test_int8_training_model_matches_bf16(self):
        pytest.importorskip("aqt")
        import jax
        from torchx_tpu.models import llama

        cfg = llama.llama_tiny(remat_policy="full")
        cfg_i8 = llama.llama_tiny(remat_policy="full", int8_matmuls=True)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(
                jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size
            )
        }
        l_fp = float(llama.loss_fn(params, batch, cfg))
        l_i8 = float(llama.loss_fn(params, batch, cfg_i8))
        assert abs(l_fp - l_i8) < 0.2, (l_fp, l_i8)

    def test_int8_scope_ffn_only(self):
        """int8_scope='ffn' quantizes the FFN dots and ONLY those: output
        differs from bf16 (int8 is active) but is at least as close to
        bf16 as full-scope int8 (attention path untouched)."""
        import jax
        import numpy as np
        import pytest

        pytest.importorskip("aqt")
        from torchx_tpu.models import llama

        cfg_bf16 = llama.llama_tiny(remat_policy="full")
        cfg_ffn = llama.llama_tiny(
            remat_policy="full", int8_matmuls=True, int8_scope="ffn"
        )
        cfg_all = llama.llama_tiny(remat_policy="full", int8_matmuls=True)
        params = llama.init_params(cfg_bf16, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 100)
        ref = np.asarray(llama.forward(params, tokens, cfg_bf16))
        out_ffn = np.asarray(llama.forward(params, tokens, cfg_ffn))
        out_all = np.asarray(llama.forward(params, tokens, cfg_all))
        err_ffn = np.abs(out_ffn - ref).mean()
        err_all = np.abs(out_all - ref).mean()
        assert err_ffn > 0, "ffn scope quantized nothing"
        assert err_ffn <= err_all + 1e-6, (
            f"ffn-only scope should not round more than full scope:"
            f" {err_ffn} vs {err_all}"
        )
        np.testing.assert_allclose(out_ffn, ref, atol=0.15, rtol=0.15)

    def test_int8_scope_validated(self):
        import pytest

        from torchx_tpu.models import llama

        with pytest.raises(ValueError, match="int8_scope"):
            llama.llama_tiny(int8_scope="attn")

    def test_int8_training_on_sharded_mesh(self):
        """AQT int8 matmuls must compose with GSPMD sharding: users flip
        int8_matmuls on real dp/fsdp/tp meshes, where AQT's internal
        quantize/dequantize ops get partitioned too."""
        pytest.importorskip("aqt")
        from torchx_tpu.train.run import train
        from torchx_tpu.models import llama
        from torchx_tpu.parallel.mesh import MeshConfig

        cfg = llama.llama_tiny(remat_policy="full", int8_matmuls=True)
        mesh = MeshConfig(dp=2, fsdp=2, tp=2, sp=1)
        m = train(cfg, mesh, batch=8, seq=64, steps=3, log_every=3)
        assert 0 < m["loss"] < 10
