"""Model/ops/parallel stack tests on the 8-device CPU mesh."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import llama
from torchx_tpu.ops.attention import xla_attention
from torchx_tpu.ops.norms import rms_norm
from torchx_tpu.ops.ring_attention import ring_attention
from torchx_tpu.ops.rope import apply_rope, rope_frequencies
from torchx_tpu.parallel.mesh import MeshConfig, make_mesh

attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name


class TestMeshConfig:
    def test_resolve_wildcard(self):
        assert MeshConfig(dp=2, fsdp=-1, tp=2).resolve(8) == {
            "pp": 1,
            "dp": 2,
            "fsdp": 2,
            "ep": 1,
            "tp": 2,
            "sp": 1,
        }

    def test_resolve_exact(self):
        assert MeshConfig(dp=1, fsdp=8, tp=1, sp=1).resolve(8)["fsdp"] == 8

    def test_resolve_errors(self):
        with pytest.raises(ValueError):
            MeshConfig(dp=3, fsdp=-1).resolve(8)
        with pytest.raises(ValueError):
            MeshConfig(dp=2, fsdp=2).resolve(8)
        with pytest.raises(ValueError):
            MeshConfig(dp=-1, fsdp=-1).resolve(8)

    def test_make_mesh(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        assert dict(mesh.shape) == {
            "pp": 1, "dp": 2, "fsdp": 2, "ep": 1, "tp": 2, "sp": 1,
        }


class TestOps:
    def test_rms_norm_matches_reference(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16,))
        out = rms_norm(x, w)
        ref = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-5) * w
        np.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_rms_norm_fused_bwd_matches_xla(self):
        """The fused Pallas backward (interpret mode on CPU) produces the
        same dx/dw as autodiff of the plain XLA forward."""
        x = jax.random.normal(
            jax.random.PRNGKey(0), (2, 16, 128), dtype=jnp.float32
        )
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (128,))
        dy = jax.random.normal(jax.random.PRNGKey(2), x.shape)

        def loss(fused):
            def f(x, w):
                return jnp.sum(rms_norm(x, w, fused=fused) * dy)

            return jax.grad(f, argnums=(0, 1))(x, w)

        dx_ref, dw_ref = loss("never")
        dx_fused, dw_fused = loss("interpret")
        np.testing.assert_allclose(dx_fused, dx_ref, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(dw_fused, dw_ref, rtol=2e-5, atol=2e-6)

    def test_rms_norm_fused_bwd_bf16(self):
        x = jax.random.normal(
            jax.random.PRNGKey(0), (4, 8, 256), dtype=jnp.bfloat16
        )
        w = jnp.ones((256,), dtype=jnp.bfloat16)
        dy = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.bfloat16)

        def grads(fused):
            def f(x, w):
                return jnp.sum(
                    rms_norm(x, w, fused=fused).astype(jnp.float32)
                    * dy.astype(jnp.float32)
                )

            return jax.grad(f, argnums=(0, 1))(x, w)

        dx_ref, dw_ref = grads("never")
        dx_fused, dw_fused = grads("interpret")
        np.testing.assert_allclose(
            np.asarray(dx_fused, np.float32),
            np.asarray(dx_ref, np.float32),
            rtol=0.05,
            atol=0.02,
        )
        np.testing.assert_allclose(
            np.asarray(dw_fused, np.float32),
            np.asarray(dw_ref, np.float32),
            rtol=0.05,
            atol=0.02,
        )

    @pytest.mark.parametrize(
        "axes",
        [
            dict(dp=2, fsdp=2, tp=1, sp=2),
            dict(dp=1, fsdp=2, tp=2, sp=2),
            # pp > 1: the norm runs inside one stage; the wrap must not
            # touch the pp axis
            dict(pp=2, fsdp=2, tp=1, sp=2),
            # ep > 1: expert axis present but dense layers ignore it
            dict(fsdp=2, ep=2, tp=2, sp=1),
        ],
    )
    def test_rms_norm_fused_sharded_mesh(self, axes):
        """The full-manual shard_map wrap: grads (incl. the weight grad,
        summed over row shards and de-duplicated over tp) match the
        unsharded reference."""
        mesh = make_mesh(MeshConfig(**axes))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 128))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (128,))
        dy = jax.random.normal(jax.random.PRNGKey(2), x.shape)

        def f(x, w):
            return jnp.sum(rms_norm(x, w, fused="interpret", mesh=mesh) * dy)

        def ref(x, w):
            return jnp.sum(rms_norm(x, w, fused="never") * dy)

        dx, dw = jax.jit(jax.grad(f, argnums=(0, 1)))(x, w)
        dx_ref, dw_ref = jax.grad(ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(dx, dx_ref, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(dw, dw_ref, rtol=2e-5, atol=2e-6)

    def test_attention_shard_wrap_matches_xla(self):
        """The fully-manual shard_map wrap Mosaic kernels need on sharded
        meshes (ops/attention._shard_wrap): splash (interpret mode) under
        the wrap on a dp x fsdp x tp mesh matches plain xla attention."""
        import importlib

        # torchx_tpu.ops re-exports the attention FUNCTION under the
        # submodule's name, so plain `import ... as` resolves to the
        # function; go through importlib for the module itself
        attn_mod = importlib.import_module("torchx_tpu.ops.attention")
        from torchx_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 512, 8, 64))
        k = jax.random.normal(jax.random.PRNGKey(1), (4, 512, 4, 64))
        v = jax.random.normal(jax.random.PRNGKey(2), (4, 512, 4, 64))

        def kernel(q, k, v, seg):  # noqa: ANN001
            return attn_mod.splash_attention(
                q, k, v, causal=True, interpret=True, segment_ids=seg
            )
        out = jax.jit(
            lambda q, k, v: attn_mod._shard_wrap(
                kernel, q, k, v, None, mesh, ("dp", "fsdp"), "tp"
            )
        )(q, k, v)
        ref = attn_mod.xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-3, rtol=5e-3
        )

    def test_rope_rotation_preserves_norm(self):
        cos, sin = rope_frequencies(16, 32)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
        out = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
        )

    def test_rope_position_zero_identity(self):
        cos, sin = rope_frequencies(8, 4)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, 8))
        out = apply_rope(x, cos, sin)
        np.testing.assert_allclose(out[0, 0], x[0, 0], rtol=1e-6)

    def test_attention_causality(self):
        # perturbing a future token must not change earlier outputs
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, 16))
        out1 = xla_attention(q, k, v, causal=True)
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(99.0)
        out2 = xla_attention(q, k2, v2, causal=True)
        np.testing.assert_allclose(out1[:, :-1], out2[:, :-1], rtol=1e-5)
        assert not np.allclose(out1[:, -1], out2[:, -1])

    def test_gqa_equals_repeated_mha(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 4, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, 16))
        gqa = xla_attention(q, k, v)
        k_rep = jnp.repeat(k, 2, axis=2)
        v_rep = jnp.repeat(v, 2, axis=2)
        mha = xla_attention(q, k_rep, v_rep)
        np.testing.assert_allclose(gqa, mha, rtol=1e-5)

    def test_segment_ids_block_cross_attention(self):
        q = k = v = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 1, 8))
        seg = jnp.array([[0, 0, 0, 0, 1, 1, 1, 1]])
        out = xla_attention(q, k, v, causal=True, segment_ids=seg)
        # first token of segment 1 attends only to itself -> output == its v
        np.testing.assert_allclose(out[0, 4, 0], v[0, 4, 0], rtol=1e-5)


class TestSplashAttention:
    def test_matches_reference_fwd(self):
        # pallas interpreter on CPU: GQA shapes (4 q-heads over 2 kv)
        from torchx_tpu.ops.attention import splash_attention

        b, s, h, kvh, d = 1, 256, 4, 2, 64
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d), jnp.float32)
        ref = xla_attention(q, k, v, causal=True)
        out = splash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3)

    def test_segment_ids(self):
        from torchx_tpu.ops.attention import splash_attention

        b, s, h, d = 1, 256, 2, 64
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)
        seg = jnp.concatenate(
            [jnp.zeros((b, s // 2), jnp.int32), jnp.ones((b, s // 2), jnp.int32)],
            axis=1,
        )
        ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
        out = splash_attention(
            q, k, v, causal=True, segment_ids=seg, interpret=True
        )
        np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3)


class TestAttentionBlockSanitize:
    def test_fit_block(self):
        # shared by the pallas and splash paths: divide-seq + lane rules
        from torchx_tpu.ops.attention import _fit_block

        assert _fit_block(256, 2048) == 256
        assert _fit_block(256, 1920) == 128  # must divide seq
        assert _fit_block(192, 2048) == 128  # lane multiple
        assert _fit_block(64, 2048) == 128  # clamped up to the lane minimum
        assert _fit_block(1024, 1536) == 768  # largest divisor <= requested
        assert _fit_block(512, 640) == 128
        assert _fit_block(256, 320) == 0  # seq not a multiple of 128
        assert _fit_block(128, 64) == 0  # seq below one lane tile


class TestRingAttention:
    def test_matches_reference_fwd_bwd(self):
        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
        b, s, h, kvh, d = 4, 32, 8, 4, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
        ref = xla_attention(q, k, v, causal=True)
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

        g_ring = jax.grad(lambda q: jnp.sum(ring_attention(q, k, v, mesh) ** 2))(q)
        g_ref = jax.grad(lambda q: jnp.sum(xla_attention(q, k, v, True) ** 2))(q)
        np.testing.assert_allclose(g_ring, g_ref, atol=1e-4)


class TestUlyssesAttention:
    def test_matches_reference_fwd_bwd(self):
        from torchx_tpu.ops.ulysses import ulysses_attention

        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
        b, s, h, kvh, d = 4, 32, 8, 4, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
        ref = xla_attention(q, k, v, causal=True)
        out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(out, ref, atol=1e-6)
        g1 = jax.grad(lambda q: jnp.sum(ulysses_attention(q, k, v, mesh) ** 2))(q)
        g2 = jax.grad(lambda q: jnp.sum(xla_attention(q, k, v, True) ** 2))(q)
        np.testing.assert_allclose(g1, g2, atol=1e-5)

    def test_heads_not_divisible_raises(self):
        from torchx_tpu.ops.ulysses import ulysses_attention

        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
        q = jnp.zeros((2, 32, 6, 8))  # 6 heads % 4 != 0
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, q, q, mesh)


class TestLlama:
    def test_forward_shapes_and_dtype(self):
        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.zeros((2, 16), dtype=jnp.int32)
        logits = llama.forward(params, tokens, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_param_count_matches_tree(self):
        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(params))
        assert n == cfg.param_count()

    def test_llama3_8b_param_count(self):
        assert llama.llama3_8b().param_count() == pytest.approx(8.03e9, rel=0.01)

    def test_param_specs_cover_tree(self):
        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        specs = llama.param_specs(cfg)
        jax.tree.map(lambda p, s: None, params, specs)  # same structure

    def test_causal_lm_property(self):
        # changing token t must not affect logits before t
        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 100)
        l1 = llama.forward(params, tokens, cfg)
        l2 = llama.forward(params, tokens.at[0, 8].set(101), cfg)
        np.testing.assert_allclose(l1[0, :8], l2[0, :8], atol=1e-5)
        assert not np.allclose(l1[0, 8], l2[0, 8])

    def test_sharded_matches_unsharded(self):
        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 100)
        ref = llama.forward(params, tokens, cfg)
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        sharded = llama.shard_params(params, cfg, mesh)
        out = jax.jit(lambda p, t: llama.forward(p, t, cfg, mesh))(sharded, tokens)
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_ring_attention_model_matches(self):
        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 100)
        ref = llama.forward(params, tokens, cfg)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
        cfg_ring = dataclasses.replace(cfg, use_ring_attention=True)
        sharded = llama.shard_params(params, cfg_ring, mesh)
        out = jax.jit(lambda p, t: llama.forward(p, t, cfg_ring, mesh))(
            sharded, tokens
        )
        np.testing.assert_allclose(out, ref, atol=1e-3)

    @staticmethod
    def _chunked_and_whole(make=llama.llama_tiny, **overrides):
        """A tiny config whose loss is summed four chunks at a time, the same
        with the loss taken whole, and parameters and a batch for both."""
        cfg = make(max_seq=64, loss_chunk=16, **overrides)
        init, _ = llama.model_fns(cfg)
        params = init(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0, 512)
        return cfg, dataclasses.replace(cfg, loss_chunk=0), params, {"tokens": tokens}

    @staticmethod
    def _assert_same_loss_and_gradients(cfg, cfg_full, params, batch, mesh=None, by=1.0, atol=2e-5):
        """Loss and every gradient leaf, chunked against whole, under an outer
        cotangent of ``by``; the chunked one differentiated through the fused rule."""

        def run(c):
            return jax.jit(jax.value_and_grad(lambda p: by * llama.loss_fn(p, batch, c, mesh)))(params)

        attn_ops.TRACED.pop("loss", None)
        (l1, g1), traced = run(cfg), attn_ops.traced("loss")
        l2, g2 = run(cfg_full)
        assert traced == "fused" and attn_ops.traced("loss") == "fused+whole"
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        assert jax.tree.structure(g1) == jax.tree.structure(params)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1), jax.tree.leaves(g2)):
            assert a.dtype == b.dtype and float(jnp.abs(b).max()) > 0, path
            np.testing.assert_allclose(a, b, atol=atol * by, err_msg=str(path))

    @pytest.mark.parametrize(
        "overrides,by",
        [
            pytest.param({}, 1.0, id="plain"),
            pytest.param({}, 3.0, id="outer-cotangent-3"),
            pytest.param({"tie_embeddings": True}, 1.0, id="tied-embeddings"),
            pytest.param({"ce_f32_logits": True}, 1.0, id="f32-logits"),
            pytest.param({"pred_heads": 2}, 1.0, id="first-of-two-heads"),
            pytest.param({"lm_head_multiplier": 0.5}, 1.0, id="head-multiplier"),
        ],
    )
    def test_chunked_loss_matches_unchunked(self, overrides, by):
        cfg, cfg_full, params, batch = self._chunked_and_whole(**overrides)
        np.testing.assert_allclose(
            llama.loss_fn(params, batch, cfg),
            llama.loss_fn(params, batch, cfg_full),
            rtol=1e-5,
        )
        self._assert_same_loss_and_gradients(cfg, cfg_full, params, batch, by=by)

    @pytest.mark.parametrize("ce_f32_logits", [False, True], ids=["stored-bf16", "stored-f32"])
    def test_chunked_loss_matches_unchunked_in_bf16(self, ce_f32_logits):
        # bf16 weights: the logits are stored bf16 or float32 as the config says, and the two
        # gradient matmuls take autodiff's own operands; both sides round alike, a chunk or whole
        cfg, cfg_full, params, batch = self._chunked_and_whole(dtype=jnp.bfloat16, ce_f32_logits=ce_f32_logits)
        self._assert_same_loss_and_gradients(cfg, cfg_full, params, batch, by=3.0, atol=2e-3)

    def test_chunked_loss_matches_unchunked_moe_with_aux_term(self):
        from torchx_tpu.models import moe

        cfg, cfg_full, params, batch = self._chunked_and_whole(moe.moe_tiny, router_aux_coef=0.05)
        assert float(llama.loss_and_aux(params, batch, cfg)[1][llama.AUX_BALANCE]) > 0
        self._assert_same_loss_and_gradients(cfg, cfg_full, params, batch, by=3.0)

    def test_chunked_loss_matches_unchunked_on_an_fsdp_tp_mesh(self):
        cfg, cfg_full, params, batch = self._chunked_and_whole()
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        sharded = llama.shard_params(params, cfg, mesh)
        self._assert_same_loss_and_gradients(cfg, cfg_full, sharded, batch, mesh=mesh, by=3.0)
        ref = jax.grad(llama.loss_fn)(params, batch, cfg_full)  # and against one device
        got = jax.jit(jax.grad(lambda p: llama.loss_fn(p, batch, cfg, mesh)))(sharded)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(a, b, atol=2e-5)

    @pytest.mark.parametrize("by", [1.0, 3.0], ids=["plain", "outer-cotangent-3"])
    def test_chunked_loss_with_mask(self, by):
        cfg, cfg_full, params, batch = self._chunked_and_whole()
        batch["loss_mask"] = jax.random.uniform(jax.random.PRNGKey(2), (4, 65)) > 0.5
        np.testing.assert_allclose(
            llama.loss_fn(params, batch, cfg),
            llama.loss_fn(params, batch, cfg_full),
            rtol=1e-5,
        )
        self._assert_same_loss_and_gradients(cfg, cfg_full, params, batch, by=by)

    def test_chunked_loss_with_an_empty_mask(self):
        cfg, cfg_full, params, batch = self._chunked_and_whole()
        batch["loss_mask"] = jnp.zeros((4, 65), bool)
        loss, grads = jax.value_and_grad(llama.loss_fn)(params, batch, cfg)
        assert float(loss) == 0.0 and all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(grads))

    @pytest.mark.parametrize(
        "loss_chunk,seq,grad,want",
        [
            pytest.param(16, 64, False, "chunked", id="evaluation"),
            pytest.param(16, 64, True, "fused", id="differentiated"),
            pytest.param(0, 64, True, "whole", id="no-chunk"),
            pytest.param(16, 56, True, "whole", id="chunk-does-not-divide"),
            pytest.param(16, 16, True, "whole", id="one-chunk"),
        ],
    )
    def test_the_loss_says_which_form_it_traced(self, loss_chunk, seq, grad, want):
        cfg = llama.llama_tiny(max_seq=64, loss_chunk=loss_chunk)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.zeros((2, seq + 1), jnp.int32)}
        attn_ops.TRACED.pop("loss", None)
        loss = lambda p: llama.loss_fn(p, batch, cfg)  # noqa: E731
        jax.eval_shape(jax.grad(loss) if grad else loss, params)
        assert attn_ops.traced("loss") == want

    def test_the_train_step_differentiates_the_fused_loss(self):
        from torchx_tpu.train import step as tl

        cfg = llama.llama_tiny(max_seq=64, loss_chunk=16)
        mesh = make_mesh(MeshConfig(fsdp=1), devices=jax.devices()[:1])
        optimizer = tl.make_optimizer()
        state = jax.eval_shape(lambda: tl.init_state(cfg, mesh, optimizer))
        attn_ops.TRACED.pop("loss", None)
        tl.make_train_step(cfg, mesh, optimizer).lower(state, {"tokens": jax.ShapeDtypeStruct((2, 65), jnp.int32)})
        assert attn_ops.traced("loss") == "fused"

    @pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
    def test_a_chunks_logits_are_made_once_under_grad(self, masked):
        """The mechanism in the program and not in the numbers: one matmul makes a
        chunk's logits and two make its gradient; nothing rematerializes them."""
        vocab = 384  # no other width of the tiny model
        cfg = llama.llama_tiny(max_seq=64, loss_chunk=16, vocab_size=vocab)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.zeros((4, 65), jnp.int32)}
        if masked:
            batch["loss_mask"] = jnp.ones((4, 65), bool)

        def walk(jaxpr, inside=()):
            for eqn in jaxpr.eqns:
                yield eqn, inside
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub, inside + (eqn.primitive.name,))

        jaxpr = jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(p, batch, cfg)))(params).jaxpr
        over_vocab = [
            (eqn, inside)
            for eqn, inside in walk(jaxpr)
            if eqn.primitive.name == "dot_general" and any(vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
        ]
        assert len(over_vocab) == 3
        for eqn, inside in over_vocab:
            assert "scan" in inside and not {"checkpoint", "remat", "remat2"} & set(inside), inside
        # logits [b, c, v]; dx [b, c, d], the vocabulary contracted away; dW [d, v] (dimensions in any order)
        made = sorted(tuple(sorted(eqn.outvars[0].aval.shape)) for eqn, _ in over_vocab)
        assert made == [(4, 16, 64), (4, 16, vocab), (64, vocab)]

    def test_loss_decreases(self):
        from torchx_tpu.train.run import train
        from torchx_tpu.parallel.mesh import MeshConfig as MC

        metrics = train(
            llama.llama_tiny(),
            MC(dp=1, fsdp=-1, tp=1, sp=1),
            batch=8,
            seq=32,
            steps=10,
            lr=1e-2,
            warmup=2,
        )
        assert metrics["loss"] < 5.5  # from ~6.2 (ln 512) at init

    def test_remat_policies_agree(self):
        # all remat policies compute identical grads (they only change
        # what is saved vs recomputed), including the named-attn policy
        import jax
        import jax.numpy as jnp

        tokens = jnp.arange(2 * 64, dtype=jnp.int32).reshape(2, 64) % 512
        grads = {}
        for policy in ["full", "dots", "dots_attn"]:
            cfg = llama.llama_tiny(remat_policy=policy)
            params = llama.init_params(cfg, jax.random.PRNGKey(0))

            def loss(p, cfg=cfg):
                return llama.forward(p, tokens, cfg).astype(jnp.float32).mean()

            grads[policy] = jax.grad(loss)(params)
        flat_a = jax.tree_util.tree_leaves(grads["full"])
        for other in ["dots", "dots_attn"]:
            flat_b = jax.tree_util.tree_leaves(grads[other])
            for a, b in zip(flat_a, flat_b):
                assert jnp.allclose(a, b, atol=2e-2), other

    def test_ring_attention_with_remat(self):
        # the 8B long-context path: remat + ring attention compose
        cfg = llama.llama_tiny(use_ring_attention=True, remat=True)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
        params = llama.shard_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh
        )
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 100)
        loss, grads = jax.jit(
            lambda p, b: jax.value_and_grad(llama.loss_fn)(p, b, cfg, mesh)
        )(params, {"tokens": tokens})
        assert jnp.isfinite(loss)
        assert all(jnp.isfinite(g).all() for g in jax.tree.leaves(grads))

    def test_llama8b_shardings_trace(self):
        """AOT-validate the full-scale 8B shardings: abstract trace of the
        train step over a 4x2 mesh — no weights materialize."""
        from torchx_tpu.train.step import TrainState, make_optimizer

        import optax

        cfg = llama.llama3_8b(max_seq=256)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=4, tp=2, sp=1))
        opt = make_optimizer()
        specs = llama.param_specs(cfg)
        from jax.sharding import NamedSharding

        param_shapes = jax.eval_shape(
            lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)
        )
        param_abstract = jax.tree.map(
            lambda shp, spec: jax.ShapeDtypeStruct(
                shp.shape, shp.dtype, sharding=NamedSharding(mesh, spec)
            ),
            param_shapes,
            specs,
        )
        opt_abstract = jax.eval_shape(opt.init, param_abstract)
        state = TrainState(
            params=param_abstract,
            opt_state=opt_abstract,
            step=jax.ShapeDtypeStruct((), jnp.int32),
        )
        batch = {"tokens": jax.ShapeDtypeStruct((8, 257), jnp.int32)}

        def step(state, batch):
            loss, grads = jax.value_and_grad(llama.loss_fn)(
                state.params, batch, cfg, mesh
            )
            updates, opt_state = opt.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            return TrainState(params, opt_state, state.step + 1), loss

        lowered = jax.jit(step).lower(state, batch)  # one trace, no compile
        assert lowered.out_info[1].shape == ()  # loss is a scalar

    def test_tied_embeddings(self):
        cfg = llama.llama_tiny(tie_embeddings=True)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        assert "lm_head" not in params
        logits = llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
        assert logits.shape[-1] == cfg.vocab_size


class TestGraftEntry:
    def test_entry_jits(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "graft_entry", "__graft_entry__.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        fn, args = mod.entry()
        out = jax.jit(fn)(*args)
        assert out.shape[0] == 1 and out.ndim == 3

    def test_dryrun_multichip_8(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "graft_entry2", "__graft_entry__.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.dryrun_multichip(8)


class TestInterpretability:
    def test_forward_from_embeddings_matches_forward(self):
        import jax
        import jax.numpy as jnp

        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.array([[1, 2, 3, 4]], dtype=jnp.int32)
        direct = llama.forward(params, tokens, cfg)
        via_embeds = llama.forward_from_embeddings(
            params, params["embed"][tokens[0]][None], cfg
        )
        assert jnp.allclose(direct, via_embeds, atol=1e-5)

    def test_token_attributions_shapes_and_grads_flow(self):
        import jax
        import jax.numpy as jnp

        from torchx_tpu.examples.interpret_llama import token_attributions

        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.array([[5, 6, 7, 8, 9]], dtype=jnp.int32)
        sal, ig = token_attributions(params, tokens, cfg, steps=4)
        assert sal.shape == (5,) and ig.shape == (5,)
        # gradients actually flow: saliency is strictly positive somewhere
        assert float(jnp.max(sal)) > 0
        assert not jnp.isnan(ig).any()


class TestTrainStepTimeKnobs:
    """The --grad-bucket-mb / --kernels / launch-anchor trainer wiring."""

    def _train(self, **kw):
        from torchx_tpu.train.run import train
        from torchx_tpu.parallel.mesh import MeshConfig as MC

        return train(
            llama.llama_tiny(),
            MC(dp=1, fsdp=-1, tp=1, sp=1),
            batch=8,
            seq=32,
            steps=4,
            warmup=2,
            **kw,
        )

    def test_bucketed_loss_bitwise_equals_single_sync(self):
        ref = self._train(grad_bucket_mb=0)
        bucketed = self._train(grad_bucket_mb="auto")
        assert bucketed["grad_buckets"] >= 1
        assert bucketed["grad_bucket_mb"] > 0
        assert any(t["chosen"] for t in bucketed["grad_bucket_trials"])
        # barriers are value identities: losses agree to the last bit
        assert bucketed["loss"] == ref["loss"]

    def test_explicit_bucket_mb_plumbs_through(self):
        out = self._train(grad_bucket_mb="16")
        assert out["grad_bucket_mb"] == 16
        assert out["grad_bucket_trials"][0]["reason"] == "explicit --grad-bucket-mb"

    def test_launch_anchor_reanchors_first_step(self):
        # a later in-process train() re-anchored at its own call must
        # report seconds for ITS launch, not the age of the process
        # (the bench int8-leg drift this seam exists to fix)
        import time

        self._train()  # consume any first-train process-start anchoring
        t0 = time.monotonic()
        out = self._train(launch_anchor=t0)
        own = time.monotonic() - t0
        assert 0 < out["launch_to_first_step_s"] <= own
        process_age = time.monotonic() - 0  # sanity: anchor is not epoch
        assert out["launch_to_first_step_s"] < process_age

    def test_kernels_flag_reported(self):
        out = self._train(kernels="pallas")  # degrades to reference on CPU
        assert out["kernels"] == "reference"
