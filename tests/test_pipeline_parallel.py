"""GPipe-style pipeline parallelism tests (pp mesh axis)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import llama
from torchx_tpu.ops.rope import rope_frequencies
from torchx_tpu.parallel.pipeline import make_pp_mesh, pipeline_apply


def mlp_body(x, layer):
    return jnp.tanh(x @ layer["w"] + layer["b"])


def mlp_params(L, d, key):
    k1, k2 = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (L, d, d)) * 0.3,
        "b": jax.random.normal(k2, (L, d)) * 0.1,
    }


def sequential(body, params, x):
    def step(h, layer):
        return body(h, layer), None

    out, _ = jax.lax.scan(step, x, params)
    return out


class TestPipelineApply:
    def test_forward_matches_sequential(self):
        params = mlp_params(8, 16, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        mesh = make_pp_mesh(4)
        out = jax.jit(
            lambda p, x: pipeline_apply(mlp_body, p, x, mesh, n_microbatches=4)
        )(params, x)
        np.testing.assert_allclose(out, sequential(mlp_body, params, x), atol=1e-6)

    def test_gradients_match(self):
        params = mlp_params(4, 8, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
        mesh = make_pp_mesh(2)
        g_pp = jax.grad(
            lambda p: jnp.sum(pipeline_apply(mlp_body, p, x, mesh, 4) ** 2)
        )(params)
        g_ref = jax.grad(lambda p: jnp.sum(sequential(mlp_body, p, x) ** 2))(params)
        for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_microbatch_count_one(self):
        # degenerate pipeline: 1 microbatch still correct (pure bubble)
        params = mlp_params(4, 8, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
        mesh = make_pp_mesh(4)
        out = pipeline_apply(mlp_body, params, x, mesh, n_microbatches=1)
        np.testing.assert_allclose(out, sequential(mlp_body, params, x), atol=1e-6)

    def test_aux_threads_through_pipeline(self):
        """A body returning (x, aux) accumulates aux across stages and
        microbatches, matching the sequential scan exactly (per-layer aux
        linear in the microbatch mean -> microbatch average == batch mean)."""

        def aux_body(x, layer):
            return mlp_body(x, layer), jnp.mean(x)

        params = mlp_params(8, 16, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        mesh = make_pp_mesh(4)
        out, aux = jax.jit(
            lambda p, x: pipeline_apply(
                aux_body, p, x, mesh, n_microbatches=4, with_aux=True
            )
        )(params, x)
        np.testing.assert_allclose(out, sequential(mlp_body, params, x), atol=1e-6)

        def seq_step(h, layer):
            h2, aux = aux_body(h, layer)
            return h2, aux

        _, aux_per_layer = jax.lax.scan(seq_step, x, params)
        np.testing.assert_allclose(float(aux), float(aux_per_layer.sum()), rtol=1e-5)

    def test_aux_gradients_flow_through_pipeline(self):
        def aux_body(x, layer):
            return mlp_body(x, layer), jnp.mean(x**2)

        params = mlp_params(4, 8, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
        mesh = make_pp_mesh(2)

        def pp_loss(p):
            out, aux = pipeline_apply(aux_body, p, x, mesh, 4, with_aux=True)
            return jnp.sum(out**2) + aux

        def seq_loss(p):
            def step(h, layer):
                h2, aux = aux_body(h, layer)
                return h2, aux

            out, aux_per_layer = jax.lax.scan(step, x, p)
            return jnp.sum(out**2) + aux_per_layer.sum()

        g_pp = jax.grad(pp_loss)(params)
        g_ref = jax.grad(seq_loss)(params)
        for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_validation_errors(self):
        params = mlp_params(6, 8, jax.random.PRNGKey(0))
        x = jnp.zeros((8, 8))
        mesh = make_pp_mesh(4)
        with pytest.raises(ValueError, match="not divisible"):
            pipeline_apply(mlp_body, params, x, mesh, 4)  # 6 layers / 4 stages
        params8 = mlp_params(8, 8, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="microbatches"):
            pipeline_apply(mlp_body, params8, x, mesh, 3)  # 8 % 3

    def test_full_llama_model_with_pp_mesh(self):
        """pp wired through llama.forward + shard_params on a 3D mesh."""
        from torchx_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = llama.llama_tiny(n_layers=4)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 100)
        ref = llama.forward(params, tokens, cfg)
        mesh = make_mesh(MeshConfig(pp=2, dp=1, fsdp=2, tp=2, sp=1))
        sharded = llama.shard_params(params, cfg, mesh)
        out = jax.jit(lambda p, t: llama.forward(p, t, cfg, mesh))(sharded, tokens)
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_ring_attention_inside_pp(self):
        """Long-context composition: ring attention over sp NESTED inside a
        pp pipeline stage (shard_map within partial-manual shard_map) —
        forward matches the unsharded dense reference."""
        import dataclasses

        from torchx_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = llama.llama_tiny(n_layers=4)
        cfg = dataclasses.replace(cfg, use_ring_attention=True)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 100)
        ref = llama.forward(
            params, tokens, dataclasses.replace(cfg, use_ring_attention=False)
        )
        mesh = make_mesh(MeshConfig(pp=2, dp=1, fsdp=2, tp=1, sp=2))
        sharded = llama.shard_params(params, cfg, mesh)
        out = jax.jit(lambda p, t: llama.forward(p, t, cfg, mesh))(sharded, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_ring_attention_inside_pp_trains(self):
        """Grads flow through the nested shard_map (GSPMD fallback) and the
        loss decreases."""
        from torchx_tpu.train.run import train
        from torchx_tpu.parallel.mesh import MeshConfig

        cfg = llama.llama_tiny(use_ring_attention=True)
        m = train(
            cfg,
            MeshConfig(pp=2, dp=1, fsdp=2, tp=1, sp=2),
            batch=4,
            seq=64,
            steps=5,
            lr=1e-2,
            warmup=1,
        )
        assert m["loss"] < 6.2

    def test_pp_train_step_loss_decreases(self):
        from torchx_tpu.train.run import train
        from torchx_tpu.parallel.mesh import MeshConfig

        m = train(
            llama.llama_tiny(n_layers=4),
            MeshConfig(pp=2, dp=1, fsdp=2, tp=2, sp=1),
            batch=8,
            seq=32,
            steps=6,
            lr=1e-2,
            warmup=1,
        )
        assert m["loss"] < 6.0

    def test_llama_layers_pipelined(self):
        """The real model body (attention + SwiGLU) through the pipeline."""
        cfg = llama.llama_tiny(n_layers=4)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(
            jax.random.PRNGKey(1), (8, 16, cfg.dim), dtype=cfg.dtype
        )
        cos, sin = rope_frequencies(cfg.head_dim, 16, cfg.rope_theta)
        body = lambda h, layer: llama._layer(cfg, None, cos, sin, h, layer)[0]  # noqa: E731
        ref = sequential(body, params["layers"], x)
        mesh = make_pp_mesh(2)
        out = jax.jit(
            lambda p, x: pipeline_apply(body, p, x, mesh, n_microbatches=4)
        )(params["layers"], x)
        np.testing.assert_allclose(out, ref, atol=1e-4)
