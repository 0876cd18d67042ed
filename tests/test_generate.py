"""KV-cache generation tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama


@pytest.fixture(scope="module")
def setup():
    cfg = llama.llama_tiny(max_seq=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    return cfg, params, prompt


class TestGenerate:
    def test_prefill_logits_match_full_forward(self, setup):
        cfg, params, prompt = setup
        cache = gen.init_kv_cache(cfg, 2, 16)
        logits_c, cache = gen.forward_with_cache(
            params, prompt, cache, jnp.int32(0), cfg
        )
        logits_f = llama.forward(params, prompt, cfg)
        np.testing.assert_allclose(logits_c, logits_f, atol=1e-5)
        # cache filled only at prompt positions
        assert not np.allclose(np.asarray(cache["k"][:, :, :8]), 0)
        np.testing.assert_array_equal(np.asarray(cache["k"][:, :, 8:]), 0)

    def test_greedy_matches_teacher_forcing(self, setup):
        cfg, params, prompt = setup
        seq = prompt
        for _ in range(6):
            logits = llama.forward(params, seq, cfg)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        out = gen.generate(params, prompt, cfg, max_new_tokens=6)
        np.testing.assert_array_equal(out, seq)

    def test_generate_jits(self, setup):
        cfg, params, prompt = setup
        fn = jax.jit(
            lambda p, t: gen.generate(p, t, cfg, max_new_tokens=4),
        )
        out = fn(params, prompt)
        assert out.shape == (2, 12)

    def test_sampling_temperature(self, setup):
        cfg, params, prompt = setup
        a = gen.generate(
            params, prompt, cfg, 8, temperature=1.5, rng=jax.random.PRNGKey(7)
        )
        b = gen.generate(
            params, prompt, cfg, 8, temperature=1.5, rng=jax.random.PRNGKey(8)
        )
        assert a.shape == b.shape == (2, 16)
        assert not np.array_equal(a, b)  # different keys -> different samples
        # deterministic under the same key
        c = gen.generate(
            params, prompt, cfg, 8, temperature=1.5, rng=jax.random.PRNGKey(7)
        )
        np.testing.assert_array_equal(a, c)

    def test_exceeds_max_seq_raises(self, setup):
        cfg, params, prompt = setup
        with pytest.raises(ValueError, match="max_seq"):
            gen.generate(params, prompt, cfg, max_new_tokens=100)

    def test_single_new_token(self, setup):
        cfg, params, prompt = setup
        out = gen.generate(params, prompt, cfg, max_new_tokens=1)
        assert out.shape == (2, 9)


class TestMoEGenerate:
    """KV-cache decode for MoE configs: the cached layer dispatches to the
    GShard expert FFN (dense-only NotImplementedError removed)."""

    @pytest.fixture(scope="class")
    def moe_setup(self):
        from torchx_tpu.models import moe

        # generous capacity so no token drops -> decode matches forward
        cfg = moe.moe_tiny(capacity_factor=4.0)
        params = moe.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
        return cfg, params, prompt

    def test_moe_prefill_matches_full_forward(self, moe_setup):
        from torchx_tpu.models import moe

        cfg, params, prompt = moe_setup
        cache = gen.init_kv_cache(cfg, 2, 16)
        logits_c, _ = gen.forward_with_cache(
            params, prompt, cache, jnp.int32(0), cfg
        )
        logits_f = moe.forward(params, prompt, cfg)
        np.testing.assert_allclose(logits_c, logits_f, atol=2e-4)

    def test_moe_greedy_matches_teacher_forcing(self, moe_setup):
        from torchx_tpu.models import moe

        cfg, params, prompt = moe_setup
        seq = prompt
        for _ in range(4):
            logits = moe.forward(params, seq, cfg)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        out = gen.generate(params, prompt, cfg, max_new_tokens=4)
        np.testing.assert_array_equal(out, seq)


class TestGenerateStream:
    def test_stream_token_identical_to_batch(self):
        import jax
        import jax.numpy as jnp
        from torchx_tpu.models import generate as gen, llama

        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], dtype=jnp.int32)
        for temp, seed in [(0.0, 0), (0.8, 7)]:
            full = gen.generate(
                params, prompt, cfg, max_new_tokens=11,
                temperature=temp, rng=jax.random.PRNGKey(seed),
            )
            chunks = list(gen.generate_stream(
                params, prompt, cfg, max_new_tokens=11,
                temperature=temp, rng=jax.random.PRNGKey(seed), chunk=4,
            ))
            streamed = jnp.concatenate([jnp.asarray(c) for c in chunks], axis=1)
            assert (streamed == full[:, 4:]).all(), temp
            # chunk sizes: prefill token, then 4/4/2
            assert [c.shape[1] for c in chunks] == [1, 4, 4, 2]

    def test_stream_rejects_overflow(self):
        import jax
        import jax.numpy as jnp
        from torchx_tpu.models import generate as gen, llama

        cfg = llama.llama_tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.ones((1, 4), dtype=jnp.int32)
        import pytest as _pytest

        with _pytest.raises(ValueError, match="max_seq"):
            list(gen.generate_stream(
                params, prompt, cfg, max_new_tokens=cfg.max_seq,
            ))


@pytest.mark.integ
@pytest.mark.parametrize("mode", [[], ["--poisson"], ["--shared-prefix"]])
def test_bench_serving_refuses_to_measure_without_a_tpu(mode):
    """scripts/bench_serving.py measures the device: with no TPU it exits
    non-zero and prints no result, in every mode — it used to switch to
    the tiny config on the CPU under the caller's label."""
    import subprocess
    import sys
    from pathlib import Path

    script = Path(__file__).resolve().parent.parent / "scripts" / "bench_serving.py"
    proc = subprocess.run(
        [sys.executable, str(script), "--steps", "4", "--batches", "1", *mode],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr, proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
