"""The plain reference, reached through each configuration's model kind,
against the program at tiny widths in float32: the
dense block against ``llama.forward``, the Mixtral block against
``moe.forward`` (capacity large enough to drop nothing), and the written-out
clip + AdamW against the program's optimizer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import kinds, models
from benchmark.reference import train as ref_train

from .conftest import FIXTURES


def config(name):
    with open(os.path.join(FIXTURES, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_logits_match_the_program(name):
    from torchx_tpu.models import llama

    c = config(name)
    cfg = models.program_config(c, max_seq=64, remat=False)
    params = models.make_weights(c, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = llama.forward(params, tokens, cfg)
    got = kinds.reference(c).logits(params, tokens, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_fp8_control_moves_the_logits():
    c = config("tiny-dense")
    params = models.make_weights(c, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, c["vocab_size"])
    ref = kinds.reference(c)
    gap = jnp.abs(ref.logits(params, tokens, c, "fp8") - ref.logits(params, tokens, c))
    assert float(jnp.max(gap)) > 1e-3


def test_training_steps_match_the_programs_optimizer():
    from torchx_tpu.examples import train_llama as tl
    from torchx_tpu.models import llama

    c = config("tiny-dense")
    cfg = models.program_config(c, max_seq=32, remat=False)
    params = models.make_weights(c, 9)
    batches = [np.asarray(jax.random.randint(jax.random.PRNGKey(i), (2, 33), 0, 512)) for i in range(3)]
    opt = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip=1.0, decay_steps=100_000, lr=1e-3, warmup=2)
    got = ref_train.follow(kinds.reference(c).mean_nll, jax.tree.map(lambda x: x.astype(jnp.float32), params),
                           batches, c, opt)

    optimizer = tl.make_optimizer(lr=1e-3, warmup=2)
    state, p, losses = optimizer.init(params), params, []
    for b in batches:
        loss, grads = jax.value_and_grad(llama.loss_fn)(p, {"tokens": jnp.asarray(b)}, cfg)
        updates, state = optimizer.update(grads, state, p)
        p = jax.tree.map(lambda a, u: a + u, p, updates)
        losses.append(float(loss))
    np.testing.assert_allclose(got["losses"], losses, rtol=1e-5)
    delta = float(jnp.linalg.norm(p["layers"]["wq"] - params["layers"]["wq"]))
    assert got["delta"]["layers/wq"] == pytest.approx(delta, rel=1e-4)
    assert [ref_train.learning_rate(i, opt) for i in range(3)] == [0.0, 5e-4, 1e-3]
