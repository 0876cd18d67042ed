"""Model interpretability example: token attribution for the Llama family.

Reference analog: torchx/examples/apps/lightning/interpret.py — a captum
integrated-gradients app over the trained CNN. The TPU-native counterpart
computes **input-embedding attributions** for a trained (or fresh) Llama
checkpoint with pure jax transforms — no interpretability library needed,
because ``jax.grad`` over the embedding lookup IS the attribution
primitive:

* saliency: d loss(target token) / d embed(input token), L2 per token;
* integrated gradients: the same gradient accumulated along the
  zero-embedding -> input-embedding path (Sundararajan et al., 2017),
  which satisfies completeness (attributions sum to the score delta).

Launch it like every other analysis app (reference usage shape)::

    tpx run -s local utils.python -m torchx_tpu.examples.interpret_llama -- \\
        --config tiny --text "the quick brown fox"
    tpx run -s local utils.python -m torchx_tpu.examples.interpret_llama -- \\
        --config llama3_1b --ckpt-dir /ckpts/run1 --text "..."
"""

from __future__ import annotations

import argparse
from typing import Optional

import jax
import jax.numpy as jnp

from torchx_tpu.models import llama


def token_attributions(
    params: llama.Params,
    tokens: jnp.ndarray,  # [1, t] int32
    cfg: llama.LlamaConfig,
    steps: int = 16,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (saliency [t], integrated_gradients [t]) for the next-token
    prediction at the final position.

    Gradients are taken w.r.t. the input EMBEDDINGS (the continuous relax-
    ation of the discrete tokens), then reduced per token position.
    """
    embeds = params["embed"][tokens[0]].astype(jnp.float32)[None]  # [1, t, d]
    target = jnp.argmax(
        llama.forward(params, tokens, cfg)[0, -1]
    )  # the model's own next-token prediction

    def score(e: jnp.ndarray) -> jnp.ndarray:
        # forward from embeddings: reuse the model stack minus the lookup
        x = e.astype(cfg.dtype)
        h = llama.forward_from_embeddings(params, x, cfg)
        return h[0, -1, target].astype(jnp.float32)

    grad_fn = jax.jit(jax.grad(score))

    # saliency: one gradient at the input
    sal = jnp.linalg.norm(grad_fn(embeds)[0], axis=-1)  # [t]

    # integrated gradients: average gradients along alpha * embeds
    def ig_step(acc: jnp.ndarray, alpha: jnp.ndarray) -> tuple[jnp.ndarray, None]:
        return acc + grad_fn(embeds * alpha)[0], None

    alphas = (jnp.arange(steps, dtype=jnp.float32) + 0.5) / steps
    total, _ = jax.lax.scan(ig_step, jnp.zeros_like(embeds[0]), alphas)
    ig = jnp.einsum("td,td->t", embeds[0], total / steps)  # completeness form
    return sal, ig


def main(argv: Optional[list[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default="tiny")
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument("--text", default="the quick brown fox jumps over")
    parser.add_argument("--ig-steps", type=int, default=16)
    args = parser.parse_args(argv)

    from torchx_tpu.models import all_configs

    cfg = all_configs()[args.config]()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if args.ckpt_dir:
        from torchx_tpu.parallel.checkpoint import Checkpointer

        ckpt = Checkpointer(args.ckpt_dir)
        step, restored = ckpt.restore_latest(params)
        ckpt.close()
        if restored is not None:
            params = restored
            print(f"loaded checkpoint step {step}")

    token_ids = [b % cfg.vocab_size for b in args.text.encode("utf-8")]
    tokens = jnp.asarray([token_ids], dtype=jnp.int32)
    sal, ig = token_attributions(params, tokens, cfg, steps=args.ig_steps)

    print(f"{'pos':>4} {'byte':>6} {'saliency':>10} {'integrated_grad':>16}")
    for i, (tid, s, g) in enumerate(zip(token_ids, sal, ig)):
        ch = chr(tid) if 32 <= tid < 127 else "?"
        print(f"{i:>4} {ch!r:>6} {float(s):>10.4f} {float(g):>16.4f}")


if __name__ == "__main__":
    main()
