"""From a configuration file to the program's config object and to weights.

The file carries the model's published ``config.json`` keys; the two model
kinds the program has (``llama`` for a dense decoder, ``moe`` for Mixtral's
block) each read the keys they need. The weights are the benchmark's own:
one jitted call from the seed, on the device, in the type they are served
or trained in, so that the reference can be given the very same numbers
without taking anything the program made.
"""

from __future__ import annotations

import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp


def _dtype(config: dict):  # noqa: ANN202
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]]


def program_config(config: dict, **overrides: Any):
    """``LlamaConfig`` / ``MoEConfig`` from the published keys."""
    from torchx_tpu.models import llama, moe

    if config.get("sliding_window"):
        raise ValueError("the program has no sliding-window attention")
    kw = dict(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=_dtype(config),
    )
    if config["model"] == "moe":
        kw.update(
            n_experts=config["num_local_experts"],
            top_k=config["num_experts_per_tok"],
            capacity_factor=float(config["deployment"]["capacity_factor"]),
        )
        kw.update(overrides)
        return moe.MoEConfig(**kw)
    if config["model"] != "llama":
        raise ValueError(f"unknown model kind {config['model']!r}")
    kw.update(overrides)
    return llama.LlamaConfig(**kw)


def seed_key(seed: int, stream: str) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's pass 2**31) and a
    stream name, so weights and tokens never share a stream."""
    mixed = zlib.crc32(f"{int(seed)}/{stream}".encode())
    return jax.random.PRNGKey(mixed & 0x7FFFFFFF)


def weight_shapes(config: dict) -> dict:
    """The parameter tree's shapes and fan-ins, as the program lays it out:
    layers stacked on a leading axis, experts on the next."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    kvh = config["num_key_value_heads"]
    hd = config.get("head_dim") or d // h
    f, L, v = (
        config["intermediate_size"],
        config["num_hidden_layers"],
        config["vocab_size"],
    )
    E = config.get("num_local_experts", 0)
    ex = (E,) if E else ()
    layers = {
        "attn_norm": ((L, d), 0),
        "wq": ((L, d, h * hd), d),
        "wk": ((L, d, kvh * hd), d),
        "wv": ((L, d, kvh * hd), d),
        "wo": ((L, h * hd, d), h * hd),
        "mlp_norm": ((L, d), 0),
        "w_gate": ((L, *ex, d, f), d),
        "w_up": ((L, *ex, d, f), d),
        "w_down": ((L, *ex, f, d), f),
    }
    if E:
        layers["w_router"] = ((L, d, E), d)
    tree = {"embed": ((v, d), d), "layers": layers, "final_norm": ((d,), 0)}
    if not config.get("tie_word_embeddings", False):
        tree["lm_head"] = ((d, v), d)
    return tree


def make_weights(config: dict, seed: int, shardings: Optional[Any] = None):
    """Seeded weights on the device in one jitted call: normal with standard
    deviation ``fan_in ** -0.5``, norm gains one, in the configuration's type."""
    dtype = _dtype(config)
    shapes = weight_shapes(config)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):  # noqa: ANN001
        out = []
        for i, (shape, fan_in) in enumerate(leaves):
            if fan_in == 0:
                out.append(jnp.ones(shape, dtype))
            else:
                w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                out.append((w * fan_in**-0.5).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed, "weights"))
