"""From a configuration file to the program's config object and to weights.

The file carries the model's published ``config.json`` keys; which of them a
model kind reads, and what parameter tree it has, is its adapter's to say
(``kinds/<kind>.py``, found from the file's ``model``). The weights are the
benchmark's own: one jitted call from the seed, on the device, in the type
they are served or trained in, so that the reference can be given the very
same numbers without taking anything the program made.
"""

from __future__ import annotations

import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import kinds


def _dtype(config: dict):  # noqa: ANN202
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]]


def program_config(config: dict, **overrides: Any):
    """The program's config object from the published keys, as the kind maps them."""
    return kinds.of(config).program_config(config, **overrides)


def seed_key(seed: int, stream: str) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's pass 2**31) and a
    stream name, so weights and tokens never share a stream."""
    mixed = zlib.crc32(f"{int(seed)}/{stream}".encode())
    return jax.random.PRNGKey(mixed & 0x7FFFFFFF)


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out, each leaf ``(shape,
    init)``. ``init`` is a fan-in (normal with standard deviation ``fan_in **
    -0.5``; 0 means ones, a norm's gain), ``"zeros"`` (a bias), or ``("normal",
    std)`` (a deviation the model states)."""
    return kinds.of(config).weight_shapes(config)


def _init_leaf(key: jax.Array, i: int, shape: tuple, init: Any, dtype):  # noqa: ANN001, ANN202
    if init == 0:
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if isinstance(init, tuple) and init[0] == "normal":
        std = init[1]
    elif isinstance(init, (int, float)) and init > 0:
        std = init**-0.5
    else:
        raise ValueError(f"unknown initial distribution {init!r}")
    w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (w * std).astype(dtype)


def make_weights(config: dict, seed: int, shardings: Optional[Any] = None):
    """Seeded weights on the device in one jitted call over the kind's tree,
    in the configuration's type. Leaf ``i`` of the flattened tree draws from
    ``fold_in(key, i)``, whatever the other leaves are."""
    dtype = _dtype(config)
    shapes = weight_shapes(config)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):  # noqa: ANN001
        return jax.tree.unflatten(treedef, [
            _init_leaf(key, i, shape, init, dtype)
            for i, (shape, init) in enumerate(leaves)
        ])

    return jax.jit(build, out_shardings=shardings)(seed_key(seed, "weights"))
