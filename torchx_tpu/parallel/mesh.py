"""Device mesh construction + sharding helpers for SPMD training.

The canonical 6-axis mesh for TPU LLM training (scaling-book recipe: pick a
mesh, annotate shardings, let XLA insert the collectives over ICI/DCN):

* ``pp``   — pipeline parallelism (layer stages; between slices, DCN),
* ``dp``   — pure data parallelism (between slices, rides DCN),
* ``fsdp`` — data parallelism with parameter sharding (rides ICI),
* ``ep``   — expert parallelism (MoE expert axis; dense models leave it 1),
* ``tp``   — tensor (model) parallelism within attention/MLP blocks,
* ``sp``   — sequence/context parallelism for long sequences.

Axis sizes multiply to the device count; unused axes get size 1 so
PartitionSpecs can always name every axis. MoE expert weights shard over
``("ep", "tp")`` combined (models/moe.py), so ep and tp can be sized
independently — tp=1, ep=8 for a small MoE, or tp=4, ep=2 to split both
ways.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the shape model is pure arithmetic and is shared with the jax-free
# client side (supervisor elastic reshape); it lives in mesh_config
from torchx_tpu.parallel.mesh_config import AXES, MeshConfig

__all__ = [
    "AXES",
    "MeshConfig",
    "make_mesh",
    "named_sharding",
    "shard_map",
    "manual_axes",
    "device_info",
    "BATCH_SPEC",
    "ACT_SPEC",
    "ACT_TP_SPEC",
]


def make_mesh(
    config: MeshConfig = MeshConfig(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the 6-axis mesh over all (or the given) devices.

    Axis order is (pp, dp, fsdp, ep, tp, sp) — outermost-to-innermost
    matches slowest-to-fastest interconnect: pp/dp between slices over DCN,
    tp on the innermost ICI dimension where its all-reduces are cheapest;
    ep sits just outside tp so the MoE all-to-all also rides ICI.
    """
    devs = list(devices) if devices is not None else jax.devices()
    sizes = config.resolve(len(devs))
    shape = tuple(sizes[a] for a in AXES)
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, AXES)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """Shorthand: ``NamedSharding(mesh, P(*spec))``."""
    return NamedSharding(mesh, P(*spec))


def device_info() -> dict:
    """The device as jax reports it — what every result that names where
    it ran carries (``platform``, ``device_kind``, ``device_count``)."""
    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "device_count": jax.device_count(),
    }


def shard_map(
    f,  # noqa: ANN001
    *,
    in_specs,  # noqa: ANN001
    out_specs,  # noqa: ANN001
    mesh: Optional[Mesh] = None,
    axis_names: Optional[frozenset] = None,
    check_vma: bool = False,
):
    """``jax.shard_map`` with this repo's defaults: ``axis_names`` (the
    manual axes) optional, ``check_vma`` off, and ``mesh=None`` meaning
    the mesh inherited from a parent manual region."""
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
        **kwargs,
    )


def manual_axes() -> frozenset:
    """Axis names manualized by an enclosing ``shard_map`` (empty set =
    not inside a manual region)."""
    ctx = jax.sharding.get_abstract_mesh()
    if ctx.empty:
        return frozenset()
    return frozenset(ctx.manual_axes)


# Canonical PartitionSpecs for transformer training state. Batch shards over
# both data axes; sequence over sp (Megatron-style sequence parallelism for
# the residual stream; attention itself uses ring attention over sp).
# raw token batches shard on batch only: the seq axis of data often has
# odd lengths (seq+1 for next-token targets) and activations pick up their
# sp sharding from the in-model constraints instead
BATCH_SPEC = P(("dp", "fsdp"), None)  # tokens [batch, seq]
ACT_SPEC = P(("dp", "fsdp"), "sp", None)  # activations [batch, seq, dim]
ACT_TP_SPEC = P(("dp", "fsdp"), None, "tp")  # attn/mlp inner activations
