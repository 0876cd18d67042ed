"""The Pallas TPU kernel behind :func:`torchx_tpu.models.ssm.decode_rows`: every
slot's recurrent state moved on one position and read out where it lies.

Kept in a module of its own so that importing Pallas is paid only by a process
that lowers the kernel.

A decode step reads and writes the state of every slot, ``[H, N, P]`` float32 a
layer (4 MB at 32 heads of 128 channels and 256 states): the largest single
stream of the step. Written in ``jax.numpy`` over a store the layer scan carries,
the chip's compiler moves it two to three times (a gather of the slots' rows
splits and copies the whole store; a slice of all rows is written out once to be
read by the update and once by the read-out). Here each block is copied into
VMEM once, updated, read out and copied back to the row it came from: one read
and one write, the rows addressed through scalar-prefetched ids (a slot's own,
or the trash row 0: several slots may name that one, and nobody reads it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: heads a grid step holds: 8 x [256, 128] float32 are 1 MiB, in and out and each in two buffers 4 MiB
_HEADS = 8


def _kernel(rows_ref, decay_ref, fed_ref, bc_ref, s_ref, y_ref, out_ref):  # noqa: ANN001, ANN202
    del rows_ref  # the index maps read it
    cols = bc_ref[...].T  # [N, 8]: column 0 the group's B, column 1 its C
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]
    for h in range(s_ref.shape[0]):
        s = s_ref[h] * decay_ref[h : h + 1, :] + b_col * fed_ref[h : h + 1, :]  # [N, P]
        out_ref[h] = s
        y_ref[h : h + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)


def ssm_step_pallas(
    state: jnp.ndarray,  # [layers, rows, H, N, P] float32: the store a layer scan carries, or [rows, H, N, P]
    rows: jnp.ndarray,  # [slots] int32: the row each slot reads and writes
    decay: jnp.ndarray,  # [slots, H] float32: exp(dt A)
    fed: jnp.ndarray,  # [slots, H, P] float32: dt x
    b: jnp.ndarray,  # [slots, G, N] float32
    c: jnp.ndarray,  # [slots, G, N] float32
    layer=None,  # noqa: ANN001 - with a stack: the layer whose rows are meant
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``S <- decay S + B (outer) fed`` and ``y = C . S`` for every slot, in place
    -> ``(y [slots, H, P] float32, state)``. Head ``h`` reads group ``h // (H /
    G)``. With ``layer`` the store is seen flat, row ``r`` of layer ``i`` at ``i *
    rows + r`` (a reshape that moves nothing), and the layer goes into the row
    ids, as a block's layer does in the paged-attention kernel."""
    shape = state.shape
    if layer is not None:
        rows = rows + layer * shape[1]
        state = state.reshape(-1, *shape[2:])
    slots, heads, p = fed.shape
    groups, n = b.shape[1:]
    hb = min(_HEADS, heads // groups)
    per_group = heads // groups // hb  # head blocks a group
    # a group's B and C as two rows of eight, so that the kernel transposes one whole tile
    bc = jnp.zeros((slots, groups, 8, n), jnp.float32).at[:, :, 0].set(b).at[:, :, 1].set(c)
    decay = jnp.broadcast_to(decay[:, :, None], fed.shape)
    by_head = pl.BlockSpec((None, hb, p), lambda i, j, rows: (i, j, 0))
    a_row = pl.BlockSpec((None, hb, n, p), lambda i, j, rows: (rows[i], j, 0, 0))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, heads // hb),
            in_specs=[
                by_head,
                by_head,
                pl.BlockSpec((None, None, 8, n), lambda i, j, rows: (i, j // per_group, 0, 0)),
                a_row,
            ],
            out_specs=[by_head, a_row],
        ),
        out_shape=[jax.ShapeDtypeStruct(fed.shape, jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},  # the state, behind the scalar-prefetched rows and three small inputs
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_step",
    )(rows.astype(jnp.int32), decay, fed, bc, state)
    return y, state.reshape(shape)
