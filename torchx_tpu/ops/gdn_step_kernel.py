"""The Pallas TPU kernel behind :func:`torchx_tpu.models.gdn.decode_rows`: every slot's
Gated DeltaNet state moved on one position by the delta rule and read out where it lies.

Kept in a module of its own so that importing Pallas is paid only by a process that
lowers the kernel.

A decode step reads and writes the state of every slot, ``[H, D, D]`` float32 a linear
layer (2 MB at 32 heads of 128 x 128): with six such layers the largest single stream of
the step. Each block of heads is copied into VMEM once, decayed, corrected (``u = beta (v -
S^T k)``, ``S += k u^T``: the read ahead of the write is what ``ops/ssm_step_kernel.py``'s body,
decay, add an outer product, read out, has no place for), read out and copied back to the
row it came from: one read and one write, the rows addressed through scalar-prefetched ids
(a slot's own, or the trash row 0: several slots may name that one, and nobody reads it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: value heads a grid step holds: 8 x [128, 128] float32 are 512 KiB, in and out and each in two buffers 2 MiB
_HEADS = 8


def _kernel(rows_ref, decay_ref, beta_ref, v_ref, kq_ref, s_ref, o_ref, out_ref, *, rep: int):  # noqa: ANN001, ANN202
    del rows_ref  # the index maps read it
    for h in range(s_ref.shape[0]):
        if h % rep == 0:  # a key head's k and q, two rows of a tile of eight, as columns
            cols = kq_ref[h // rep].T  # [D_k, 8]
            k_col, q_col = cols[:, 0:1], cols[:, 1:2]
        s = s_ref[h] * decay_ref[h : h + 1, :]  # [D_k, D_v]
        u = beta_ref[h : h + 1, :] * (v_ref[h : h + 1, :] - jnp.sum(s * k_col, axis=0, keepdims=True))
        s = s + k_col * u
        out_ref[h] = s
        o_ref[h : h + 1, :] = jnp.sum(s * q_col, axis=0, keepdims=True)


def gdn_step_pallas(
    state: jnp.ndarray,  # [layers, rows, H, D_k, D_v] float32: the store a layer scan carries, or [rows, H, D_k, D_v]
    rows: jnp.ndarray,  # [slots] int32: the row each slot reads and writes
    decay: jnp.ndarray,  # [slots, H] float32: exp(g)
    beta: jnp.ndarray,  # [slots, H] float32
    q: jnp.ndarray,  # [slots, Hk, D_k] float32: normed and scaled
    k: jnp.ndarray,  # [slots, Hk, D_k] float32: normed
    v: jnp.ndarray,  # [slots, H, D_v] float32
    layer=None,  # noqa: ANN001 - with a stack: the layer whose rows are meant
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``S <- decay S``, ``S <- S + k (beta (v - S^T k))^T`` and ``o = S^T q`` for every slot, in
    place -> ``(o [slots, H, D_v] float32, state)``. Value head ``h`` reads key head ``h // (H /
    Hk)``. With ``layer`` the store is seen flat, row ``r`` of layer ``i`` at ``i * rows + r`` (a
    reshape that moves nothing), and the layer goes into the row ids, as a block's layer
    does in the paged-attention kernel."""
    shape = state.shape
    if layer is not None:
        rows = rows + layer * shape[1]
        state = state.reshape(-1, *shape[2:])
    slots, heads, dv = v.shape
    key_heads, dk = k.shape[1:]
    rep = heads // key_heads
    hb = min(_HEADS, heads)
    # a key head's k and q as two rows of eight, so that the kernel transposes one whole tile
    kq = jnp.zeros((slots, key_heads, 8, dk), jnp.float32).at[:, :, 0].set(k).at[:, :, 1].set(q)
    lanes = lambda x: jnp.broadcast_to(x[:, :, None], v.shape)  # noqa: E731 - a head's scalar along its lanes
    by_head = pl.BlockSpec((None, hb, dv), lambda i, j, rows: (i, j, 0))
    a_row = pl.BlockSpec((None, hb, dk, dv), lambda i, j, rows: (rows[i], j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, heads // hb),
            in_specs=[
                by_head,
                by_head,
                by_head,
                pl.BlockSpec((None, hb // rep, 8, dk), lambda i, j, rows: (i, j, 0, 0)),
                a_row,
            ],
            out_specs=[by_head, a_row],
        ),
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},  # the state, behind the scalar-prefetched rows and four small inputs
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_step",
    )(rows.astype(jnp.int32), lanes(decay), lanes(beta), v, kq, state)
    return o, state.reshape(shape)
