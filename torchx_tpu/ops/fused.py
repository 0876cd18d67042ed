"""Fused Pallas training kernels: flash attention and RMSNorm(+residual).

This module is the ``--kernels pallas`` hot path (ISSUE 20 / the 60%-MFU
push). It owns two hand-written Mosaic kernels, both testable on CPU via
the Pallas interpreter:

* :func:`flash_attention` — tiled online-softmax attention. The score
  matrix is never materialized: the kv-sequential grid keeps one
  ``[block_q, block_kv]`` tile of logits live in VMEM, carrying the
  running row-max ``m``, denominator ``l`` and f32 accumulator across kv
  blocks (the standard flash recurrence). The backward is the standard
  two-kernel flash backward: ``delta = rowsum(dO * O)`` precomputed, one
  kv-sequential kernel accumulating ``dq``, one q-sequential kernel
  accumulating ``dk``/``dv`` — logits are recomputed from the saved
  logsumexp, so residual memory stays O(seq).
* :func:`rms_norm_residual` — residual add + RMSNorm in one VMEM pass:
  ``s = x + residual`` (input dtype, bitwise-identical to the unfused
  add), ``y = rms_norm(s) * w`` in f32. Returns both ``y`` and ``s`` (the
  stream continues from ``s``). The backward reuses the fused dx+dw
  kernel from :mod:`torchx_tpu.ops.norms` on ``s`` and routes the ``s``
  cotangent through both inputs.

Selection contract (the ``--kernels`` flag, TPX112's runtime twin):
``"pallas"`` compiles Mosaic on TPU and resolves to the reference ops
anywhere else (:func:`resolve_kernels`, what the CPU tests need);
``"interpret"`` runs the same kernels in the Pallas interpreter (CPU parity
tests); ``"reference"`` never enters this module. The static shape gates
(:func:`flash_shapes_ok`, :func:`norm_shapes_ok`) choose the reference op
for shapes the kernels do not tile — :func:`flash_attention` returns
``None`` and the caller runs :func:`torchx_tpu.ops.attention.attention`,
:func:`rms_norm_residual` runs the plain-XLA math. Past those gates
``"pallas"`` means the Mosaic kernel or an error: a mesh that does not
divide, a row count that does not tile, or a pipeline stage's manual region
raises instead of running something else under the same name. Both record
what they lowered to in :data:`torchx_tpu.ops.attention.TRACED`.

The dots run in float32 (q, k, v are upcast before every matmul), so on the
MXU these kernels pay the f32 rate; compare with splash's bf16 operands
before reading a speed difference as a property of the tiling.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchx_tpu.ops.attention import (
    _fit_block,
    _on_tpu,
    _repeat_kv,
    _shard_wrap,
    note_traced,
)
from torchx_tpu.ops.norms import (
    _bwd_pallas,
    _pick_rows,
    _refuse_on_tpu,
    _rms_norm_fwd_math,
)

#: Same "already softmax-dead" constant the xla reference uses.
NEG_INF = -1e30

#: head dims the flash kernels tile on the MXU (lane-dim friendly).
FLASH_HEAD_DIMS = (64, 128, 256)


def flash_shapes_ok(s_q: int, s_k: int, head_dim: int) -> bool:
    """Static gate for the fused flash kernels: lane-tileable head dim,
    128-multiple self-attention sequences. (TPX112 duplicates this check
    statically — analyze never imports jax.)"""
    return (
        head_dim in FLASH_HEAD_DIMS
        and s_q == s_k
        and s_q % 128 == 0
        and s_q >= 128
    )


def norm_shapes_ok(d: int) -> bool:
    """Static gate for the fused norm kernel: lane-aligned feature dim."""
    return d % 128 == 0


# ---------------------------------------------------------------------------
# flash attention forward
# ---------------------------------------------------------------------------

#: Lane width: per-row softmax state (running max, denominator, logsumexp)
#: is kept lane-replicated as ``[block_q, LANES]`` — Mosaic has no layout
#: for a rank-1 ``[block_q]`` vector carried across grid steps.
LANES = 128

NT_DIMS = (((1,), (1,)), ((), ()))  # [m, k] x [n, k] -> [m, n]
NN_DIMS = (((1,), (0,)), ((), ()))  # [m, k] x [k, n] -> [m, n]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lanes_to(x, width: int):
    """Lane-replicated ``[rows, LANES]`` -> ``[rows, width]`` (``width`` a
    multiple of LANES, or narrower than one lane tile)."""
    from jax.experimental.pallas import tpu as pltpu

    if width < LANES:
        return x[:, :width]
    return pltpu.repeat(x, width // LANES, axis=1)


def _causal_mask(s, row0, col0):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


def _compiler_params(interpret: bool):
    """The last grid axis revisits its output/scratch blocks (sequential);
    the leading two are independent."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    }


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
    scale, causal, bq, bk,
):
    """One (batch*head, q-block, kv-block) grid cell. The kv axis is the
    innermost (sequential on TPU) grid dim, so the ``m``/``l``/``acc``
    scratch carries the online-softmax state across kv blocks — no S×S
    score matrix ever exists. ``o``/``lse`` are written at the last kv
    block."""
    import jax.experimental.pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    d = acc_scr.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qf = q_ref[...].astype(jnp.float32) * scale  # [bq, d]
    kf = k_ref[...].astype(jnp.float32)  # [bk, d]
    vf = v_ref[...].astype(jnp.float32)
    s = _dot(qf, kf, NT_DIMS)  # [bq, bk]
    if causal:
        s = _causal_mask(s, i * bq, j * bk)
    m_prev = m_scr[...]  # [bq, LANES]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes_to(m_new, bk))
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * _lanes_to(alpha, d) + _dot(p, vf, NN_DIMS)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # causal rows always see kv block 0, so l > 0 everywhere
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / _lanes_to(l, d)).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _flash_fwd(q3, k3, v3, causal, block_q, block_kv, interpret):
    """[bh, s, d] x3 -> (o [bh, s, d], lse [bh, s] f32)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s_q, d = q3.shape
    s_k = k3.shape[1]
    bq = _fit_block(block_q or 512, s_q)
    bk = _fit_block(block_kv or 512, s_k)
    o, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, scale=d**-0.5, causal=causal, bq=bq, bk=bk
        ),
        grid=(bh, s_q // bq, s_k // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, s_q, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="tpx_flash_fwd",
        **_compiler_params(interpret),
    )(q3, k3, v3)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# flash attention backward (standard two-kernel flash bwd)
# ---------------------------------------------------------------------------


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
    scale, causal, bq, bk,
):
    import jax.experimental.pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qf = q_ref[...].astype(jnp.float32)
    kf = k_ref[...].astype(jnp.float32)
    vf = v_ref[...].astype(jnp.float32)
    dof = do_ref[...].astype(jnp.float32)
    s = _dot(qf * scale, kf, NT_DIMS)  # [bq, bk]
    if causal:
        s = _causal_mask(s, i * bq, j * bk)
    # lse/delta arrive as [1, bq] rows; one column per q row here
    lse = jnp.expand_dims(lse_ref[0], -1)  # [bq, 1]
    delta = jnp.expand_dims(delta_ref[0], -1)
    p = jnp.exp(s - lse)  # exact softmax from the saved logsumexp
    dp = _dot(dof, vf, NT_DIMS)  # [bq, bk]
    ds = p * (dp - delta)
    dq_scr[...] += _dot(ds, kf, NN_DIMS) * scale

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, bq, bk,
):
    """Works on the transposed ``[bk, bq]`` score tile, so every matmul
    contracts a minor-most or a leading dim (no transposed-LHS dot) and
    lse/delta broadcast down the sublanes as ``[1, bq]`` rows."""
    import jax.experimental.pallas as pl

    j, i = pl.program_id(1), pl.program_id(2)  # q blocks sequential here

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qf = q_ref[...].astype(jnp.float32)
    kf = k_ref[...].astype(jnp.float32)
    vf = v_ref[...].astype(jnp.float32)
    dof = do_ref[...].astype(jnp.float32)
    st = _dot(kf, qf * scale, NT_DIMS)  # [bk, bq]
    if causal:
        kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(q_pos >= kv_pos, st, NEG_INF)
    pt = jnp.exp(st - lse_ref[:1, :])  # [bk, bq]
    dv_scr[...] += _dot(pt, dof, NN_DIMS)  # [bk, d]
    dpt = _dot(vf, dof, NT_DIMS)  # [bk, bq]
    dst = pt * (dpt - delta_ref[:1, :])
    dk_scr[...] += _dot(dst, qf, NN_DIMS) * scale  # [bk, d]

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


#: f32 sublane tile height: the dkv kernel reads lse/delta as
#: ``[SUBLANES, bq]`` blocks broadcast down the sublanes.
SUBLANES = 8


def _flash_bwd(q3, k3, v3, o, lse, do, causal, block_q, block_kv, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s_q, d = q3.shape
    s_k = k3.shape[1]
    bq = _fit_block(block_q or 512, s_q)
    bk = _fit_block(block_kv or 512, s_k)
    scale = d**-0.5
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # [bh, s_q]

    q_spec = pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk
        ),
        grid=(bh, s_q // bq, s_k // bk),  # kv sequential: dq accumulates
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="tpx_flash_dq",
        **_compiler_params(interpret),
    )(q3, k3, v3, do, lse[:, None, :], delta[:, None, :])

    # dkv grid swaps roles: q blocks are innermost/sequential, the dk/dv
    # scratch at kv position j accumulates across q blocks.
    q_spec_t = pl.BlockSpec((None, bq, d), lambda b, j, i: (b, i, 0))
    kv_spec_t = pl.BlockSpec((None, bk, d), lambda b, j, i: (b, j, 0))
    row_spec_t = pl.BlockSpec((None, SUBLANES, bq), lambda b, j, i: (b, 0, i))
    rows = lambda x: jnp.broadcast_to(  # noqa: E731
        x[:, None, :], (bh, SUBLANES, s_q)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk
        ),
        grid=(bh, s_k // bk, s_q // bq),
        in_specs=[
            q_spec_t, q_spec_t, row_spec_t, row_spec_t, kv_spec_t, kv_spec_t
        ],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_k, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="tpx_flash_dkv",
        **_compiler_params(interpret),
    )(q3, do, rows(lse), rows(delta), k3, v3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, block_q, block_kv, interpret):
    return _flash_fwd(q3, k3, v3, causal, block_q, block_kv, interpret)[0]


def _flash_vjp_fwd(q3, k3, v3, causal, block_q, block_kv, interpret):
    o, lse = _flash_fwd(q3, k3, v3, causal, block_q, block_kv, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_vjp_bwd(causal, block_q, block_kv, interpret, res, do):
    q3, k3, v3, o, lse = res
    return _flash_bwd(
        q3, k3, v3, o, lse, do, causal, block_q, block_kv, interpret
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jnp.ndarray,  # [b, s, h, d]
    k: jnp.ndarray,  # [b, s, kv_h, d]
    v: jnp.ndarray,
    causal: bool = True,
    kernels: str = "pallas",
    block_q: int = 0,
    block_kv: int = 0,
    mesh=None,
) -> Optional[jnp.ndarray]:
    """Fused flash attention, or ``None`` when gating says "fall back".

    ``None`` is returned when: ``kernels`` does not select this module,
    ``"pallas"`` was asked for off-TPU (the reference ops are faster than
    the interpreter there — TPX112's warning), or the shapes fail
    :func:`flash_shapes_ok`. The caller keeps the reference path as the
    single fallback. A mesh that does not divide batch/heads raises.
    """
    if kernels not in ("pallas", "interpret"):
        return None
    if kernels == "pallas" and not _on_tpu():
        return None
    if not flash_shapes_ok(q.shape[1], k.shape[1], q.shape[-1]):
        return None
    if q.shape[2] % k.shape[2]:
        return None
    interpret = kernels == "interpret"
    n_rep = q.shape[2] // k.shape[2]

    def kernel(q4, k4, v4, seg):  # noqa: ANN001 (matches _shard_wrap)
        k4 = _repeat_kv(k4, n_rep)
        v4 = _repeat_kv(v4, n_rep)
        b, s, h, d = q4.shape
        to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)  # noqa: E731
        o3 = _flash(
            to3(q4), to3(k4), to3(v4), causal, block_q, block_kv, interpret
        )
        return o3.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    note_traced("attention", "fused_flash")
    if mesh is None:
        return kernel(q, k, v, None)
    return _shard_wrap(kernel, q, k, v, None, mesh, ("dp", "fsdp"), "tp")


# ---------------------------------------------------------------------------
# fused residual-add + RMSNorm
# ---------------------------------------------------------------------------


def _rms_norm_residual_math(x, res, weight, eps):
    """Reference path: exactly the unfused op sequence, so the fused
    kernels can be parity-tested bitwise against it."""
    s = x + res
    return _rms_norm_fwd_math(s, weight, eps), s


def _norm_res_kernel(x_ref, r_ref, w_ref, y_ref, s_ref, *, eps: float):
    s = x_ref[...] + r_ref[...]  # input dtype: bitwise == unfused add
    s_ref[...] = s
    sf = s.astype(jnp.float32)
    # reciprocal(sqrt(...)) rather than rsqrt: bitwise-identical to
    # _rms_norm_fwd_math under the interpreter (the parity tests check it)
    rrms = jnp.reciprocal(
        jnp.sqrt(jnp.mean(sf * sf, axis=-1, keepdims=True) + eps)
    )
    y_ref[...] = ((sf * rrms) * w_ref[...].astype(jnp.float32)).astype(
        y_ref.dtype
    )


#: VMEM bytes per block element: two inputs and two outputs in the stream
#: dtype, double-buffered (4 x 2 x 2 B), plus ~3 f32 temporaries.
_NORM_RES_BYTES_PER_ELT = 28


def _norm_res_pallas(x2d, r2d, weight, eps, interpret):
    """-> (y [n, d], s [n, d]) or None when the shard doesn't tile."""
    import jax.experimental.pallas as pl

    n, d = x2d.shape
    rows = _pick_rows(n, d, _NORM_RES_BYTES_PER_ELT)
    if rows == 0 or d % 128:
        return None
    return pl.pallas_call(
        functools.partial(_norm_res_kernel, eps=eps),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2d.dtype),
            jax.ShapeDtypeStruct((n, d), x2d.dtype),
        ],
        interpret=interpret,
        name="tpx_norm_residual",
    )(x2d, r2d, weight.reshape(1, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rms_norm_residual_fused(x, res, weight, eps, interpret):
    return _rms_norm_residual_math(x, res, weight, eps)


def _nr_fwd(x, res, weight, eps, interpret):
    d = x.shape[-1]
    out = _norm_res_pallas(
        x.reshape(-1, d), res.reshape(-1, d), weight, eps, interpret
    )
    if out is None:  # untileable shard: plain math, same values
        _refuse_on_tpu(
            interpret, f"{x.shape} rows do not tile the fused norm kernel"
        )
        y, s = _rms_norm_residual_math(x, res, weight, eps)
    else:
        y, s = (a.reshape(x.shape) for a in out)
    return (y, s), (s, weight)


def _nr_bwd(eps, interpret, resids, cot):
    s, weight = resids
    dy, ds_out = cot
    d = s.shape[-1]
    # the dx+dw kernel from ops/norms runs on the summed stream s; the
    # extra ds_out cotangent (s is also an output) adds straight through
    dx2d, dw = _bwd_pallas(
        s.reshape(-1, d), dy.reshape(-1, d), weight, eps, interpret=interpret
    )
    ds = dx2d.reshape(s.shape).astype(s.dtype) + ds_out
    return ds, ds, dw.astype(weight.dtype)


_rms_norm_residual_fused.defvjp(_nr_fwd, _nr_bwd)


def rms_norm_residual(
    x: jnp.ndarray,
    residual: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-5,
    kernels: str = "reference",
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``s = x + residual; y = rms_norm(s) * weight`` -> ``(y, s)``.

    Unlike :func:`flash_attention` this never returns ``None``: a
    ``kernels`` value that does not select the kernel, and a feature dim
    that fails :func:`norm_shapes_ok`, run the reference op sequence with
    identical values, so call sites need no fallback branch. ``mesh``
    plays the same role as in :func:`torchx_tpu.ops.norms.rms_norm` —
    Mosaic kernels cannot be auto-partitioned, so a sharded stream runs
    the kernel under a full-manual shard_map (weight replicated, its grad
    summed by the shard_map transpose).
    """
    if kernels not in ("pallas", "interpret"):
        return _rms_norm_residual_math(x, residual, weight, eps)
    if kernels == "pallas" and not _on_tpu():
        return _rms_norm_residual_math(x, residual, weight, eps)
    interpret = kernels == "interpret"
    if not norm_shapes_ok(x.shape[-1]):
        note_traced("norm_residual", "reference")
        return _rms_norm_residual_math(x, residual, weight, eps)
    from torchx_tpu.parallel.mesh import manual_axes

    if manual_axes():
        # inside a parent manual region (pipeline stage): a nested
        # shard_map would rebind axes, so the kernel cannot run here
        _refuse_on_tpu(interpret, "no fused norm inside a pipeline stage")
        return _rms_norm_residual_math(x, residual, weight, eps)
    note_traced("norm_residual", "fused")
    if mesh is None or all(s == 1 for s in dict(mesh.shape).values()):
        return _rms_norm_residual_fused(x, residual, weight, eps, interpret)

    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    batch_axes = tuple(a for a in ("dp", "fsdp") if sizes.get(a, 1) > 1)
    batch_div = 1
    for a in batch_axes:
        batch_div *= sizes[a]
    seq_axis = (
        "sp"
        if x.ndim == 3
        and sizes.get("sp", 1) > 1
        and x.shape[1] % sizes["sp"] == 0
        else None
    )
    if x.ndim != 3 or (batch_div > 1 and x.shape[0] % batch_div):
        _refuse_on_tpu(
            interpret, f"{x.shape} does not divide the mesh's {batch_axes}"
        )
        return _rms_norm_residual_math(x, residual, weight, eps)
    x_spec = P(batch_axes or None, seq_axis, None)
    from torchx_tpu.parallel.mesh import shard_map as tpx_shard_map

    fn = tpx_shard_map(
        lambda xs, rs, ws: _rms_norm_residual_fused(xs, rs, ws, eps, interpret),
        mesh=mesh,
        in_specs=(x_spec, x_spec, P(None)),
        out_specs=(x_spec, x_spec),
        axis_names=frozenset(sizes),  # Mosaic needs a fully-manual context
        check_vma=False,
    )
    return fn(x, residual, weight)


def resolve_kernels(requested: str) -> str:
    """Resolve a ``--kernels`` request against the runtime platform:
    ``"pallas"`` off-TPU becomes ``"reference"`` (what TPX112 warns
    about at launch time); everything else passes through. On a TPU
    ``"pallas"`` stays ``"pallas"``, and from there on gives way to
    nothing (see the module docstring)."""
    if requested == "pallas" and not _on_tpu():
        return "reference"
    return requested
