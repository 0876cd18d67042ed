"""Normalization ops.

RMSNorm computes in float32 regardless of input dtype (bf16 squares
underflow badly) and casts back — the standard TPU-stable recipe. The
*forward* needs no custom kernel: XLA fuses the whole thing into the
surrounding matmul's epilogue.

The *backward* is a different story (round-4 xprof, docs/performance.md):
autodiff of ``x_hat * w`` emits the weight-grad ``sum_{b,s}(dy * x_hat)``
as a separate ``[d]``-output reduction dot per layer. XLA schedules those
on the MXU as skinny matmuls — ~6% of the training step re-reading
activations the dx pass already read. :func:`rms_norm` therefore carries a
custom VJP whose backward is one fused Pallas kernel producing ``dx`` and
``dw`` in a single read of ``x``/``dy`` (grid-sequential f32 accumulation
of ``dw``), used on TPU when shapes allow; elsewhere the plain-XLA
backward applies (identical math, f32 accumulation, reduction order aside).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_norm_fwd_math(
    x: jnp.ndarray, weight: jnp.ndarray, eps: float
) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    rrms = jnp.reciprocal(
        jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    )
    return ((xf * rrms) * weight.astype(jnp.float32)).astype(dtype)


def _bwd_math(x, weight, dy, eps):
    """Reference backward (pure XLA): returns (dx, dw[f32])."""
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    rrms = jnp.reciprocal(
        jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    )
    xhat = xf * rrms
    dw = jnp.sum(dyf * xhat, axis=tuple(range(x.ndim - 1)))
    dxhat = dyf * wf
    c = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = (rrms * (dxhat - xhat * c)).astype(x.dtype)
    return dx, dw


def _bwd_kernel(x_ref, dy_ref, w_ref, dx_ref, dw_ref, *, eps: float):
    """Fused dx + dw for one [rows, d] tile; dw accumulates across the
    sequential TPU grid."""
    import jax.experimental.pallas as pl

    xf = x_ref[...].astype(jnp.float32)
    dyf = dy_ref[...].astype(jnp.float32)
    wf = w_ref[...].astype(jnp.float32)  # [1, d]
    rrms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xhat = xf * rrms
    dxhat = dyf * wf
    c = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rrms * (dxhat - xhat * c)).astype(dx_ref.dtype)
    dw_tile = jnp.sum(dyf * xhat, axis=0, keepdims=True)  # [1, d] f32

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = dw_tile

    @pl.when(pl.program_id(0) > 0)
    def _acc():
        dw_ref[...] += dw_tile


def _pick_rows(n: int, d: int = 2048, bytes_per_elt: int = 22) -> int:
    """Largest row-tile that divides ``n`` and keeps ``bytes_per_elt`` per
    block element under 12 MiB of the 16 MiB scoped VMEM. The default is
    the norm backward's: 3 bf16 io blocks double-buffered (12 B) plus the
    f32 temporaries live at once (xf/dyf and one product, ~10 B)."""
    for r in (1024, 512, 256, 128, 64, 32, 16, 8):
        if n % r == 0 and r * d * bytes_per_elt <= 12 * 1024 * 1024:
            return r
    return 0


def _refuse_on_tpu(interpret: bool, why: str) -> None:
    """A Pallas norm kernel that was asked for and passed the static shape
    gate is the Mosaic kernel or an error: on the chip a later give-way
    raises. The interpreter keeps the plain-math fallback the CPU parity
    tests rely on."""
    if not interpret:
        raise ValueError(f"fused norm kernel unavailable: {why}")


def _bwd_pallas(x2d, dy2d, weight, eps: float, interpret: bool = False):
    """-> (dx [n, d], dw [d] f32) via the fused kernel."""
    import jax.experimental.pallas as pl

    n, d = x2d.shape
    rows = _pick_rows(n, d)
    if rows == 0 or d % 128:
        # untileable shard (interpret mode bypasses _fused_ok, and the
        # sharded path re-tiles on PER-SHARD rows): plain math, same grads
        _refuse_on_tpu(interpret, f"{x2d.shape} rows do not tile")
        return _bwd_math(x2d, weight, dy2d, eps)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2d.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
        name="tpx_norm_bwd",
    )(x2d, dy2d, weight.reshape(1, d))
    return dx, dw[0]


def _fused_ok(x: jnp.ndarray) -> bool:
    """The static gate: TPU only, lane-aligned feature dim, tileable row
    count."""
    n = 1
    for s in x.shape[:-1]:
        n *= s
    return (
        jax.default_backend() == "tpu"
        and x.ndim >= 2
        and x.shape[-1] % 128 == 0
        and _pick_rows(n, x.shape[-1]) > 0
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_fused(x, weight, eps, interpret):
    return _rms_norm_fwd_math(x, weight, eps)


def _fused_fwd(x, weight, eps, interpret):
    return _rms_norm_fwd_math(x, weight, eps), (x, weight)


def _fused_bwd(eps, interpret, res, dy):
    x, weight = res
    d = x.shape[-1]
    dx2d, dw = _bwd_pallas(
        x.reshape(-1, d), dy.reshape(-1, d), weight, eps, interpret=interpret
    )
    # Under shard_map the weight enters replicated (P(None)) and the
    # shard_map transpose psums its cotangent over the axes the region's
    # specs shard rows over — measured: a mesh sharding rows over
    # (dp, fsdp, sp) sums those shards exactly once, and axes that merely
    # replicate the rows (tp/ep) are treated as carrying replicated
    # cotangents (which these are). The local row-shard dw is therefore
    # exactly right as-is.
    return dx2d.reshape(x.shape), dw.astype(weight.dtype)


_rms_norm_fused.defvjp(_fused_fwd, _fused_bwd)


def rms_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-5,
    fused: str = "auto",
    mesh=None,
) -> jnp.ndarray:
    """RMS-normalize ``x`` over its last axis and scale by ``weight``.

    ``fused`` selects the backward:

    * "auto" (default) — the plain XLA backward. Measured on v5e-1
      (round 5, docs/performance.md): the weight-grad reductions already
      lower as multiply-reduce fusions at ~1.3% of the step, and the
      Pallas kernel — while itself nearly free (0.01% of step) — costs
      ~0.3pp MFU in fusion opportunities at the custom_vjp boundary, so
      plain is the measured-fastest default. Overridable per-process with
      ``TPX_FUSED_NORM``.
    * "pallas" — force the fused dx+dw kernel (re-evaluate at batch >= 8
      or on hardware where the reductions lower as skinny MXU dots).
    * "interpret" — the kernel in the Pallas interpreter (CPU tests).
    * "never" — plain XLA backward, no env override.

    ``mesh`` must be passed when batch/seq may be sharded and the fused
    kernel is wanted: like every Mosaic kernel it cannot be automatically
    partitioned, so on a multi-device mesh it runs under a full-manual
    shard_map — [b, s, d] x over (dp, fsdp) x sp, weight replicated, the
    weight grad summed over the row shards by the shard_map transpose.
    """
    if fused == "auto":
        import os

        from torchx_tpu.settings import ENV_TPX_FUSED_NORM

        fused = os.environ.get(ENV_TPX_FUSED_NORM, "never")
    interpret = fused == "interpret"
    if not (interpret or (fused == "pallas" and _fused_ok(x))):
        return _rms_norm_fwd_math(x, weight, eps)
    from torchx_tpu.parallel.mesh import manual_axes

    if manual_axes():
        # inside a shard_map manual region (a pipeline stage): opening a
        # nested shard_map over the concrete mesh would rebind the
        # parent's axes (rejected by Shardy), so the kernel cannot run
        _refuse_on_tpu(interpret, "no fused norm inside a pipeline stage")
        return _rms_norm_fwd_math(x, weight, eps)
    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        return _rms_norm_fused(x, weight, eps, interpret)

    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    batch_axes = tuple(a for a in ("dp", "fsdp") if sizes.get(a, 1) > 1)
    batch_div = 1
    for a in batch_axes:
        batch_div *= sizes[a]
    seq_axis = (
        "sp"
        if x.ndim == 3 and sizes.get("sp", 1) > 1 and x.shape[1] % sizes["sp"] == 0
        else None
    )
    if x.ndim != 3 or (batch_div > 1 and x.shape[0] % batch_div):
        _refuse_on_tpu(
            interpret, f"{x.shape} does not divide the mesh's {batch_axes}"
        )
        return _rms_norm_fwd_math(x, weight, eps)  # unshardable: plain path
    x_spec = P(batch_axes or None, seq_axis, None)
    from torchx_tpu.parallel.mesh import shard_map as tpx_shard_map

    fn = tpx_shard_map(
        lambda xs, ws: _rms_norm_fused(xs, ws, eps, interpret),
        mesh=mesh,
        in_specs=(x_spec, P(None)),
        out_specs=x_spec,
        axis_names=frozenset(sizes),  # Mosaic needs a fully-manual context
        check_vma=False,
    )
    return fn(x, weight)
