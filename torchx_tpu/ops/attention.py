"""Causal multi-head attention with GQA, TPU-first.

Kernel selection (``impl``):

* ``"pallas"`` — the Pallas TPU flash-attention kernel
  (jax.experimental.pallas.ops.tpu.flash_attention): O(seq) memory, tiled
  for the MXU. Used automatically on TPU for long sequences.
* ``"splash"`` — the Pallas TPU splash-attention kernel
  (jax.experimental.pallas.ops.tpu.splash_attention): sparse-aware flash
  with *native GQA* — KV heads are shared across query-head groups inside
  the kernel, so the 4x ``_repeat_kv`` HBM blow-up the flash path pays at
  Llama-3 shapes (32 q-heads over 8 kv-heads) disappears. This is the
  production MaxText kernel.
* ``"xla"`` — plain einsum softmax attention. XLA fuses this well for short
  sequences and it runs everywhere (CPU tests); also the numerical
  reference the pallas path is tested against.
* ``"auto"`` — splash on TPU when shapes allow (head_dim in {64, 128, 256},
  seq a multiple of 128 and >= 512, no packed segment_ids — the v5e sweep
  measured splash fastest at GQA shapes, docs/performance.md), else xla.
  The flash kernel is explicit-opt-in via ``"pallas"``.

All paths compute softmax in float32 and accept grouped KV heads
(n_kv_heads <= n_heads, Llama-3 GQA).

Every call site records what it lowered to in :data:`TRACED`, so a run can
report the kernel it used rather than the one it asked for. A kernel that
was selected and then cannot run (a mesh that does not divide the batch or
the heads) raises; only the static shape gates choose the XLA path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchx_tpu.obs import hot
from torchx_tpu.ops.quant import maybe_matmul


#: op name -> implementations its call sites lowered to in this process
#: (written at trace time, e.g. ``{"attention": {"splash"}}``).
TRACED: dict[str, set[str]] = {}


def note_traced(op: str, impl: str) -> None:
    """Record that a call site of ``op`` traced ``impl``."""
    TRACED.setdefault(op, set()).add(impl)


def traced(op: str) -> str:
    """What ``op`` lowered to so far: ``"splash"``, ``"splash+xla"`` when
    call sites differed, ``""`` when nothing traced it yet."""
    return "+".join(sorted(TRACED.get(op, ())))


def project_heads(x: jnp.ndarray, w, heads: int, hd: int) -> jnp.ndarray:  # noqa: ANN001
    """``x @ w`` split into heads, ``[..., d] -> [..., heads, hd]``: head ``j``
    is columns ``[j * hd, (j + 1) * hd)`` of the product.

    The barrier pins the product as the 2-D ``[rows, heads * hd]`` value it is,
    so the split is a reshape of the activation and never reaches the weight:
    the matmul takes its layer's slice of the parameter stack inside its own
    fusion, in the layout the tree has, as ``wo`` and the MLP do. With the
    reshape adjacent, XLA folds it into the matmul (however the product is
    written: flattened first, float32 out, operands swapped or transposed), the
    weight becomes ``[d, heads, hd]``, the chip's compiler runs the contraction
    as a convolution over the heads and asks for the weight as ``[heads, hd,
    d]``: every layer's ``wq``/``wk``/``wv`` sliced out of its stack and written
    transposed in front of a 16-64 row matmul, every step, 5 of the 23 ms
    ``k-exaone`` decode program (PERF.md section 6, PR 32). The decode programs
    are held to it on the chip's compiler (``obs.hlo.program_moves``,
    ``tests/test_paged_attention_kernel.py``);
    ``traced("projections")`` answers ``in_place``. The serving steps know it as
    ``generate._project_heads``; latent attention's ``W_qb`` goes through it too
    (``models/mla.py``)."""
    note_traced("projections", "in_place")
    return jax.lax.optimization_barrier(maybe_matmul(x, w)).reshape(*x.shape[:-1], heads, hd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[b, s, kv_heads, d] -> [b, s, kv_heads*n_rep, d]"""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def xla_attention(
    q: jnp.ndarray,  # [b, s, h, d]
    k: jnp.ndarray,  # [b, s, kv_h, d]
    v: jnp.ndarray,
    causal: bool = True,
    segment_ids: Optional[jnp.ndarray] = None,
    window: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Plain einsum softmax attention (f32 softmax, GQA via KV repeat);
    runs everywhere and is the numerical reference for the kernels.
    ``window`` > 0 (causal only) admits key ``j`` for query ``i`` where
    ``i - window < j <= i``: a sliding layer's local mask. ``scale``
    multiplies the scores; None: the head width to the power -1/2."""
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    s_q, s_k = q.shape[1], k.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool))
        if window:
            mask = mask & ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool), -window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _fit_block(requested: int, seq: int) -> int:
    """Largest multiple-of-128 divisor of ``seq`` that is <= ``requested``
    (clamped up to the 128-lane minimum) — both TPU kernels require blocks
    that divide the sequence and are lane multiples. 0 = no valid block
    (seq is not a multiple of 128)."""
    blk = (min(max(requested, 128), seq) // 128) * 128
    while blk >= 128 and seq % blk:
        blk -= 128
    return blk if blk >= 128 else 0


def _pallas_ok(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    return d in (64, 128, 256) and s_q % 128 == 0 and s_k % 128 == 0 and s_q >= 512


def pallas_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
) -> jnp.ndarray:
    """block_q/block_kv (0 = kernel defaults) tune the flash tiling.
    Profiling showed the default 128-blocks run the MXU half-empty at
    head_dim 64 (docs/performance.md) — larger blocks amortize that."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    kwargs = {}
    bq = bk = 0
    if block_q or block_kv:
        # 0 from _fit_block means 'no valid custom block, use defaults'
        bq = _fit_block(block_q or 128, q.shape[1])
        bk = _fit_block(block_kv or 128, k.shape[1])
    if bq and bk:  # only pass tiling the kernel will accept
        kwargs["block_sizes"] = BlockSizes(
            block_q=bq,
            block_k_major=bk,
            block_k=bk,
            block_b=1,
            block_q_major_dkv=bq,
            block_k_major_dkv=bk,
            block_k_dkv=bk,
            block_q_dkv=bq,
            block_k_major_dq=bk,
            block_k_dq=bk,
            block_q_dq=bq,
        )
    # pallas kernel takes [b, h, s, d]
    out = flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        sm_scale=q.shape[-1] ** -0.5,
        **kwargs,
    )
    return out.transpose(0, 2, 1, 3)


#: the most ``dq`` partials the fused backward may write: one ``q``-sized array a kv block, so this many times
#: ``q``'s bytes, transient, a call (256 MiB a layer at ``mistral7b-train-4k``'s 2 x 4096 x 32 x 128)
_DQ_PARTIALS = 4
#: the widest kv block the fused backward copies: ``k``, ``v``, ``dk``, ``dv`` and the float32 sums of the last two
#: at this many rows of 128 are 6 MiB of the kernel's 16 MiB of fast memory, beside the score tiles
_BWD_BLOCK_KV = 2048
#: the fused backward's q block at heads of 128 or narrower: half the grid steps of 512 rows, 7% off a call at the
#: train cell's shape (PERF.md section 6, PR 50); at heads of 256 under kv blocks of 2,048 Mosaic refuses it
_BWD_BLOCK_Q = 1024


def _backward_blocks(s_q: int, s_k: int, d: int, bq: int, bkv: int) -> dict:
    """The splash backward's blocks and form, from the sequence lengths and the head width under forward blocks
    ``bq`` x ``bkv``: the ``BlockSizes`` fields beside the forward's.

    Fused (one walk over the block pairs makes ``dk``, ``dv`` and ``dq`` from one ``S``, one ``P``, one ``dP`` a
    pair, and writes one ``dq`` partial a kv block that is summed behind the call) while the partials stay at
    :data:`_DQ_PARTIALS` times ``q`` or fewer: the kv block the backward copies is the narrowest multiple of
    ``bkv`` that divides ``s_k`` and leaves that few blocks, up to :data:`_BWD_BLOCK_KV` rows (the scores are still
    made ``bkv`` rows at a time), its q block :data:`_BWD_BLOCK_Q` rows where they divide ``s_q``. Past that (over
    8 k keys, or a length only narrow blocks divide) the two kernels at the forward's blocks, each of which makes
    the scores again and neither of which writes anything but its gradients."""
    for blk in range(bkv, min(_BWD_BLOCK_KV, s_k) + 1, bkv):
        if s_k % blk == 0 and s_k // blk <= _DQ_PARTIALS:
            wide = max(bq, _fit_block(_BWD_BLOCK_Q, s_q)) if d <= 128 else bq
            return dict(block_q_dkv=wide, block_kv_dkv=blk, block_kv_dkv_compute=bkv, use_fused_bwd_kernel=True)
    return dict(block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv, block_q_dq=bq, block_kv_dq=bkv)


def splash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
    segment_ids: Optional[jnp.ndarray] = None,
    interpret: bool = False,
    window: int = 0,
) -> jnp.ndarray:
    """Splash attention: GQA-native flash (no KV head repeat). ``window`` > 0
    (causal) is the kernel's local mask: ``window - 1`` keys to the left of
    the diagonal and none to the right, whole blocks outside it skipped.

    KV stays at ``n_kv_heads`` all the way into the kernel — at Llama-3
    GQA ratios that is 4x less KV HBM traffic than ``pallas_attention``'s
    ``_repeat_kv``. ``interpret=True`` runs the kernel in the Pallas
    interpreter so CPU tests can cover this path.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        BlockSizes,
        CausalMask,
        FullMask,
        LocalMask,
        MultiHeadMask,
        SegmentIds,
        make_splash_mha,
    )

    b, s_q, h, d = q.shape
    s_k = k.shape[1]

    bq = _fit_block(block_q or 512, s_q)
    bkv = _fit_block(block_kv or 1024, s_k)
    if not (bq and bkv):
        # _fit_block only fails when the sequence has no multiple-of-128
        # divisor, i.e. seq itself is not a multiple of 128
        raise ValueError(
            "splash attention needs sequence lengths that are multiples"
            f" of 128; got q_seq={s_q}, kv_seq={s_k}"
            " (use impl='xla' for ragged shapes)"
        )
    if window and causal:
        one_head = LocalMask((s_q, s_k), window_size=(window - 1, 0), offset=0)
    else:
        one_head = CausalMask((s_q, s_k)) if causal else FullMask((s_q, s_k))
    mask = MultiHeadMask([one_head] * h)
    backward = _backward_blocks(s_q, s_k, d, bq, bkv)
    note_traced("attention_bwd", "fused" if backward.get("use_fused_bwd_kernel") else "split")
    kernel = make_splash_mha(
        mask,
        head_shards=1,
        q_seq_shards=1,
        block_sizes=BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv, **backward),
        interpret=interpret,
    )
    seg = None
    if segment_ids is not None:
        seg = SegmentIds(q=segment_ids, kv=segment_ids)
    # kernel shapes: q [h, s, d], k/v [kv_h, s, d]; sm scale is the
    # caller's job (fold into q — cheaper than scaling the logits)
    out = jax.vmap(kernel, in_axes=(0, 0, 0, 0 if seg is not None else None))(
        q.transpose(0, 2, 1, 3) * (d**-0.5),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        seg,
    )
    return out.transpose(0, 2, 1, 3)


def _shard_wrap(kernel, q, k, v, segment_ids, mesh, batch_axes, head_axis):
    """Run a Pallas kernel under shard_map when the mesh shards its inputs.

    Mosaic lowering demands a FULLY-manual axis context (partial-manual is
    rejected with "Mosaic kernels cannot be automatically partitioned", see
    jax/_src/tpu_custom_call.py), so the wrap manualizes every mesh axis
    not already bound by a parent shard_map. Attention is embarrassingly
    parallel over batch and heads: batch shards over (dp, fsdp), heads over
    tp, the sequence axis stays whole (resharded at entry if the residual
    stream was sp-sharded), and nothing else moves — no collectives inside;
    fsdp/tp weight collectives stay outside, handled by the partitioner.

    Raises ValueError when the shapes don't divide the mesh: the kernel
    was selected, so running anything else would hide what the step uses.
    """
    sizes = dict(mesh.shape)
    if all(s == 1 for s in sizes.values()):
        # single-device mesh (the single-chip bench): nothing to partition
        return kernel(q, k, v, segment_ids)
    from torchx_tpu.parallel.mesh import manual_axes

    parent_manual = set(manual_axes())
    batch_axes = tuple(
        a for a in batch_axes if sizes.get(a, 1) > 1 and a not in parent_manual
    )
    if head_axis in parent_manual or sizes.get(head_axis, 1) <= 1:
        head_axis = None

    batch_div = 1
    for a in batch_axes:
        batch_div *= sizes[a]
    head_div = sizes.get(head_axis, 1) if head_axis else 1
    if (
        q.shape[0] % batch_div
        or q.shape[2] % head_div
        or k.shape[2] % head_div
    ):
        raise ValueError(
            f"batch {q.shape[0]} / heads {q.shape[2]} (kv {k.shape[2]}) do not"
            f" divide the mesh's {batch_axes} / {head_axis!r} axes; Pallas"
            " kernels need divisible shapes"
        )

    qkv_spec = P(batch_axes or None, None, head_axis, None)
    seg_spec = P(batch_axes or None, None)
    # Mosaic requires every mesh axis manual: bind all axes a parent
    # shard_map hasn't (size-1 and unused axes just replicate)
    manual = frozenset(sizes) - frozenset(parent_manual)
    from torchx_tpu.parallel.mesh import shard_map as tpx_shard_map

    fn = tpx_shard_map(
        kernel,
        in_specs=(
            qkv_spec,
            qkv_spec,
            qkv_spec,
            seg_spec if segment_ids is not None else None,
        ),
        out_specs=qkv_spec,
        axis_names=manual,
        check_vma=False,
        **(dict(mesh=None) if parent_manual else dict(mesh=mesh)),
    )
    return fn(q, k, v, segment_ids)


@jax.named_scope(hot.ATTN_KERNEL)
def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    segment_ids: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    block_q: int = 0,
    block_kv: int = 0,
    mesh=None,
    window: int = 0,
) -> jnp.ndarray:
    """[b, s, heads, head_dim] x3 -> [b, s, heads, head_dim].

    ``window`` > 0 makes the causal mask local (a sliding layer): splash's
    local mask where splash is chosen, the XLA function's elsewhere;
    ``traced("attention")`` answers ``splash_local`` / ``xla_local``.

    ``mesh`` (a jax.sharding.Mesh) must be passed when batch or heads are
    sharded and a Pallas kernel may be selected: Mosaic kernels cannot be
    automatically partitioned, so the kernel runs under a shard_map over
    the (dp, fsdp) batch axes and the tp head axis.
    """
    if window and (impl == "pallas" or not causal):
        raise ValueError("a window needs the causal mask and the splash or XLA path")
    if impl == "pallas" and segment_ids is not None:
        raise ValueError(
            "the pallas flash-attention path does not support segment_ids;"
            " use impl='xla' (or 'auto', which falls back) for packed"
            " cross-document masking"
        )
    use_splash = impl == "splash" or (
        # measured fastest on TPU (v5e sweep, docs/performance.md): splash
        # beats the flash kernel at GQA shapes (no KV repeat) — 46.9% vs
        # 39.6% MFU at llama3_1b — so "auto" prefers it when shapes allow
        impl == "auto"
        and segment_ids is None
        and _on_tpu()
        and _pallas_ok(q, k)
    )
    if use_splash or impl == "pallas":
        if use_splash:

            def kernel(q, k, v, seg):  # noqa: ANN001
                return splash_attention(
                    q, k, v, causal=causal, block_q=block_q,
                    block_kv=block_kv, segment_ids=seg, window=window,
                )
        else:

            def kernel(q, k, v, seg):  # noqa: ANN001
                return pallas_attention(
                    q, k, v, causal=causal, block_q=block_q, block_kv=block_kv
                )

        note_traced("attention", ("splash_local" if window else "splash") if use_splash else "pallas_flash")
        if mesh is None:
            return kernel(q, k, v, segment_ids)
        return _shard_wrap(
            kernel, q, k, v, segment_ids, mesh, ("dp", "fsdp"), "tp"
        )
    note_traced("attention", "xla_local" if window else "xla")
    return xla_attention(q, k, v, causal=causal, segment_ids=segment_ids, window=window)
