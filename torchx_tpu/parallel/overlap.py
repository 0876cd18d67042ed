"""Bucketed gradient-sync overlap (the ``--grad-bucket-mb`` knob).

The trainer's post-backward gradient reduction is one logical all-reduce
over the whole parameter tree. Fused into a single collective it cannot
start until the *last* backward contribution is ready, so none of it
overlaps compute. This module splits the tree into size-capped buckets in
reverse-layer order — the order backward produces gradients — so each
bucket's reduce can issue as soon as its leaves exist, hiding collective
time behind the rest of the backward pass (TorchTitan's async-TP result,
2410.06511, translated to the JAX scheduling model).

Two execution modes, one semantics:

* **gspmd** (the default inside the jit train step, where the
  partitioner owns the reduces) — per-bucket
  :func:`jax.lax.optimization_barrier`. The barrier is a
  value-identity, so gradients are **bitwise identical** to the unbucketed
  step; what changes is scheduling: XLA can no longer fuse the per-leaf
  reduces into one giant post-backward collective, and its
  latency-hiding scheduler overlaps each bucket's reduce with the
  still-running backward. Today's single-sync semantics are preserved by
  construction.
* **manual** (shard_map meshes, and the unit-testable ground truth) —
  :func:`bucketed_psum`: one :func:`jax.lax.psum` per bucket over the
  data-parallel axis, chained through an optimization barrier so buckets
  issue in reverse-layer order. psum is leafwise, so any bucketing —
  including one bucket for the whole tree — produces bitwise-identical
  per-leaf sums; the bucket boundary is pure scheduling.

``resolve_bucket_mb`` picks the cap ``remat_auto``-style: deterministic
candidate ladder, one trial record per candidate, first acceptable
choice wins — the trainer logs the trials next to the remat ones.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: Candidate bucket caps (MiB) tried by auto selection, small first —
#: smaller buckets start overlapping earlier in the backward pass.
BUCKET_MB_CANDIDATES: Tuple[int, ...] = (4, 8, 16, 32, 64, 128)

#: Auto selection aims near this many buckets: enough boundaries for the
#: scheduler to overlap, few enough that per-collective launch latency
#: stays amortized.
TARGET_BUCKETS = 8

_MIB = 1024 * 1024


def _nbytes(leaf: Any) -> int:
    """Works for concrete arrays and ShapeDtypeStruct-likes alike."""
    size = getattr(leaf, "size", None)
    if size is None:
        size = math.prod(getattr(leaf, "shape", ()) or (1,))
    return int(size) * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize


@dataclass(frozen=True)
class BucketPlan:
    """Size-capped grouping of gradient-tree leaves, reverse-layer order.

    ``buckets`` holds tuples of *flattened-leaf indices*; iteration order
    is the issue order (last-produced leaves first). A leaf larger than
    the cap gets a bucket of its own — it cannot be split without
    changing the collective's shape.
    """

    bucket_bytes: int
    buckets: Tuple[Tuple[int, ...], ...]
    total_bytes: int

    @property
    def n_buckets(self) -> int:
        """Number of buckets (== number of per-bucket reduces issued)."""
        return len(self.buckets)

    @property
    def largest_bucket_bytes(self) -> int:
        """Byte size of the largest bucket (the overlap-limiting one)."""
        return self._largest

    def describe(self) -> dict:
        """Loggable summary: cap, bucket count, total and largest MiB."""
        return {
            "bucket_mb": self.bucket_bytes // _MIB,
            "n_buckets": self.n_buckets,
            "total_mb": round(self.total_bytes / _MIB, 3),
            "largest_bucket_mb": round(self._largest / _MIB, 3),
        }

    @property
    def _largest(self) -> int:
        if not self.buckets:
            return 0
        return max(sum(self._leaf_bytes[i] for i in b) for b in self.buckets)

    # populated by plan_buckets (object.__setattr__: frozen dataclass)
    _leaf_bytes: Tuple[int, ...] = ()


def plan_buckets(tree: Any, bucket_bytes: int) -> BucketPlan:
    """Greedy size-capped bucketing of ``tree``'s leaves in **reverse**
    flatten order (backward finishes the last layers first, so reverse
    order approximates gradient-ready order under ``lax.scan`` stacking).

    Deterministic: same tree structure + cap -> same plan, so the bucket
    layout never perturbs compilation caches between runs.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    sizes = tuple(_nbytes(leaf) for leaf in leaves)
    buckets: list[Tuple[int, ...]] = []
    cur: list[int] = []
    cur_bytes = 0
    for idx in reversed(range(len(leaves))):
        nb = sizes[idx]
        if cur and cur_bytes + nb > bucket_bytes:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(idx)
        cur_bytes += nb
        if cur_bytes >= bucket_bytes:  # oversize leaf: own bucket
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(tuple(cur))
    plan = BucketPlan(
        bucket_bytes=int(bucket_bytes),
        buckets=tuple(buckets),
        total_bytes=sum(sizes),
    )
    object.__setattr__(plan, "_leaf_bytes", sizes)
    return plan


@dataclass(frozen=True)
class BucketTrial:
    """One auto-selection candidate, recorded remat_auto-style so the
    trainer can log why a cap was (not) chosen."""

    bucket_mb: int
    n_buckets: int
    largest_bucket_mb: float
    chosen: bool
    reason: str

    def to_dict(self) -> dict:
        """JSON form for the trainer's results / bench trial logs."""
        return {
            "bucket_mb": self.bucket_mb,
            "n_buckets": self.n_buckets,
            "largest_bucket_mb": self.largest_bucket_mb,
            "chosen": self.chosen,
            "reason": self.reason,
        }


def resolve_bucket_mb(
    tree: Any,
    requested: Any = "auto",
    candidates: Sequence[int] = BUCKET_MB_CANDIDATES,
) -> Tuple[int, Tuple[BucketTrial, ...]]:
    """Resolve a ``--grad-bucket-mb`` request against a gradient tree.

    An explicit positive integer passes through (one trial record).
    ``"auto"``/``0`` walks the candidate ladder smallest-first and picks
    the first cap yielding at most :data:`TARGET_BUCKETS` buckets — the
    smallest cap (earliest overlap) that does not shred the tree into
    latency-dominated confetti. Falls back to the largest candidate.
    """
    if requested not in ("auto", 0, "0", None):
        mb = int(requested)
        if mb <= 0:
            raise ValueError(f"--grad-bucket-mb must be positive, got {mb}")
        plan = plan_buckets(tree, mb * _MIB)
        trial = BucketTrial(
            bucket_mb=mb,
            n_buckets=plan.n_buckets,
            largest_bucket_mb=round(plan.largest_bucket_bytes / _MIB, 3),
            chosen=True,
            reason="explicit --grad-bucket-mb",
        )
        return mb, (trial,)

    trials: list[BucketTrial] = []
    chosen: Optional[int] = None
    for mb in candidates:
        plan = plan_buckets(tree, mb * _MIB)
        ok = plan.n_buckets <= TARGET_BUCKETS
        pick = ok and chosen is None
        if pick:
            chosen = mb
        trials.append(
            BucketTrial(
                bucket_mb=mb,
                n_buckets=plan.n_buckets,
                largest_bucket_mb=round(plan.largest_bucket_bytes / _MIB, 3),
                chosen=pick,
                reason=(
                    "first cap with <= %d buckets" % TARGET_BUCKETS
                    if pick
                    else (
                        "acceptable but a smaller cap was already chosen"
                        if ok
                        else "too many buckets (collective launch latency)"
                    )
                ),
            )
        )
    if chosen is None:  # tiny trees: even the largest cap over-fragments
        chosen = candidates[-1]
        trials[-1] = BucketTrial(
            bucket_mb=chosen,
            n_buckets=trials[-1].n_buckets,
            largest_bucket_mb=trials[-1].largest_bucket_mb,
            chosen=True,
            reason="largest candidate (fallback)",
        )
    return chosen, tuple(trials)


def _apply_bucketed(leaves: list, plan: BucketPlan, combine) -> list:
    """Shared walk: run ``combine(tuple_of_values, anchor)`` per bucket in
    plan order, threading an anchor value so bucket i+1 cannot issue
    before bucket i. ``combine`` returns the replacement values."""
    out = list(leaves)
    anchor = None
    for bucket in plan.buckets:
        vals = tuple(out[i] for i in bucket)
        vals = combine(vals, anchor)
        for i, v in zip(bucket, vals):
            out[i] = v
        anchor = vals[0]
    return out


def apply_bucketed_barriers(grads: Any, plan: BucketPlan) -> Any:
    """GSPMD mode: value-identity barriers at bucket boundaries.

    Bitwise-safe (optimization_barrier changes scheduling, never values):
    the partitioner still inserts the same per-leaf reduces, but can no
    longer fuse them across bucket boundaries, and the chained anchor
    fixes their issue order to reverse-layer.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)

    def combine(vals, anchor):
        if anchor is not None:
            vals = jax.lax.optimization_barrier(tuple(vals) + (anchor,))[:-1]
        return jax.lax.optimization_barrier(vals)

    return jax.tree_util.tree_unflatten(
        treedef, _apply_bucketed(leaves, plan, combine)
    )


def bucketed_psum(grads: Any, axis_name: Any, plan: BucketPlan) -> Any:
    """Manual mode (inside shard_map): one psum per bucket, issue-ordered.

    psum is leafwise, so the per-leaf results are bitwise identical to a
    single whole-tree psum regardless of bucket size — the property the
    bucket-boundary tests pin down.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)

    def combine(vals, anchor):
        if anchor is not None:
            vals = jax.lax.optimization_barrier(tuple(vals) + (anchor,))[:-1]
        return jax.lax.psum(vals, axis_name)

    return jax.tree_util.tree_unflatten(
        treedef, _apply_bucketed(leaves, plan, combine)
    )


def bucketed_sync(
    grads: Any,
    *,
    bucket_mb: int,
    mode: str = "auto",
    axis_name: Any = "dp",
    plan: Optional[BucketPlan] = None,
) -> Tuple[Any, Optional[BucketPlan]]:
    """Bucket the gradient tree and apply the mode's per-bucket sync.

    ``bucket_mb <= 0`` is the off switch: grads pass through untouched
    (exactly today's single-sync step). ``mode``:

    * ``"auto"`` — ``"manual"`` inside a shard_map region that has the
      reduce axis bound manually, else ``"gspmd"`` (the jit train step,
      which preserves single-sync semantics bit for bit).
    * ``"gspmd"`` — :func:`apply_bucketed_barriers` (no collectives of
      its own; the partitioner owns the reduces).
    * ``"manual"`` — :func:`bucketed_psum` over ``axis_name``.

    Returns ``(grads, plan)``; plan is None when bucketing is off.
    """
    if bucket_mb is None or int(bucket_mb) <= 0:
        return grads, None
    if plan is None:
        plan = plan_buckets(grads, int(bucket_mb) * _MIB)
    if mode == "auto":
        from torchx_tpu.parallel.mesh import manual_axes

        # a collective axis is usable here iff an enclosing shard_map
        # manualized it
        names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        mode = "manual" if names and set(names) <= manual_axes() else "gspmd"
    if mode == "manual":
        return bucketed_psum(grads, axis_name, plan), plan
    if mode == "gspmd":
        return apply_bucketed_barriers(grads, plan), plan
    raise ValueError(f"unknown bucketed_sync mode {mode!r}")
