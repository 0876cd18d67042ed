"""Automatic rematerialization policy selection from AOT memory analysis.

The remat policy is a pure memory/recompute trade: ``dots_attn`` saves the
most activations (cheapest backward, biggest footprint), ``dots`` drops
the attention-kernel outputs, ``full`` recomputes everything. Today the
right choice depends on batch, sequence, mesh, and model size — picking it
by hand means either OOMing at scale or paying recompute FLOPs the HBM
could have absorbed.

``remat_policy="auto"`` resolves the choice at launch: each candidate
policy (cheapest recompute first) is AOT-lowered and compiled against
abstract inputs, the compiler's buffer assignment
(``compiled.memory_analysis()``, same accounting as
:mod:`torchx_tpu.parallel.aot_fit`) is checked against the device HBM
budget, and the first policy that fits wins. The trial compiles land in
the persistent XLA compilation cache, so the winner's real compile in the
trainer is a cache hit — the selection's marginal cost is roughly the
compiles of the candidates that did NOT fit.

The trainer (train/run.py) resolves "auto" before building the
train step and reports the chosen policy in its result dict and the
``step.*`` trace family; :mod:`bench` records it per bench leg.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
from jax.sharding import Mesh

from torchx_tpu.parallel.aot_fit import (
    DEFAULT_HEADROOM,
    FitResult,
    V5P_HBM_BYTES,
    compile_fit,
)

#: candidate policies, cheapest recompute (largest footprint) first — the
#: selection order: stop at the first one whose compiled step fits.
POLICY_ORDER: tuple[str, ...] = ("dots_attn", "dots", "full")


@dataclasses.dataclass
class PolicyTrial:
    """One candidate policy's fit verdict (for logs / bench JSON)."""

    policy: str
    fits: bool
    peak_bytes: int  # 0 when the trial compile failed
    error: Optional[str] = None


def device_hbm_bytes(default: int = V5P_HBM_BYTES) -> int:
    """Per-device HBM budget: the addressable device's ``bytes_limit``.
    The CPU backend reports none — there ``default`` (the v5p budget) keeps
    auto-selection meaningful in dryruns. A TPU that reports none is an
    error: assuming 95 GiB on a 16 GB chip picks the largest footprint."""
    device = jax.local_devices()[0]
    stats = device.memory_stats()
    if stats and stats.get("bytes_limit", 0) > 0:
        return int(stats["bytes_limit"])
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind} reports no memory_stats()['bytes_limit'];"
            " pass hbm_bytes explicitly"
        )
    return default


def choose_remat_policy(
    cfg: Any,
    mesh: Mesh,
    batch: int,
    seq: int,
    *,
    hbm_bytes: Optional[int] = None,
    headroom: float = DEFAULT_HEADROOM,
    fit_fn: Optional[Callable[[Any], FitResult]] = None,
) -> tuple[str, list[PolicyTrial]]:
    """Resolve ``remat_policy="auto"`` -> a concrete policy for this run.

    Tries :data:`POLICY_ORDER` in sequence and returns the first policy
    whose AOT-compiled train step fits ``hbm_bytes * headroom`` per
    device, plus the trial records for reporting. If nothing fits (or
    every trial compile fails) the answer is ``"full"`` — maximal
    recompute is the only remaining lever, and the real compile will
    surface the OOM with its own diagnostics.

    ``fit_fn`` overrides the fit oracle (a callable taking the candidate
    config and returning a :class:`~torchx_tpu.parallel.aot_fit.FitResult`)
    — tests inject mocked memory analyses; the default AOT-compiles via
    :func:`~torchx_tpu.parallel.aot_fit.compile_fit`.
    """
    budget = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    if fit_fn is None:
        fit_fn = lambda c: compile_fit(  # noqa: E731
            c, mesh, batch, seq, hbm_bytes=budget, headroom=headroom
        )
    trials: list[PolicyTrial] = []
    for policy in POLICY_ORDER:
        candidate = dataclasses.replace(cfg, remat=True, remat_policy=policy)
        try:
            res = fit_fn(candidate)
        except Exception as e:  # noqa: BLE001 - a failed trial is a verdict
            trials.append(
                PolicyTrial(policy=policy, fits=False, peak_bytes=0, error=str(e))
            )
            continue
        trials.append(
            PolicyTrial(policy=policy, fits=res.fits, peak_bytes=res.peak_bytes)
        )
        if res.fits:
            return policy, trials
    return "full", trials
