"""Predicted step cost: how tune orders survivors before measuring.

The ranking is deliberately coarse — it only has to order candidates,
not predict wall clock — but it is built from the same terms the explain
report shows: per-axis collective bytes over the generation's ICI/DCN
bandwidth, a roofline compute floor, and an HBM-pressure penalty (a plan
that fits at 99% of budget thrashes the allocator and forfeits fusion
headroom; prefer slack). The per-generation ``step_time_scale`` from the
calibration table owns the whole measured time residual and multiplies
the total, so every measured run tightens future rankings (the byte-level
``collective_scale`` stays with the explain report — applying both here
would double-count one correction).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from torchx_tpu.analyze import costmodel
from torchx_tpu.analyze.plan import ParallelPlan


@dataclasses.dataclass(frozen=True)
class GenerationPerf:
    """Roofline constants for one accelerator generation (per chip)."""

    flops: float  # peak bf16 FLOP/s
    ici_bytes_per_s: float  # per-link ICI bandwidth
    dcn_bytes_per_s: float  # effective cross-slice bandwidth


#: Public-spec-order-of-magnitude constants; the calibration table owns
#: the residual error, so these only need to be relatively sane.
GENERATION_PERF: dict[str, GenerationPerf] = {
    "v2": GenerationPerf(46e12, 70e9, 10e9),
    "v3": GenerationPerf(123e12, 112e9, 10e9),
    "v4": GenerationPerf(275e12, 300e9, 25e9),
    "v5e": GenerationPerf(197e12, 200e9, 25e9),
    "v5p": GenerationPerf(459e12, 450e9, 25e9),
    "v6e": GenerationPerf(918e12, 450e9, 50e9),
    "v7x": GenerationPerf(2300e12, 900e9, 100e9),
}

#: CPU-sim fallback: arbitrary but consistent, keeps rankings meaningful
#: on the forced-host-device backend.
_DEFAULT_PERF = GenerationPerf(1e12, 10e9, 1e9)

#: MFU the compute floor assumes — a constant factor, so it cannot
#: reorder candidates, only keep the seconds plausible.
ASSUMED_MFU = 0.5

#: HBM pressure (total / usable) above which the penalty ramps in.
PRESSURE_KNEE = 0.85


def perf_for(generation: str) -> GenerationPerf:
    """Roofline constants for an accelerator string. Only a string with no
    TPU generation in it (CPU sim, empty) gets the CPU-sim default; a TPU
    generation missing from :data:`GENERATION_PERF` is an error."""
    from torchx_tpu.tune.calibrate import generation_key

    key = generation_key(generation)
    if key == "cpu-sim":
        return _DEFAULT_PERF
    if key not in GENERATION_PERF:
        raise ValueError(
            f"no roofline constants for {generation!r} (key {key!r});"
            f" have {sorted(GENERATION_PERF)}"
        )
    return GENERATION_PERF[key]


@dataclasses.dataclass(frozen=True)
class StepCost:
    """Predicted per-step cost of one candidate plan."""

    step_s: float
    compute_s: float
    collective_s: float
    collective_bytes: int
    hbm_pressure: float  # total / usable (under the calibrated fit)
    penalty: float  # multiplicative HBM-pressure factor (>= 1)

    def to_dict(self) -> dict:
        return {
            "step_s": self.step_s,
            "compute_s": self.compute_s,
            "collective_s": self.collective_s,
            "collective_bytes": self.collective_bytes,
            "hbm_pressure": self.hbm_pressure,
            "penalty": self.penalty,
        }


def predicted_step_cost(
    plan: ParallelPlan,
    *,
    generation: str = "",
    calibration: Optional[object] = None,
    headroom: float = costmodel.DEFAULT_HEADROOM,
) -> StepCost:
    """Rank key for one plan: compute floor + collective time, scaled by
    the HBM-pressure penalty and the generation's calibration."""
    perf = perf_for(generation or plan.accelerator)
    m = plan.model

    # roofline compute floor: 6 * active params * tokens per chip
    tokens_per_chip = plan.batch * plan.seq / max(1, plan.devices)
    flops_per_chip = 6.0 * m.active_param_count() * tokens_per_chip
    compute_s = flops_per_chip / (perf.flops * ASSUMED_MFU)

    # collective bytes are deliberately UNCALIBRATED here: observe()
    # folds the step-time residual into step_time_scale AND (for the
    # explain report) collective_scale, so applying both to the same
    # prediction would double-count the correction and oscillate
    traffic = costmodel.collective_traffic(plan)
    collective_s = 0.0
    collective_bytes = 0
    for t in traffic:
        bw = perf.dcn_bytes_per_s if t.network in ("dcn", "mixed") else (
            perf.ici_bytes_per_s
        )
        collective_s += t.bytes_per_step / bw
        collective_bytes += t.bytes_per_step

    fit = costmodel.hbm_fit(plan, headroom=headroom, calibration=calibration)
    usable = max(1, int(fit.budget_bytes * fit.headroom))
    pressure = fit.total_bytes / usable
    # fits-at-the-brink plans lose allocator/fusion headroom: ramp a
    # penalty from the knee; an exceeding plan should already be pruned,
    # but rank it last if one slips through (headroom override races)
    penalty = 1.0 + 2.0 * max(0.0, pressure - PRESSURE_KNEE)

    scale = float(getattr(calibration, "step_time_scale", 1.0) or 1.0)
    # charge only the EXPOSED share of collective time: profiled runs
    # measure how much comm the schedule hides behind compute (bucketed
    # grad sync, async collectives) and the calibration carries it as
    # overlap_frac; uncalibrated -> discount 1.0, identical to before
    exposed_collective_s = collective_s * costmodel.overlap_discount(
        calibration
    )
    step_s = (compute_s + exposed_collective_s) * penalty * scale
    return StepCost(
        step_s=step_s,
        compute_s=compute_s,
        collective_s=collective_s,
        collective_bytes=collective_bytes,
        hbm_pressure=pressure,
        penalty=penalty,
    )
