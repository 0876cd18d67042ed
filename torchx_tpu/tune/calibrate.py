"""Persisted per-generation calibration of the static cost model.

The deep-preflight cost model (``analyze/costmodel.py``) is first-order
arithmetic; its activation and collective terms carry generation-specific
error (XLA fusion, padding, kernel choice). Every measured tune/bench run
closes the loop: the observed ``measured / predicted`` ratio nudges a
per-generation scale via an EMA with gain ``alpha`` in (0, 1), so

    err_after = |1 - alpha| * err_before  <  err_before

whenever prediction != measurement — the model provably gets closer with
every observation. ``costmodel.hbm_fit`` / ``collective_traffic`` accept
the scales as an optional ``calibration`` argument (default None keeps
the uncalibrated behavior bit-identical), and the fleet placer's
``hbm_refusal`` oracle loads the same table per pool generation.

The table is one JSON file under ``$TPX_TUNE_DIR`` (default
``~/.torchx_tpu/tune``), written atomically (tmp + fsync + ``os.replace``)
so concurrent readers never see a torn file.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Optional

from torchx_tpu import settings

#: EMA gain: one observation moves a scale halfway to the measured ratio.
DEFAULT_ALPHA = 0.5

CALIBRATION_FILE = "calibration.json"


def tune_dir() -> str:
    """State root for tune journals + the calibration table."""
    return os.environ.get(settings.ENV_TPX_TUNE_DIR) or os.path.join(
        os.path.expanduser("~"), ".torchx_tpu", "tune"
    )


def generation_key(name: str) -> str:
    """Normalize an accelerator string to a calibration key.

    ``"TPU v5 lite"`` (the ``device_kind`` a v5e reports) / ``"TPU v5e"`` /
    ``"v5litepod-8"`` / ``"v5e"`` -> ``"v5e"``; ``"TPU v5p"`` -> ``"v5p"``;
    ``"TPU7x"`` -> ``"v7x"``; anything without a recognizable generation
    (CPU sim, empty) -> ``"cpu-sim"``.
    """
    m = re.search(r"(?:v|tpu)\s*(\d+)\s*([a-z]*)", str(name).lower())
    if m is None:
        return "cpu-sim"
    gen, suffix = m.groups()
    if suffix in ("lite", "litepod"):
        suffix = "e"
    return f"v{gen}{suffix}"


@dataclasses.dataclass
class CalibrationScales:
    """Multiplicative corrections for one accelerator generation.

    ``activation_scale`` rescales the activation-HBM term,
    ``collective_scale`` the per-axis collective bytes, and
    ``step_time_scale`` the end-to-end predicted step time (what the
    tune ranking and the bench error tracking consume).
    ``overlap_frac`` is the measured comm/compute overlap fraction (the
    step profiler's ``1 - exposed/modeled``): the ranking discounts the
    collective term by it instead of charging exposed comm at 100%.
    """

    activation_scale: float = 1.0
    collective_scale: float = 1.0
    step_time_scale: float = 1.0
    overlap_frac: float = 0.0
    samples: int = 0

    def to_dict(self) -> dict:
        return {
            "activation_scale": self.activation_scale,
            "collective_scale": self.collective_scale,
            "step_time_scale": self.step_time_scale,
            "overlap_frac": self.overlap_frac,
            "samples": self.samples,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationScales":
        return cls(
            activation_scale=float(d.get("activation_scale", 1.0)),
            collective_scale=float(d.get("collective_scale", 1.0)),
            step_time_scale=float(d.get("step_time_scale", 1.0)),
            overlap_frac=float(d.get("overlap_frac", 0.0)),
            samples=int(d.get("samples", 0)),
        )


class CalibrationTable:
    """The on-disk generation -> :class:`CalibrationScales` map."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._scales: dict[str, CalibrationScales] = {}

    # -- persistence -------------------------------------------------------

    @classmethod
    def load(cls, path: str) -> "CalibrationTable":
        """Load a table (missing/corrupt file = identity scales)."""
        table = cls(path)
        try:
            with open(path) as f:
                raw = json.load(f)
            for gen, d in raw.get("generations", {}).items():
                table._scales[str(gen)] = CalibrationScales.from_dict(d)
        except (OSError, json.JSONDecodeError, AttributeError, TypeError):
            pass  # missing/corrupt table = identity scales
        return table

    @classmethod
    def load_default(cls) -> "CalibrationTable":
        """Load the shared table under ``$TPX_TUNE_DIR``."""
        return cls.load(os.path.join(tune_dir(), CALIBRATION_FILE))

    def save(self) -> None:
        """Atomically persist (tmp + fsync + ``os.replace``)."""
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def to_dict(self) -> dict:
        """The persisted JSON form."""
        return {
            "version": 1,
            "generations": {
                g: s.to_dict() for g, s in sorted(self._scales.items())
            },
        }

    # -- lookup / update ---------------------------------------------------

    def scales_for(self, generation: str) -> CalibrationScales:
        """Scales for one generation (identity when never observed)."""
        return self._scales.get(
            generation_key(generation), CalibrationScales()
        )

    def observe(
        self,
        generation: str,
        *,
        predicted_step_s: Optional[float] = None,
        measured_step_s: Optional[float] = None,
        predicted_collective_s: Optional[float] = None,
        predicted_hbm_bytes: Optional[float] = None,
        measured_hbm_bytes: Optional[float] = None,
        activation_bytes: Optional[float] = None,
        alpha: float = DEFAULT_ALPHA,
    ) -> dict[str, Any]:
        """Fold one prediction-vs-measurement pair into the table.

        The predictions must be the CALIBRATED ones (what the current
        scales produce), so the EMA converges on the residual error:
        with ``scale' = scale * (1 + alpha * (m/p - 1))`` the new
        calibrated prediction is ``p' = p * (1 + alpha * (m/p - 1))``
        and ``|p' - m| = (1 - alpha) * |p - m|`` — strictly smaller for
        ``alpha`` in (0, 1). Returns the before/after relative errors.
        """
        if not 0.0 < alpha < 1.0:
            raise ValueError(f"alpha must be in (0, 1), got {alpha}")
        gen = generation_key(generation)
        cur = self._scales.get(gen, CalibrationScales())
        out: dict[str, Any] = {"generation": gen, "alpha": alpha}

        def _fold(scale: float, pred: float, meas: float) -> tuple[float, dict]:
            err_before = abs(pred - meas) / meas
            new_scale = scale * (1.0 + alpha * (meas / pred - 1.0))
            err_after = abs(pred * (new_scale / scale) - meas) / meas
            return new_scale, {
                "predicted": pred,
                "measured": meas,
                "err_before": err_before,
                "err_after": err_after,
            }

        act, coll, step = (
            cur.activation_scale,
            cur.collective_scale,
            cur.step_time_scale,
        )
        if predicted_step_s and measured_step_s:
            step, out["step_time"] = _fold(
                step, predicted_step_s, measured_step_s
            )
            if predicted_collective_s:
                # attribute the same relative residual to the collective
                # term (the step-level measurement cannot split compute
                # from collectives; the shared ratio keeps both honest —
                # profiled runs refine it via observe_collectives, whose
                # measurement CAN split them)
                coll = coll * (1.0 + alpha * (
                    measured_step_s / predicted_step_s - 1.0
                ))
        if predicted_hbm_bytes and measured_hbm_bytes:
            # only the activation term is calibrated (params/optimizer
            # are exact arithmetic), so the scale update solves for the
            # activation share of the total-HBM residual:
            #   total' = total + act*(s'/s - 1) = total + alpha*(m - total)
            p, m = predicted_hbm_bytes, measured_hbm_bytes
            err_before = abs(p - m) / m
            act_share = float(activation_bytes or 0.0)
            if act_share > 0:
                new_act = max(0.05, act * (1.0 + alpha * (m - p) / act_share))
                total_after = p + act_share * (new_act / act - 1.0)
                act = new_act
            else:
                total_after = p
            out["hbm"] = {
                "predicted": p,
                "measured": m,
                "err_before": err_before,
                "err_after": abs(total_after - m) / m,
            }
        self._scales[gen] = CalibrationScales(
            activation_scale=act,
            collective_scale=coll,
            step_time_scale=step,
            overlap_frac=cur.overlap_frac,
            samples=cur.samples + 1,
        )
        out["scales"] = self._scales[gen].to_dict()
        return out

    def observe_collectives(
        self,
        generation: str,
        *,
        predicted_collective_s: float,
        measured_collective_s: float,
        alpha: float = DEFAULT_ALPHA,
    ) -> dict[str, Any]:
        """Fold a directly MEASURED collective-seconds observation into
        ``collective_scale``.

        :meth:`observe`'s step-level measurement cannot split compute
        from collectives, so it only shares the whole-step residual with
        the collective term. The step profiler (``obs/profile.py``)
        removes that limit: its per-phase attribution yields measured
        exposed-collective seconds per step, and this fold gives
        ``collective_scale`` its own EMA on the same contraction math as
        :meth:`observe` (``predicted_collective_s`` must be the
        CALIBRATED prediction, so the residual strictly shrinks).
        Returns the before/after relative errors and the new scales.
        """
        if not 0.0 < alpha < 1.0:
            raise ValueError(f"alpha must be in (0, 1), got {alpha}")
        if predicted_collective_s <= 0.0 or measured_collective_s <= 0.0:
            raise ValueError(
                "predicted_collective_s and measured_collective_s must be"
                f" > 0, got {predicted_collective_s} / {measured_collective_s}"
            )
        gen = generation_key(generation)
        cur = self._scales.get(gen, CalibrationScales())
        p, m = float(predicted_collective_s), float(measured_collective_s)
        new_scale = cur.collective_scale * (1.0 + alpha * (m / p - 1.0))
        self._scales[gen] = CalibrationScales(
            activation_scale=cur.activation_scale,
            collective_scale=new_scale,
            step_time_scale=cur.step_time_scale,
            overlap_frac=cur.overlap_frac,
            samples=cur.samples + 1,
        )
        return {
            "generation": gen,
            "alpha": alpha,
            "collectives": {
                "predicted": p,
                "measured": m,
                "err_before": abs(p - m) / m,
                "err_after": abs(p * (new_scale / cur.collective_scale) - m) / m,
            },
            "scales": self._scales[gen].to_dict(),
        }

    def observe_overlap(
        self,
        generation: str,
        *,
        measured_overlap_frac: float,
        alpha: float = DEFAULT_ALPHA,
    ) -> dict[str, Any]:
        """Fold a measured comm/compute overlap fraction into the table.

        The step profiler's summary reports ``overlap_frac = 1 -
        exposed/modeled`` per run; the EMA here (``new = old + alpha *
        (measured - old)``) converges on the schedule's steady overlap,
        and the ranking (:func:`torchx_tpu.tune.rank.predicted_step_cost`)
        charges only ``collective_s * (1 - overlap_frac)`` instead of the
        fully-serialized collective time. Clamped to [0, 0.95]: some
        collective time is always exposed (the last bucket has no
        compute left to hide behind), and a runaway 1.0 would make every
        collective free and un-rank mesh choices entirely.
        """
        if not 0.0 < alpha < 1.0:
            raise ValueError(f"alpha must be in (0, 1), got {alpha}")
        m = min(max(float(measured_overlap_frac), 0.0), 0.95)
        gen = generation_key(generation)
        cur = self._scales.get(gen, CalibrationScales())
        new_frac = min(
            max(cur.overlap_frac + alpha * (m - cur.overlap_frac), 0.0), 0.95
        )
        self._scales[gen] = CalibrationScales(
            activation_scale=cur.activation_scale,
            collective_scale=cur.collective_scale,
            step_time_scale=cur.step_time_scale,
            overlap_frac=new_frac,
            samples=cur.samples + 1,
        )
        return {
            "generation": gen,
            "alpha": alpha,
            "overlap": {
                "measured": m,
                "before": cur.overlap_frac,
                "after": new_frac,
            },
            "scales": self._scales[gen].to_dict(),
        }
