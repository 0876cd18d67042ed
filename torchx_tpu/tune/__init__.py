"""Closed-loop config autotuner over the deep-preflight cost model.

``tpx tune`` searches the training-config space (mesh spec x remat
policy x prefetch depth x per-device batch x int8 scope) without
spending device time on configs the static analyzer can already kill:

1. **Enumerate** — a declarative :class:`~torchx_tpu.tune.space.SearchSpace`
   expands into deterministic candidates.
2. **Prune statically** — every candidate runs through
   :func:`~torchx_tpu.analyze.explain.deep_preflight` (TPX700/701/703
   verdicts) and, optionally, the XLA AOT memory fit
   (``parallel/aot_fit.compile_fit`` in a batch subprocess). Zero device
   seconds; every kill is journaled with the verdict that caused it.
3. **Measure top-k** — survivors are ranked by predicted step cost
   (:mod:`~torchx_tpu.tune.rank`: collective bytes over ICI/DCN
   bandwidth + an HBM-pressure penalty) and only the top-k run short
   seeded bench trials (``tune/measure.py`` subprocess reusing the
   ``train.run.train`` harness).
4. **Emit + recalibrate** — the winner becomes a content-digested
   **plan artifact** (:mod:`~torchx_tpu.tune.artifact`) the submit gate
   can pin (``$TPX_PLAN_ARTIFACT``, TPX706/707) and ``tpx explain`` can
   diff against; each measured run's prediction-vs-actual error updates
   the persisted per-generation calibration table
   (:mod:`~torchx_tpu.tune.calibrate`) that rescales ``costmodel.py``
   and feeds the fleet placer's ``hbm_refusal`` oracle.

The whole package is jax-free at module level (enforced by
``scripts/lint_internal.py``); only the measure / AOT-probe
*subprocesses* import jax.
"""

from __future__ import annotations

from typing import Any

__all__ = [
    "Candidate",
    "SearchSpace",
    "CalibrationTable",
    "PlanArtifact",
    "TuneJournal",
    "run_tune",
]

_LAZY = {
    "Candidate": ("torchx_tpu.tune.space", "Candidate"),
    "SearchSpace": ("torchx_tpu.tune.space", "SearchSpace"),
    "CalibrationTable": ("torchx_tpu.tune.calibrate", "CalibrationTable"),
    "PlanArtifact": ("torchx_tpu.tune.artifact", "PlanArtifact"),
    "TuneJournal": ("torchx_tpu.tune.journal", "TuneJournal"),
    "run_tune": ("torchx_tpu.tune.driver", "run_tune"),
}


def __getattr__(name: str) -> Any:
    # lazy re-exports keep `import torchx_tpu.tune` free of the driver's
    # analyze/obs imports (and break the analyze <-> tune import cycle:
    # explain.py lazily imports tune.artifact for `--artifact` diffs)
    try:
        module, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(name) from None
    import importlib

    return getattr(importlib.import_module(module), attr)
