"""Measured-trial subprocess for the tune driver.

``python -m torchx_tpu.tune.measure`` reads one trial spec (JSON) on
stdin, runs a short seeded training trial through the real
``torchx_tpu.train.run.train`` harness (the same code path bench.py
measures), and prints ONE JSON result line prefixed ``TUNE_METRICS ``
on stdout. All jax imports live inside function bodies: the module
itself stays importable under the package's jax-free lint, and only
this *subprocess* ever initializes a backend — the driver never does.

Spec fields: ``candidate`` (tune/space.Candidate dict), optional
``steps``, ``data_path``, ``seed``.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Optional

RESULT_PREFIX = "TUNE_METRICS "

#: metrics keys copied from the trainer's result into the trial record.
_KEEP = (
    "tokens_per_sec_per_chip",
    "mfu",
    "step_time_s",
    "loss",
    "remat_policy",
    "launch_to_first_step_s",
    "data_wait_frac",
)


def measure(spec: dict[str, Any]) -> dict[str, Any]:
    """Run one trial and return the trimmed metrics dict."""
    from torchx_tpu.models import all_configs
    from torchx_tpu.train.run import train
    from torchx_tpu.parallel.mesh_config import MeshConfig, parse_mesh_spec
    from torchx_tpu.tune.space import Candidate

    cand = Candidate.from_dict(spec["candidate"])
    overrides: dict[str, Any] = {"remat_policy": cand.remat_policy}
    if cand.int8:
        overrides["int8_matmuls"] = True
        overrides["int8_scope"] = cand.int8_scope
    cfg = all_configs()[cand.config](**overrides)

    mesh_cfg = (
        parse_mesh_spec(cand.mesh_spec) if cand.mesh_spec else MeshConfig()
    )
    steps = int(spec.get("steps", 4))
    metrics = train(
        cfg,
        mesh_cfg,
        batch=cand.batch,
        seq=cand.seq,
        steps=steps,
        log_every=max(1, steps // 2),
        prefetch=cand.prefetch,
        data_path=spec.get("data_path"),
    )
    out = {k: metrics[k] for k in _KEEP if k in metrics}
    out["steps"] = steps
    out["cid"] = cand.cid
    return out


def main(argv: Optional[list[str]] = None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if args and args[0] not in ("-",):
        with open(args[0]) as f:
            spec = json.load(f)
    else:
        spec = json.load(sys.stdin)
    result = measure(spec)
    print(RESULT_PREFIX + json.dumps(result, sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
