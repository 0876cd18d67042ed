"""The tune search driver: enumerate -> prune -> rank -> measure -> emit.

Orchestrates one ``tpx tune`` run (see the package docstring for the
funnel). The driver itself never imports jax: the AOT memory probe and
the measured trials run as subprocesses (``parallel/aot_fit`` /
``tune/measure``), each importing jax exactly once for its whole batch
of work. Every decision — enumeration, each pruned candidate with the
verdict that killed it, each measured trial — lands in the fsync'd
journal, so a killed run resumes: completed trials replay from the
journal and only the remainder touches a device again.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from typing import Any, Optional

from torchx_tpu import settings
from torchx_tpu.analyze.diagnostics import Severity
from torchx_tpu.specs.api import Role
from torchx_tpu.tune import rank as tune_rank
from torchx_tpu.tune.artifact import PlanArtifact
from torchx_tpu.tune.calibrate import CalibrationTable, tune_dir
from torchx_tpu.tune.journal import TuneJournal
from torchx_tpu.tune.space import Candidate, SearchSpace

ARTIFACT_FILE = "plan_artifact.json"
JOURNAL_FILE = "journal.jsonl"

#: how many ranked survivors the AOT stage probes (the next-best slides
#: in when a probe kills one of the top-k).
AOT_PROBE_FACTOR = 2


class TuneError(RuntimeError):
    """The tune run cannot proceed (empty space, no survivors, ...)."""


def role_for_candidate(cand: Candidate, devices: int) -> Role:
    """The synthetic single-slice role a candidate would submit as —
    what :func:`~torchx_tpu.analyze.explain.deep_preflight` analyzes.

    The CPU-sim device-count env makes the plan resolve onto ``devices``
    chips of ONE slice (tune searches within a slice; cross-slice specs
    still classify DCN through their explicit axis sizes)."""
    args = [
        "-m",
        "torchx_tpu.examples.train_llama",
        "--config",
        cand.config,
        "--mesh",
        cand.mesh_spec,
        "--batch",
        str(cand.batch),
        "--seq",
        str(cand.seq),
        "--remat-policy",
        cand.remat_policy,
    ]
    if cand.int8:
        args.append("--int8")
    return Role(
        name="tune",
        entrypoint="python",
        args=args,
        env={
            settings.ENV_XLA_FLAGS: (
                f"--xla_force_host_platform_device_count={devices}"
            )
        },
    )


@dataclasses.dataclass
class Trial:
    """One candidate's journey through the funnel."""

    candidate: Candidate
    status: str  # pruned_static | pruned_aot | measured | measure_failed
    #             | ranked_out (survived, outside top-k) | selected
    code: str = ""  # the TPX verdict / AOT verdict that decided it
    message: str = ""
    predicted: dict[str, Any] = dataclasses.field(default_factory=dict)
    metrics: dict[str, Any] = dataclasses.field(default_factory=dict)
    replayed: bool = False  # metrics came from the resume journal

    def to_dict(self) -> dict[str, Any]:
        return {
            "cid": self.candidate.cid,
            "candidate": self.candidate.to_dict(),
            "status": self.status,
            "code": self.code,
            "message": self.message,
            "predicted": self.predicted,
            "metrics": self.metrics,
            "replayed": self.replayed,
        }


@dataclasses.dataclass
class TuneResult:
    """What one ``run_tune`` call produced."""

    space: SearchSpace
    trials: list[Trial]
    winner: Optional[Trial]
    artifact_path: str
    report: dict[str, Any]
    calibration: dict[str, Any]

    def to_dict(self) -> dict[str, Any]:
        return {
            "space": self.space.to_dict(),
            "trials": [t.to_dict() for t in self.trials],
            "winner": self.winner.to_dict() if self.winner else None,
            "artifact": self.artifact_path,
            "report": self.report,
            "calibration": self.calibration,
        }


def _assert_chip_free(child: str) -> None:
    """The AOT probe and the measured trials need the chip, and a chip
    belongs to one process at a time: a driver process that has imported
    jax may already hold it, and the child would then fail or hang."""
    if "jax" in sys.modules:
        raise TuneError(
            f"the tune driver's process imported jax before starting its"
            f" {child} child; run `tpx tune` (or run_tune with children) from"
            " a process that stays off jax"
        )


def _last_json(stdout: str, prefix: str = "") -> Optional[Any]:
    """The last parseable JSON line of a subprocess's stdout (the jax
    runtime chats on stdout/stderr around the payload)."""
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if prefix:
            if not line.startswith(prefix):
                continue
            line = line[len(prefix):]
        if not line.startswith(("{", "[")):
            continue
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def run_tune(
    space: SearchSpace,
    *,
    devices: int,
    hbm_bytes: Optional[int] = None,
    generation: str = "",
    out_dir: Optional[str] = None,
    top_k: int = 3,
    aot: bool = True,
    measure: bool = True,
    data_path: Optional[str] = None,
    measure_cmd: Optional[list[str]] = None,
    aot_cmd: Optional[list[str]] = None,
    subprocess_env: Optional[dict[str, str]] = None,
    measure_timeout: float = 1800.0,
    session: str = "",
) -> TuneResult:
    """Run the full funnel over ``space`` (see module docstring).

    ``out_dir`` (default ``$TPX_TUNE_DIR/<space digest>``) holds the
    journal and the emitted artifact; re-running with the same space and
    out_dir resumes. ``measure_cmd`` / ``aot_cmd`` override the
    subprocess argv prefixes (tests inject stubs; the spec/requests JSON
    arrives on stdin either way). ``subprocess_env`` entries overlay
    ``os.environ`` for both subprocess kinds (e.g. ``JAX_PLATFORMS`` /
    ``XLA_FLAGS`` for CPU-sim runs).
    """
    from torchx_tpu.analyze import costmodel
    from torchx_tpu.analyze.explain import deep_preflight
    from torchx_tpu.obs import metrics as obs_metrics
    from torchx_tpu.obs import trace as obs_trace

    if devices < 1:
        raise TuneError(f"devices must be >= 1, got {devices}")
    cands = space.candidates()
    if not cands:
        raise TuneError("search space enumerated zero candidates")

    out_dir = out_dir or os.path.join(tune_dir(), space.digest())
    journal = TuneJournal(os.path.join(out_dir, JOURNAL_FILE))
    prior_digest = journal.space_digest()
    if prior_digest is not None and prior_digest != space.digest():
        # the journal belongs to a different space: resuming would lie
        journal.reset()
    seen = {
        (e.get("event"), e.get("cid")): e for e in journal.replay()
    }

    def journal_once(event: dict[str, Any]) -> None:
        key = (event.get("event"), event.get("cid"))
        if key in seen:
            return
        seen[key] = event
        journal.append(event)

    table = CalibrationTable.load(
        os.path.join(tune_dir(), "calibration.json")
    )
    scales = table.scales_for(generation)
    env = {**os.environ, **(subprocess_env or {})}

    trials: list[Trial] = []
    with obs_trace.span(
        "launcher.tune",
        session=session,
        config=space.config,
        candidates=len(cands),
        devices=devices,
    ) as sp:
        obs_metrics.TUNE_CANDIDATES.inc(len(cands), config=space.config)
        journal_once(
            {
                "event": "enumerated",
                "space_digest": space.digest(),
                "total": len(cands),
                "space": space.to_dict(),
            }
        )

        # -- stage 1: static prune (deep preflight, zero device seconds)
        survivors: list[tuple[Candidate, Any, tune_rank.StepCost]] = []
        with obs_trace.span("tune.static_prune", session=session):
            for cand in cands:
                role = role_for_candidate(cand, devices)
                plan, diags = deep_preflight(
                    role,
                    devices=devices,
                    hbm_bytes=hbm_bytes,
                    calibration=scales,
                )
                errors = [d for d in diags if d.severity is Severity.ERROR]
                if errors:
                    worst = errors[0]
                    trials.append(
                        Trial(
                            candidate=cand,
                            status="pruned_static",
                            code=worst.code,
                            message=worst.message,
                        )
                    )
                    obs_metrics.TUNE_PRUNED.inc(
                        stage="static", code=worst.code
                    )
                    journal_once(
                        {
                            "event": "pruned",
                            "cid": cand.cid,
                            "stage": "static",
                            "code": worst.code,
                            "message": worst.message,
                        }
                    )
                    continue
                if plan is None:  # not plan-shaped: cannot happen for our
                    raise TuneError(  # synthetic role — fail loudly if it does
                        f"candidate {cand.cid} resolved no plan"
                    )
                # the trainer shards batch over dp*fsdp and seq over sp
                # exactly (no padding): indivisible candidates would only
                # fail later, on the device — prune them here for free
                if (
                    plan.batch % plan.data_shards
                    or plan.seq % plan.axis("sp")
                ):
                    msg = (
                        f"batch {plan.batch} / seq {plan.seq} not divisible"
                        f" by data shards {plan.data_shards} / sp"
                        f" {plan.axis('sp')}"
                    )
                    trials.append(
                        Trial(
                            candidate=cand,
                            status="pruned_static",
                            code="SHARD_INDIVISIBLE",
                            message=msg,
                        )
                    )
                    obs_metrics.TUNE_PRUNED.inc(
                        stage="static", code="SHARD_INDIVISIBLE"
                    )
                    journal_once(
                        {
                            "event": "pruned",
                            "cid": cand.cid,
                            "stage": "static",
                            "code": "SHARD_INDIVISIBLE",
                            "message": msg,
                        }
                    )
                    continue
                cost = tune_rank.predicted_step_cost(
                    plan,
                    generation=generation,
                    calibration=scales,
                )
                survivors.append((cand, plan, cost))

        # -- stage 2: rank by predicted step cost
        survivors.sort(key=lambda t: t[2].step_s)

        # -- stage 3: AOT memory-fit probe over the ranked head (one jax
        #    subprocess for the whole batch; still zero device seconds)
        aot_pruned: set[str] = set()
        aot_results: dict[str, dict[str, Any]] = {}
        if aot and survivors:
            probe = survivors[: max(top_k * AOT_PROBE_FACTOR, top_k)]
            requests = [
                {
                    "config": c.config,
                    "mesh_spec": c.mesh_spec,
                    "batch": c.batch,
                    "seq": c.seq,
                    "remat_policy": plan.remat_policy,
                    "int8_scope": c.int8_scope,
                    "hbm_bytes": plan.hbm_bytes_per_chip,
                }
                for c, plan, _cost in probe
            ]
            if aot_cmd is None:
                _assert_chip_free("aot_fit")
            cmd = aot_cmd or [
                sys.executable,
                "-m",
                "torchx_tpu.parallel.aot_fit",
            ]
            with obs_trace.span(
                "tune.aot_probe", session=session, probes=len(requests)
            ):
                try:
                    proc = subprocess.run(
                        cmd,
                        input=json.dumps(requests),
                        capture_output=True,
                        text=True,
                        env=env,
                        timeout=measure_timeout,
                    )
                    results = _last_json(proc.stdout)
                except (subprocess.SubprocessError, OSError) as e:
                    results = None
                    journal_once(
                        {"event": "aot_error", "message": str(e), "cid": None}
                    )
            if isinstance(results, list) and len(results) == len(probe):
                for (c, _plan, _cost), r in zip(probe, results):
                    aot_results[c.cid] = r
                    if r.get("error"):
                        continue  # advisory: keep the candidate
                    if r.get("fits") is False:
                        aot_pruned.add(c.cid)
                        trials.append(
                            Trial(
                                candidate=c,
                                status="pruned_aot",
                                code="AOT_EXCEEDS",
                                message=(
                                    f"XLA AOT peak {r.get('peak_bytes', 0)}"
                                    f" bytes exceeds the per-chip budget"
                                ),
                                predicted={"aot": r},
                            )
                        )
                        obs_metrics.TUNE_PRUNED.inc(
                            stage="aot", code="AOT_EXCEEDS"
                        )
                        journal_once(
                            {
                                "event": "pruned",
                                "cid": c.cid,
                                "stage": "aot",
                                "code": "AOT_EXCEEDS",
                                "message": "XLA AOT memory fit exceeded",
                            }
                        )

        ranked = [
            (c, plan, cost)
            for c, plan, cost in survivors
            if c.cid not in aot_pruned
        ]
        if not ranked:
            raise TuneError(
                "static + AOT pruning killed every candidate; widen the"
                " space or raise the HBM budget"
            )

        # -- stage 4: measure the top-k via short seeded bench trials
        prior_measured = journal.measured()
        measured: list[Trial] = []
        to_measure = ranked[:top_k] if measure else []
        for c, plan, cost in to_measure:
            predicted = {
                "step_cost": cost.to_dict(),
                "aot": aot_results.get(c.cid),
            }
            if c.cid in prior_measured:
                t = Trial(
                    candidate=c,
                    status="measured",
                    predicted=predicted,
                    metrics=prior_measured[c.cid],
                    replayed=True,
                )
                trials.append(t)
                measured.append(t)
                continue
            journal.append({"event": "measure_start", "cid": c.cid})
            spec = {
                "candidate": c.to_dict(),
                "steps": space.measure_steps,
                "data_path": data_path,
            }
            if measure_cmd is None:
                _assert_chip_free("measure")
            cmd = measure_cmd or [
                sys.executable,
                "-m",
                "torchx_tpu.tune.measure",
            ]
            with obs_trace.span(
                "tune.measure", session=session, cid=c.cid
            ):
                try:
                    proc = subprocess.run(
                        cmd,
                        input=json.dumps(spec),
                        capture_output=True,
                        text=True,
                        env=env,
                        timeout=measure_timeout,
                    )
                    from torchx_tpu.tune.measure import RESULT_PREFIX

                    metrics = (
                        _last_json(proc.stdout, prefix=RESULT_PREFIX)
                        if proc.returncode == 0
                        else None
                    )
                except (subprocess.SubprocessError, OSError) as e:
                    proc, metrics = None, None
                    err = str(e)
            if isinstance(metrics, dict) and "step_time_s" in metrics:
                t = Trial(
                    candidate=c,
                    status="measured",
                    predicted=predicted,
                    metrics=metrics,
                )
                journal.append(
                    {"event": "measured", "cid": c.cid, "metrics": metrics}
                )
                obs_metrics.TUNE_MEASURED.inc(status="ok")
                trials.append(t)
                measured.append(t)
            else:
                err = (
                    err
                    if proc is None
                    else (proc.stderr or proc.stdout or "")[-2000:]
                )
                journal.append(
                    {"event": "measure_failed", "cid": c.cid, "message": err}
                )
                obs_metrics.TUNE_MEASURED.inc(status="failed")
                trials.append(
                    Trial(
                        candidate=c,
                        status="measure_failed",
                        code="MEASURE_FAILED",
                        message=err,
                        predicted=predicted,
                    )
                )

        # survivors outside the measured head
        decided = {t.candidate.cid for t in trials}
        for c, plan, cost in ranked:
            if c.cid not in decided:
                trials.append(
                    Trial(
                        candidate=c,
                        status="ranked_out",
                        predicted={"step_cost": cost.to_dict()},
                    )
                )

        # -- stage 5: winner + calibration + artifact
        winner: Optional[Trial] = None
        good = [t for t in measured if t.metrics.get("tokens_per_sec_per_chip")]
        if good:
            winner = max(
                good, key=lambda t: t.metrics["tokens_per_sec_per_chip"]
            )
        elif not measure and ranked:
            c, plan, cost = ranked[0]
            winner = Trial(
                candidate=c,
                status="selected",
                predicted={"step_cost": cost.to_dict()},
            )
            trials = [
                t if t.candidate.cid != c.cid else winner for t in trials
            ]

        calibration_obs: dict[str, Any] = {}
        if winner is not None and winner.metrics.get("step_time_s"):
            cost_dict = winner.predicted.get("step_cost", {})
            pred_step = float(cost_dict.get("step_s") or 0.0)
            if pred_step > 0:
                calibration_obs = table.observe(
                    generation,
                    predicted_step_s=pred_step,
                    measured_step_s=float(winner.metrics["step_time_s"]),
                    predicted_collective_s=float(
                        cost_dict.get("collective_s") or 0.0
                    ),
                )
                table.save()

        pruned_static = sum(1 for t in trials if t.status == "pruned_static")
        pruned_aot = sum(1 for t in trials if t.status == "pruned_aot")
        by_code: dict[str, int] = {}
        for t in trials:
            if t.status.startswith("pruned"):
                by_code[t.code] = by_code.get(t.code, 0) + 1
        report = {
            "candidates": len(cands),
            "pruned_static": pruned_static,
            "pruned_aot": pruned_aot,
            "measured": len(measured),
            "measure_failed": sum(
                1 for t in trials if t.status == "measure_failed"
            ),
            "prune_rate": (pruned_static + pruned_aot) / len(cands),
            "pruned_by_code": dict(sorted(by_code.items())),
            "device_seconds_pruning": 0.0,
        }

        artifact_path = ""
        if winner is not None:
            wrole = role_for_candidate(winner.candidate, devices)
            wplan, _ = deep_preflight(
                wrole, devices=devices, hbm_bytes=hbm_bytes,
                calibration=scales,
            )
            fit = costmodel.hbm_fit(wplan, calibration=scales)
            traffic = costmodel.collective_traffic(wplan, calibration=scales)
            artifact = PlanArtifact(
                space=space.to_dict(),
                candidate=winner.candidate.to_dict(),
                plan=wplan.to_dict(),
                predictions={
                    **winner.predicted,
                    "hbm": fit.to_dict(),
                    "collective_bytes_per_step": {
                        t.axis: t.bytes_per_step for t in traffic
                    },
                },
                measurements=winner.metrics,
                calibration=calibration_obs,
                report=report,
            )
            artifact_path = artifact.save(
                os.path.join(out_dir, ARTIFACT_FILE)
            )
            journal_once(
                {
                    "event": "winner",
                    "cid": winner.candidate.cid,
                    "digest": artifact.digest,
                }
            )
        if sp is not None:
            sp.attrs["pruned"] = report["pruned_static"] + report["pruned_aot"]
            sp.attrs["measured"] = report["measured"]
            sp.attrs["winner"] = winner.candidate.cid if winner else ""

    return TuneResult(
        space=space,
        trials=trials,
        winner=winner,
        artifact_path=artifact_path,
        report=report,
        calibration=calibration_obs,
    )
