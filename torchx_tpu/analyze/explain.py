"""Deep preflight: the ``tpx explain`` report and TPX7xx diagnostics.

Combines the jax-free plan IR (:mod:`~torchx_tpu.analyze.plan`), the
sharding propagation (:mod:`~torchx_tpu.analyze.propagation`) and the
cost model (:mod:`~torchx_tpu.analyze.costmodel`) into one report per
AppDef: every resharding boundary, the per-chip HBM fit, and per-axis
collective traffic classified ICI vs DCN — plus the TPX7xx diagnostics
the submit gate consumes (``rules.check_deep_preflight``).

TPX7xx family:

* **TPX700** (error) — propagation found a resharding boundary GSPMD
  resolves by involuntary full rematerialization.
* **TPX701** (error) — static HBM fit exceeds the per-chip budget.
* **TPX702** (warning) — a DCN-classified mesh axis carries
  fsdp/ep/tp/sp-scale collective traffic.
* **TPX703** (error) — the role looks plan-shaped but the mesh spec
  cannot resolve onto its device count.
* **TPX704** (warning) — a serve-shaped role's KV pool does not fit
  next to the parameters.
* **TPX705** (info) — no plan resolvable; deep preflight skipped
  (``tpx explain`` only — the submit gate stays silent and the TPX110
  heuristic covers the role).
* **TPX706** (error) — the role's resolved plan diverges from a pinned
  tune plan artifact (``$TPX_PLAN_ARTIFACT`` / ``--artifact``).
* **TPX707** (error) — the pinned plan artifact is unreadable, malformed
  or fails its content digest.

Every :func:`explain` run opens a ``launcher.explain`` span and bumps the
``tpx_explain_*`` metrics. The optional ``aot=True`` cross-check is the
single place this pipeline touches jax (lazily, via
``parallel/aot_fit.compile_fit``); everything else stays jax-free,
enforced by ``scripts/lint_internal.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from torchx_tpu.analyze import costmodel, propagation
from torchx_tpu.analyze.costmodel import ICI_BOUND_AXES
from torchx_tpu.analyze.diagnostics import Diagnostic, Severity
from torchx_tpu.analyze.plan import ParallelPlan, PlanError, plan_from_role
from torchx_tpu.specs.api import AppDef, Role

GIB = 1024**3


def _gib(n: int) -> str:
    return f"{n / GIB:.2f} GiB" if n >= GIB // 8 else f"{n / 2**20:.1f} MiB"


def deep_preflight(
    role: Role,
    *,
    devices: Optional[int] = None,
    hbm_bytes: Optional[int] = None,
    headroom: float = costmodel.DEFAULT_HEADROOM,
    calibration: Optional[Any] = None,
) -> tuple[Optional[ParallelPlan], list[Diagnostic]]:
    """Run the deep preflight over one role: ``(plan, diagnostics)``.

    ``plan`` is None when the role is not plan-shaped (TPX705 info is
    then the only diagnostic) or when the plan itself is broken (TPX703
    error). Shared by the submit-gate rule, ``tpx explain`` and the
    ``tpx tune`` static-prune stage (which passes its per-generation
    ``calibration`` scales so verdicts reflect measured reality).
    """
    try:
        plan = plan_from_role(role, devices=devices, hbm_bytes=hbm_bytes)
    except PlanError as e:
        return None, [
            Diagnostic(
                code="TPX703",
                severity=Severity.ERROR,
                role=role.name,
                field="args.--mesh",
                message=f"parallelism plan is inconsistent: {e}",
                hint="make the mesh axis sizes multiply out to the role's"
                " device count (slices x chips, or replicas x nproc)",
            )
        ]
    if plan is None:
        return None, [
            Diagnostic(
                code="TPX705",
                severity=Severity.INFO,
                role=role.name,
                message=(
                    "no parallelism plan resolvable from the role args (no"
                    " recognized --config); deep preflight skipped"
                ),
                hint="use a builtin --config name to enable static"
                " sharding/HBM analysis",
            )
        ]
    diags: list[Diagnostic] = []
    flow = propagation.propagate(plan)
    for b in flow.boundaries:
        if b.kind != "full_remat":
            continue
        diags.append(
            Diagnostic(
                code="TPX700",
                severity=Severity.ERROR,
                role=role.name,
                field=f"sharding.{b.op}",
                message=(
                    f"involuntary full rematerialization at {b.op}:"
                    f" {b.producer} -> {b.consumer} over"
                    f" {'/'.join(b.axes)} — {b.note}"
                ),
                hint="pin the gather/combine output with"
                " with_sharding_constraint (models/llama.py"
                " forward_features), or train with"
                " torchx_tpu.examples.train_llama",
            )
        )

    fit = costmodel.hbm_fit(plan, headroom=headroom, calibration=calibration)
    if not fit.fits:
        over = fit.total_bytes - int(fit.budget_bytes * fit.headroom)
        if plan.serve:
            diags.append(
                Diagnostic(
                    code="TPX704",
                    severity=Severity.WARNING,
                    role=role.name,
                    field="resource.tpu",
                    message=(
                        f"serve KV pool does not fit: params + {plan.max_batch}"
                        f"-slot KV pool need {_gib(fit.total_bytes)} of"
                        f" {_gib(int(fit.budget_bytes * fit.headroom))} usable"
                        f" HBM ({_gib(over)} over, budget {fit.source})"
                    ),
                    hint="lower --max-batch, shorten max_seq, or move to a"
                    " larger-HBM generation",
                )
            )
        else:
            diags.append(
                Diagnostic(
                    code="TPX701",
                    severity=Severity.ERROR,
                    role=role.name,
                    field="resource.tpu",
                    message=(
                        f"static HBM fit exceeded: {_gib(fit.total_bytes)}"
                        f" needed vs {_gib(int(fit.budget_bytes * fit.headroom))}"
                        f" usable per chip ({_gib(over)} over; components:"
                        + ", ".join(
                            f" {k}={_gib(v)}"
                            for k, v in sorted(
                                fit.components.items(),
                                key=lambda kv: -kv[1],
                            )
                        )
                        + f"; budget {fit.source})"
                    ),
                    hint="raise fsdp/tp, lower --batch/--seq, or use"
                    " --remat-policy full",
                )
            )

    traffic = costmodel.collective_traffic(plan, calibration=calibration)
    for t in traffic:
        if t.axis in ICI_BOUND_AXES and t.network in ("dcn", "mixed"):
            diags.append(
                Diagnostic(
                    code="TPX702",
                    severity=Severity.WARNING,
                    role=role.name,
                    field="args.--mesh",
                    message=(
                        f"mesh axis {t.axis}={t.size} spans the {t.network}"
                        f" network (slice size {plan.chips_per_slice}) but"
                        f" carries ~{_gib(t.bytes_per_step)}/step of"
                        f" {'/'.join(t.ops)} traffic — ICI-bound"
                        " collectives over DCN will pace the step"
                    ),
                    hint="keep fsdp/ep/tp/sp inside a slice and put only"
                    " dp/pp on the cross-slice dimension",
                )
            )
    return plan, diags


@dataclasses.dataclass
class ExplainReport:
    """The full deep-preflight report for one AppDef."""

    target: str = ""
    scheduler: Optional[str] = None
    roles: list[dict[str, Any]] = dataclasses.field(default_factory=list)

    @property
    def diagnostics(self) -> list[Diagnostic]:
        """All diagnostics across every role, in role order."""
        return [d for r in self.roles for d in r.get("_diags", [])]

    @property
    def has_errors(self) -> bool:
        """True when any diagnostic is error severity (CLI exit 1)."""
        return any(d.severity is Severity.ERROR for d in self.diagnostics)

    def summary(self) -> dict[str, int]:
        """Diagnostic counts by severity name."""
        out = {"error": 0, "warning": 0, "info": 0}
        for d in self.diagnostics:
            out[d.severity.value] += 1
        return out

    def to_dict(self) -> dict[str, Any]:
        """Stable JSON form (``tpx explain --json``; schema version 1,
        golden-filed in tests/test_explain.py)."""
        roles = []
        for r in self.roles:
            entry = {k: v for k, v in r.items() if not k.startswith("_")}
            entry["diagnostics"] = [d.to_dict() for d in r.get("_diags", [])]
            roles.append(entry)
        return {
            "version": 1,
            "target": self.target,
            "scheduler": self.scheduler,
            "roles": roles,
            "summary": self.summary(),
        }

    def render(self) -> str:
        """Human-readable multi-section report (what ``tpx explain``
        prints)."""
        s = self.summary()
        sched = f" [scheduler: {self.scheduler}]" if self.scheduler else ""
        lines = [
            f"{self.target or 'app'}: deep preflight — {s['error']} error(s),"
            f" {s['warning']} warning(s), {s['info']} info{sched}"
        ]
        for r in self.roles:
            plan = r.get("plan")
            if plan is None:
                lines.append(f"\nrole {r['role']}: no plan (deep preflight skipped)")
                for d in r.get("_diags", []):
                    lines.append(f"  {d.severity.value:<7} {d.code} {d.message}")
                continue
            mesh = ",".join(
                f"{a}={v}" for a, v in plan["mesh"].items() if v != 1
            ) or "(single device)"
            lines.append(
                f"\nrole {r['role']}: {plan['config']} on {plan['devices']}"
                f" device(s) ({plan['slices']} slice(s) x"
                f" {plan['chips_per_slice']} chips"
                f"{', ' + plan['accelerator'] if plan['accelerator'] else ''})"
                f"  mesh {mesh}  batch {plan['batch']} seq {plan['seq']}"
                f"  remat {plan['remat_policy']}"
            )
            sh = r["sharding"]
            lines.append(
                f"  sharding: activations {sh['activation_spec']}"
                + ("  ** INVOLUNTARY FULL REMAT **" if sh["full_remat"] else "")
            )
            if sh["boundaries"]:
                lines.append("  | boundary | kind | axes | producer -> consumer |")
                lines.append("  |---|---|---|---|")
                for b in sh["boundaries"]:
                    lines.append(
                        f"  | {b['op']} | {b['kind']} |"
                        f" {','.join(b['axes'])} |"
                        f" {b['producer']} -> {b['consumer']} |"
                    )
            hbm = r["hbm"]
            comp = ", ".join(
                f"{k} {_gib(v)}"
                for k, v in sorted(
                    hbm["components"].items(), key=lambda kv: -kv[1]
                )
            )
            lines.append(
                f"  hbm: {_gib(hbm['total_bytes'])} of"
                f" {_gib(hbm['usable_bytes'])} usable per chip"
                f" ({hbm['budget_bytes'] // GIB} GiB x {hbm['headroom']}"
                f" headroom, {hbm['source']}) -> {hbm['verdict'].upper()}"
            )
            lines.append(f"       {comp}")
            if r["collectives"]:
                lines.append("  | axis | size | network | bytes/step | ops |")
                lines.append("  |---|---|---|---|---|")
                for t in r["collectives"]:
                    lines.append(
                        f"  | {t['axis']} | {t['size']} | {t['network']} |"
                        f" {_gib(t['bytes_per_step'])} |"
                        f" {','.join(t['ops'])} |"
                    )
            art = r.get("artifact")
            if art:
                lines.append(
                    f"  artifact: pinned {art['digest'][:12]}… -> "
                    + (
                        "DIVERGES: " + "; ".join(art["diffs"])
                        if art["diverges"]
                        else "matches the tuned plan"
                    )
                )
            aot = r.get("aot")
            if aot:
                if aot.get("error"):
                    lines.append(f"  aot: cross-check failed: {aot['error']}")
                else:
                    lines.append(
                        f"  aot: compiled args {_gib(aot['args_bytes'])}"
                        f" (static {_gib(aot['static_state_bytes'])},"
                        f" {aot['state_agreement_pct']:+.1f}%), temps"
                        f" {_gib(aot['temp_bytes'])}, peak"
                        f" {_gib(aot['peak_bytes'])} ->"
                        f" {'FITS' if aot['fits'] else 'EXCEEDS'}"
                    )
            for d in r.get("_diags", []):
                lines.append(
                    f"  {d.severity.value:<7} {d.code} [{d.location}]"
                    f" {d.message}"
                )
                if d.hint:
                    lines.append(f"          fix: {d.hint}")
        return "\n".join(lines)


def artifact_diff_diagnostics(
    artifact_path: str, role_name: str, plan: Optional[ParallelPlan]
) -> tuple[list[Diagnostic], Optional[dict[str, Any]]]:
    """Diff one role's resolved plan against a pinned tune artifact.

    Returns ``(diagnostics, detail)`` — TPX707 when the artifact cannot
    be trusted (unreadable/malformed/digest mismatch), TPX706 when the
    plan diverges from the pinned winner on any tuned knob. ``detail``
    is the JSON-safe record ``tpx explain`` embeds (None for non-plan
    roles under a broken artifact). Shared by :func:`explain` and the
    submit gate's ``rules.check_plan_artifact``."""
    from torchx_tpu.tune.artifact import ArtifactError, load_artifact

    try:
        art = load_artifact(artifact_path)
    except ArtifactError as e:
        return [
            Diagnostic(
                code="TPX707",
                severity=Severity.ERROR,
                role=role_name,
                field="env.TPX_PLAN_ARTIFACT",
                message=f"pinned plan artifact rejected: {e}",
                hint="re-run `tpx tune` to regenerate the artifact; never"
                " edit it by hand (the digest is content-addressed)",
            )
        ], None
    if plan is None:
        return [], None
    diffs = art.diff_plan(plan.to_dict())
    detail: dict[str, Any] = {
        "path": artifact_path,
        "digest": art.digest,
        "candidate": art.candidate,
        "diverges": bool(diffs),
        "diffs": diffs,
    }
    if not diffs:
        return [], detail
    return [
        Diagnostic(
            code="TPX706",
            severity=Severity.ERROR,
            role=role_name,
            field="args",
            message=(
                "plan diverges from the pinned tune artifact"
                f" ({art.digest[:12]}…): " + "; ".join(diffs)
            ),
            hint="match the tuned config (see `tpx explain --artifact`),"
            " re-run `tpx tune`, or drop the $TPX_PLAN_ARTIFACT pin",
        )
    ], detail


def explain(
    app: AppDef,
    *,
    scheduler: Optional[str] = None,
    devices: Optional[int] = None,
    hbm_bytes: Optional[int] = None,
    headroom: float = costmodel.DEFAULT_HEADROOM,
    aot: bool = False,
    artifact: Optional[str] = None,
    calibration: Optional[Any] = None,
    session: str = "",
    gate: str = "api",
) -> ExplainReport:
    """Deep-preflight every role of ``app`` and return the report.

    ``artifact`` diffs each plan-shaped role against a pinned tune plan
    artifact (TPX706/707); ``calibration`` applies learned per-generation
    cost-model scales (see :mod:`torchx_tpu.tune.calibrate`)."""
    from torchx_tpu.obs import metrics as obs_metrics
    from torchx_tpu.obs import trace as obs_trace

    report = ExplainReport(target=app.name, scheduler=scheduler)
    with obs_trace.span(
        "launcher.explain",
        session=session,
        scheduler=scheduler,
        app=app.name,
        gate=gate,
    ) as sp:
        for role in app.roles:
            plan, diags = deep_preflight(
                role,
                devices=devices,
                hbm_bytes=hbm_bytes,
                headroom=headroom,
                calibration=calibration,
            )
            entry: dict[str, Any] = {"role": role.name, "_diags": diags}
            if artifact:
                art_diags, art_detail = artifact_diff_diagnostics(
                    artifact, role.name, plan
                )
                diags.extend(art_diags)
                if art_detail is not None:
                    entry["artifact"] = art_detail
            if plan is None:
                entry["plan"] = None
            else:
                flow = propagation.propagate(plan)
                fit = costmodel.hbm_fit(
                    plan, headroom=headroom, calibration=calibration
                )
                entry["plan"] = plan.to_dict()
                entry["sharding"] = flow.to_dict()
                entry["hbm"] = fit.to_dict()
                entry["collectives"] = [
                    t.to_dict()
                    for t in costmodel.collective_traffic(
                        plan, calibration=calibration
                    )
                ]
                obs_metrics.EXPLAIN_HBM_TOTAL_BYTES.set(
                    fit.total_bytes, role=role.name
                )
                if aot:
                    entry["aot"] = _aot_cross_check(plan, fit, headroom)
            report.roles.append(entry)
        summary = report.summary()
        if sp is not None:
            sp.attrs["errors"] = summary["error"]
            sp.attrs["warnings"] = summary["warning"]
    obs_metrics.EXPLAIN_RUNS.inc(
        gate=gate, status="errors" if report.has_errors else "clean"
    )
    for d in report.diagnostics:
        obs_metrics.EXPLAIN_DIAGNOSTICS.inc(
            code=d.code, severity=d.severity.value
        )
    return report


def _aot_cross_check(
    plan: ParallelPlan, fit: costmodel.HbmFit, headroom: float
) -> dict[str, Any]:
    """Cross-check the static fit against the XLA compiler's own memory
    analysis (``parallel/aot_fit.compile_fit``) — the ONE jax-importing
    path in this pipeline, entered only on ``--aot``.

    Compares the compiler's argument bytes (params + optimizer state +
    batch, what lives across steps) against the static prediction of the
    same quantity; temps are reported but not scored (the CPU backend's
    attention fallback inflates them far past TPU reality).
    """
    import os

    static_state = (
        fit.components.get("params", 0)
        + fit.components.get("optimizer", 0)
        + fit.components.get("batch", 0)
    )
    try:
        import jax  # noqa: F401 - deliberate lazy import

        if len(jax.devices()) != plan.devices:
            return {
                "error": (
                    f"plan needs {plan.devices} device(s) but the jax"
                    f" runtime has {len(jax.devices())}; set"
                    " XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{plan.devices} (before jax initializes)"
                ),
                "static_state_bytes": static_state,
            }
        import dataclasses as _dc

        import numpy as np
        from jax.sharding import Mesh

        from torchx_tpu.models import all_configs
        from torchx_tpu.parallel.aot_fit import compile_fit
        from torchx_tpu.parallel.mesh_config import AXES

        cfg = all_configs()[plan.model.name]()
        cfg = _dc.replace(
            cfg,
            remat_policy=plan.remat_policy if cfg.remat else cfg.remat_policy,
            use_ring_attention=plan.ring_attention,
        )
        shape = tuple(plan.axis(a) for a in AXES)
        devs = np.array(jax.devices()).reshape(shape)
        mesh = Mesh(devs, AXES)
        r = compile_fit(
            cfg,
            mesh,
            plan.batch,
            plan.seq,
            hbm_bytes=plan.hbm_bytes_per_chip,
            headroom=headroom,
        )
        agreement = (
            100.0 * (static_state - r.args_bytes) / r.args_bytes
            if r.args_bytes
            else 0.0
        )
        return {
            "args_bytes": int(r.args_bytes),
            "temp_bytes": int(r.temp_bytes),
            "peak_bytes": int(r.peak_bytes),
            "fits": bool(r.fits),
            "static_state_bytes": int(static_state),
            "state_agreement_pct": agreement,
            "platform": jax.default_backend(),
            "note": (
                "temps are a CPU-backend upper bound"
                if os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
                else ""
            ),
        }
    except Exception as e:  # noqa: BLE001 - aot is best-effort advisory
        return {"error": str(e), "static_state_bytes": static_state}
