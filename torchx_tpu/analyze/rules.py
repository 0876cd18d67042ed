"""Built-in rules + the pluggable rule registry for the preflight analyzer.

Every rule is a callable ``(RuleContext) -> Iterable[Diagnostic]`` registered
under a stable name. The engine (:mod:`torchx_tpu.analyze.engine`) runs all
registered rules over one AppDef; plugins and tests can add their own with
:func:`register_rule` / the :func:`rule` decorator.

Code families (full table in docs/api/analyze.md):

* ``TPX00x`` component source (emitted via ``specs/file_linter.py``)
* ``TPX01x`` AppDef structure
* ``TPX1xx`` TPU topology / resources
* ``TPX2xx`` env vars / macros / ports / mounts
* ``TPX3xx`` scheduler capability fit
* ``TPX4xx`` supervisor / retry coherence
* ``TPX5xx`` control-plane resilience coherence
* ``TPX6xx`` control-daemon coherence
* ``TPX7xx`` deep preflight (static sharding / HBM / collective analysis)
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from string import Template
from typing import Any, Callable, Iterable, Iterator, Mapping, Optional

from torchx_tpu import settings as s
from torchx_tpu.analyze.diagnostics import Diagnostic, Severity
from torchx_tpu.schedulers.api import SchedulerCapabilities
from torchx_tpu.specs.api import (
    AppDef,
    CfgVal,
    RetryPolicy,
    Role,
    _TPU_GENERATIONS,
)
from torchx_tpu.supervisor.policy import SupervisorPolicy


@dataclass
class RuleContext:
    """Everything a rule may look at for one analyzer run.

    Attributes:
        app: the AppDef under analysis (never None).
        scheduler: target scheduler name, or None when linting
            scheduler-agnostically.
        cfg: resolved (or raw) run opts for the scheduler, may be empty.
        capabilities: the target scheduler's feature profile, or None when
            the backend is unknown (capability rules then skip).
        policy: supervisor policy for retry-coherence rules, or None.
    """

    app: AppDef
    scheduler: Optional[str] = None
    cfg: Optional[Mapping[str, CfgVal]] = None
    capabilities: Optional[SchedulerCapabilities] = None
    policy: Optional[SupervisorPolicy] = None


Rule = Callable[[RuleContext], Iterable[Diagnostic]]

_RULES: dict[str, Rule] = {}


def register_rule(name: str, fn: Rule) -> None:
    """Register (or replace) a rule under a stable name."""
    _RULES[name] = fn


def rule(name: str) -> Callable[[Rule], Rule]:
    """Decorator form of :func:`register_rule`."""

    def deco(fn: Rule) -> Rule:
        register_rule(name, fn)
        return fn

    return deco


def all_rules() -> dict[str, Rule]:
    """Snapshot of the registry (name -> rule), insertion-ordered."""
    return dict(_RULES)


# ---------------------------------------------------------------------------
# Env var ownership
# ---------------------------------------------------------------------------

#: Env vars the launcher injects into every replica: a role that sets one
#: corrupts the rendezvous/identity wiring — always an error.
LAUNCHER_OWNED_ENV = frozenset(
    {
        s.ENV_TPX_APP_ID,
        s.ENV_TPX_JOB_ID,
        s.ENV_TPX_REPLICA_ID,
        s.ENV_TPX_ROLE_NAME,
        s.ENV_TPX_NUM_REPLICAS,
        s.ENV_TPX_SLICE_ID,
        s.ENV_TPX_HOST_ID,
        s.ENV_TPX_HOSTS_PER_SLICE,
        s.ENV_TPX_MIN_REPLICAS,
        s.ENV_TPX_COORDINATOR_HOST,
        s.ENV_MEGASCALE_COORDINATOR_ADDRESS,
        s.ENV_MEGASCALE_NUM_SLICES,
        s.ENV_MEGASCALE_SLICE_ID,
        s.ENV_TPU_WORKER_ID,
        s.ENV_TPU_WORKER_HOSTNAMES,
    }
)

#: Reserved-prefix vars that are nonetheless legitimate user knobs (the
#: framework documents them as inputs); setting one is not even a warning.
USER_SETTABLE_ENV = frozenset(
    {
        s.ENV_TPX_SIMULATE_PREEMPTION_EXIT,
        s.ENV_TPX_RESUME_STEP,
        s.ENV_TPX_FUSED_NORM,
        s.ENV_TPX_ERROR_FILE,
        s.ENV_TPX_LOG_DIR,
        s.ENV_TPX_TRACE,
        s.ENV_TPX_TRACE_ID,
        s.ENV_TPX_PARENT_SPAN,
        s.ENV_TPX_EVENT_DESTINATION,
        s.ENV_TPX_OBS_DIR,
        s.ENV_TPX_NO_LINT,
        s.ENV_TPX_TRACKERS,
        s.ENV_TPX_PARENT_RUN_ID,
        s.ENV_TPX_INTERNAL_SESSION_ID,
        s.ENV_TPU_VISIBLE_CHIPS,
        s.ENV_TPU_PROCESS_BOUNDS,
        s.ENV_TPU_CHIPS_PER_PROCESS_BOUNDS,
        s.ENV_TPU_SKIP_MDS_QUERY,
        "TPU_STDERR_LOG_LEVEL",
        "TPU_MIN_LOG_LEVEL",
        "TPU_LIBRARY_PATH",
    }
)

#: Prefixes the launcher considers reserved for platform wiring.
RESERVED_ENV_PREFIXES = ("TPX_", "TPU_", "MEGASCALE_")

#: Macro identifiers ``macros.Values.substitute`` knows how to resolve.
KNOWN_MACROS = frozenset(
    {"img_root", "app_id", "replica_id", "num_replicas", "coordinator_env"}
)


def unknown_macro_names(value: str) -> set[str]:
    """Identifiers in ``${...}``/``$...`` placeholders that are not launcher
    macros. ``$$`` escapes (runtime shell expansion) are ignored — that is
    the documented way to defer expansion to the replica's shell."""
    out: set[str] = set()
    for m in Template.pattern.finditer(value):
        name = m.group("named") or m.group("braced")
        if name and name not in KNOWN_MACROS:
            out.add(name)
    return out


def _tpu_roles(app: AppDef) -> Iterator[Role]:
    for role in app.roles:
        if role.resource is not None and role.resource.tpu is not None:
            yield role


# ---------------------------------------------------------------------------
# TPX01x — AppDef structure
# ---------------------------------------------------------------------------


@rule("structure")
def check_structure(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX010-TPX015: roles exist, are uniquely named, runnable and sized."""
    app = ctx.app
    if not app.roles:
        yield Diagnostic(
            code="TPX010",
            severity=Severity.ERROR,
            message=f"AppDef {app.name!r} has no roles",
            field="roles",
            hint="add at least one Role to the AppDef",
        )
        return
    seen: set[str] = set()
    for role in app.roles:
        if role.name in seen:
            yield Diagnostic(
                code="TPX014",
                severity=Severity.ERROR,
                role=role.name,
                field="name",
                message=f"duplicate role name {role.name!r}",
                hint="role names must be unique within an AppDef",
            )
        seen.add(role.name)
        if not role.entrypoint:
            yield Diagnostic(
                code="TPX011",
                severity=Severity.ERROR,
                role=role.name,
                field="entrypoint",
                message=f"role {role.name!r} has no entrypoint",
                hint="set Role.entrypoint to the command to run",
            )
        if role.num_replicas <= 0:
            yield Diagnostic(
                code="TPX012",
                severity=Severity.ERROR,
                role=role.name,
                field="num_replicas",
                message=f"num_replicas must be positive, got {role.num_replicas}",
                hint="set num_replicas >= 1",
            )
        if role.min_replicas is not None and not (
            0 < role.min_replicas <= role.num_replicas
        ):
            yield Diagnostic(
                code="TPX013",
                severity=Severity.ERROR,
                role=role.name,
                field="min_replicas",
                message=(
                    f"min_replicas={role.min_replicas} must satisfy"
                    f" 0 < min_replicas <= num_replicas={role.num_replicas}"
                ),
                hint="lower min_replicas or raise num_replicas",
            )
        if not role.image:
            yield Diagnostic(
                code="TPX015",
                severity=Severity.WARNING,
                role=role.name,
                field="image",
                message=f"role {role.name!r} has no image",
                hint=(
                    "container backends need an image; the local scheduler"
                    " treats it as a path root"
                ),
            )


# ---------------------------------------------------------------------------
# TPX1xx — TPU topology / resources
# ---------------------------------------------------------------------------


@rule("topology")
def check_topology(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX101-TPX103: slice sizes that exist, topology shapes that match the
    generation, and TPU chips kept out of ``resource.devices``."""
    for role in ctx.app.roles:
        res = role.resource
        if res is None:
            continue
        tpu = res.tpu
        if tpu is not None:
            info = _TPU_GENERATIONS[tpu.accelerator]
            single = info["single_host_chips"]
            per_vm = info["multi_host_vm_chips"]
            if tpu.chips > single and tpu.chips % per_vm:
                yield Diagnostic(
                    code="TPX101",
                    severity=Severity.ERROR,
                    role=role.name,
                    field="resource.tpu.chips",
                    message=(
                        f"no {tpu.accelerator} slice has {tpu.chips} chips:"
                        f" multi-host slices are built from {per_vm}-chip"
                        f" hosts (single-host max is {single})"
                    ),
                    hint=(
                        f"use a chip count <= {single} or a multiple of"
                        f" {per_vm} (e.g. {max(per_vm, tpu.chips // per_vm * per_vm)})"
                    ),
                )
            elif tpu.accelerator in ("v5e", "v6e") and tpu.chips > 256:
                yield Diagnostic(
                    code="TPX101",
                    severity=Severity.ERROR,
                    role=role.name,
                    field="resource.tpu.chips",
                    message=(
                        f"{tpu.accelerator} pods top out at 256 chips,"
                        f" got {tpu.chips}"
                    ),
                    hint="use num_replicas > 1 (multi-slice DCN) beyond one pod",
                )
            if tpu.topology:
                dims = tpu.topology.split("x")
                if tpu.accelerator in ("v5e", "v6e") and len(dims) != 2:
                    yield Diagnostic(
                        code="TPX102",
                        severity=Severity.ERROR,
                        role=role.name,
                        field="resource.tpu.topology",
                        message=(
                            f"{tpu.accelerator} slices are 2D meshes;"
                            f" topology {tpu.topology!r} has {len(dims)} dims"
                        ),
                        hint='use a 2D shape like "4x8"',
                    )
                elif tpu.accelerator in ("v4", "v5p") and len(dims) != 3:
                    yield Diagnostic(
                        code="TPX102",
                        severity=Severity.ERROR,
                        role=role.name,
                        field="resource.tpu.topology",
                        message=(
                            f"{tpu.accelerator} slices are 3D tori;"
                            f" topology {tpu.topology!r} has {len(dims)} dims"
                        ),
                        hint='use a 3D shape like "2x2x4"',
                    )
        for key in res.devices:
            if "tpu" in key.lower():
                yield Diagnostic(
                    code="TPX103",
                    severity=Severity.ERROR,
                    role=role.name,
                    field=f"resource.devices.{key}",
                    message=(
                        f"TPU chips do not go in resource.devices ({key!r});"
                        " they are allocated via resource.tpu"
                    ),
                    hint="set resource.tpu = TpuSlice(...) instead",
                )


#: Mesh axes of the trainer's canonical 6-axis mesh (parallel/mesh.py
#: ``AXES``, duplicated here because analyze never imports jax).
MESH_AXES = frozenset({"pp", "dp", "fsdp", "ep", "tp", "sp"})

#: Entrypoint modules known to pin gather outputs with explicit sharding
#: constraints (models/llama.py forward_features), making expert-parallel
#: meshes remat-free. Custom trainer modules get the TPX110 warning.
REMAT_SAFE_MODULES = ("torchx_tpu.examples.train_llama",)


def _mesh_specs(role: Role) -> Iterator[str]:
    """Values of ``--mesh`` arguments in a role's arg list (both the
    two-token ``--mesh dp=2,...`` and one-token ``--mesh=dp=2,...``
    spellings)."""
    args = [str(a) for a in role.args]
    for i, a in enumerate(args):
        if a == "--mesh" and i + 1 < len(args):
            yield args[i + 1]
        elif a.startswith("--mesh="):
            yield a.split("=", 1)[1]


@rule("mesh")
def check_mesh(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX110-TPX111: mesh axis specs in role args.

    TPX110 is the launch-time twin of the runtime remat push: a mesh that
    shards experts (``ep``) while also sharding weights or sequence
    (``fsdp``/``sp``) makes the embedding/expert gathers transition
    between a dim-sharded operand layout and a batch/seq-sharded output
    layout. GSPMD partitions that gather by replicate+reslice —
    "involuntary full rematerialization", warned on every compile and
    paid in HBM + latency — unless the model pins the gather outputs with
    explicit ``with_sharding_constraint``. The stock trainer does; a
    custom entrypoint module probably does not, so warn before the job
    ever reaches a pod.

    The heuristic is the FALLBACK: when the role resolves into a full
    :class:`~torchx_tpu.analyze.plan.ParallelPlan` (a recognizable
    ``--config``), real sharding propagation owns the question and emits
    TPX700 with the exact boundary instead (``check_deep_preflight``) —
    the pattern-match would double-report, so it stands down. TPX111
    (unknown axis names) always runs; it is pure spec hygiene.
    """
    from torchx_tpu.analyze.plan import PlanError, plan_from_role

    for role in ctx.app.roles:
        args = [str(a) for a in role.args]
        safe = any(
            m in (role.entrypoint or "") or m in args for m in REMAT_SAFE_MODULES
        )
        try:
            superseded = plan_from_role(role) is not None
        except PlanError:
            superseded = True  # broken plan: TPX703 owns the role
        for spec in _mesh_specs(role):
            sizes: dict[str, int] = {}
            for pair in spec.split(","):
                if not pair.strip():
                    continue
                axis, _, value = pair.partition("=")
                axis = axis.strip()
                try:
                    sizes[axis] = int(value)
                except ValueError:
                    sizes[axis] = 0  # unparseable size: still report the axis
                if axis not in MESH_AXES:
                    yield Diagnostic(
                        code="TPX111",
                        severity=Severity.ERROR,
                        role=role.name,
                        field="args.--mesh",
                        message=(
                            f"unknown mesh axis {axis!r} in --mesh {spec!r};"
                            f" the trainer mesh has axes"
                            f" {'/'.join(sorted(MESH_AXES))}"
                        ),
                        hint="fix the axis name (e.g. fsdp=-1, not fsd=-1)",
                    )
            ep = sizes.get("ep", 1)
            paired = [
                a for a in ("fsdp", "sp") if sizes.get(a, 1) > 1 or sizes.get(a) == -1
            ]
            if (ep > 1 or ep == -1) and paired and not safe and not superseded:
                yield Diagnostic(
                    code="TPX110",
                    severity=Severity.WARNING,
                    role=role.name,
                    field="args.--mesh",
                    message=(
                        f"--mesh {spec!r} pairs expert parallelism (ep) with"
                        f" {'/'.join(paired)} sharding: embedding/expert"
                        " gathers then reshard dim-sharded -> batch/seq-"
                        "sharded, which GSPMD partitions by involuntary"
                        " full rematerialization (replicate + reslice)"
                        " unless gather outputs carry explicit sharding"
                        " constraints"
                    ),
                    hint=(
                        "pin gather outputs with with_sharding_constraint"
                        " (see models/llama.py forward_features), or use"
                        " torchx_tpu.examples.train_llama which already"
                        " does"
                    ),
                )


#: Fused-kernel tileability (ops/fused.py ``FLASH_HEAD_DIMS`` /
#: ``flash_shapes_ok`` / ``norm_shapes_ok``, duplicated here because
#: analyze never imports jax): flash attention tiles head_dims of
#: 64/128/256 over 128-token blocks; the fused norm needs a lane-aligned
#: model dim.
_FUSED_HEAD_DIMS = frozenset({64, 128, 256})
_FUSED_LANE = 128


def _flag_value(role: Role, flag: str) -> Optional[str]:
    """Last value of ``flag`` in a role's arg list (both the two-token
    ``--flag v`` and one-token ``--flag=v`` spellings)."""
    args = [str(a) for a in role.args]
    found: Optional[str] = None
    for i, a in enumerate(args):
        if a == flag and i + 1 < len(args):
            found = args[i + 1]
        elif a.startswith(flag + "="):
            found = a.split("=", 1)[1]
    return found


@rule("kernels")
def check_kernels(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX112: ``--kernels pallas`` that will silently fall back.

    The trainer degrades ``--kernels pallas`` to the reference XLA ops
    whenever the Mosaic kernels cannot run: on a non-TPU backend, or when
    the model/sequence shapes do not tile (flash attention needs a
    head_dim of 64/128/256 and a 128-divisible sequence; the fused norm
    needs a lane-aligned model dim). The job still trains — but the MFU
    the flag was supposed to buy never materializes, so surface the
    fallback at submit time instead of letting someone discover it in a
    profile three hours into a run.
    """
    from torchx_tpu.analyze.plan import MODEL_SHAPES

    for role in ctx.app.roles:
        if _flag_value(role, "--kernels") != "pallas":
            continue
        on_tpu = role.resource is not None and role.resource.tpu is not None
        if not on_tpu:
            yield Diagnostic(
                code="TPX112",
                severity=Severity.WARNING,
                role=role.name,
                field="args.--kernels",
                message=(
                    "--kernels pallas on a non-TPU backend: the fused"
                    " Mosaic kernels need TPU cores, so the trainer will"
                    " silently fall back to the reference XLA ops"
                ),
                hint=(
                    "request a TPU resource, or drop the flag (use"
                    " --kernels interpret only for parity testing — it"
                    " runs the kernels in the Pallas interpreter, slowly)"
                ),
            )
            continue
        config = _flag_value(role, "--config")
        model = MODEL_SHAPES.get(config or "")
        if model is None:
            continue  # unknown config: nothing shape-checkable
        problems = []
        if model.head_dim not in _FUSED_HEAD_DIMS:
            problems.append(
                f"head_dim {model.head_dim} (flash attention tiles"
                f" {'/'.join(str(d) for d in sorted(_FUSED_HEAD_DIMS))})"
            )
        if model.dim % _FUSED_LANE:
            problems.append(
                f"dim {model.dim} (fused norm needs a multiple of"
                f" {_FUSED_LANE})"
            )
        seq_raw = _flag_value(role, "--seq")
        try:
            seq = int(seq_raw) if seq_raw is not None else None
        except ValueError:
            seq = None
        if seq is not None and (seq < _FUSED_LANE or seq % _FUSED_LANE):
            problems.append(
                f"seq {seq} (flash attention needs a multiple of"
                f" {_FUSED_LANE})"
            )
        if problems:
            yield Diagnostic(
                code="TPX112",
                severity=Severity.WARNING,
                role=role.name,
                field="args.--kernels",
                message=(
                    f"--kernels pallas with config {config!r} cannot"
                    f" tile: {'; '.join(problems)} — the affected ops"
                    " fall back to the reference XLA path"
                ),
                hint=(
                    "pick a config whose shapes tile (head_dim 64/128/"
                    "256, dim and seq multiples of 128), or drop the"
                    " flag; the fallback is correct, just not fused"
                ),
            )


# ---------------------------------------------------------------------------
# TPX2xx — env / macros / ports / mounts
# ---------------------------------------------------------------------------


@rule("env")
def check_env(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX201-TPX203: launcher-owned env overrides (error), reserved-prefix
    collisions (warning) and JAX runtime config (info)."""
    for role in ctx.app.roles:
        for key in role.env:
            if key in LAUNCHER_OWNED_ENV:
                yield Diagnostic(
                    code="TPX201",
                    severity=Severity.ERROR,
                    role=role.name,
                    field=f"env.{key}",
                    message=(
                        f"env var {key!r} is injected by the launcher"
                        " (replica identity / rendezvous wiring); setting it"
                        " in the role corrupts the gang bootstrap"
                    ),
                    hint="remove it from Role.env — every scheduler sets it",
                )
            elif key in USER_SETTABLE_ENV:
                continue
            elif key.startswith(RESERVED_ENV_PREFIXES):
                yield Diagnostic(
                    code="TPX202",
                    severity=Severity.WARNING,
                    role=role.name,
                    field=f"env.{key}",
                    message=(
                        f"env var {key!r} uses a reserved prefix"
                        f" ({'/'.join(RESERVED_ENV_PREFIXES)}) but is not a"
                        " documented knob"
                    ),
                    hint="rename it unless you are targeting platform internals",
                )
            elif key.startswith("JAX_"):
                yield Diagnostic(
                    code="TPX203",
                    severity=Severity.INFO,
                    role=role.name,
                    field=f"env.{key}",
                    message=(
                        f"env var {key!r} configures the JAX runtime;"
                        " make sure it is intentional"
                    ),
                )


@rule("macros")
def check_macros(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX204: ``${...}`` placeholders that no launcher macro resolves."""
    for role in ctx.app.roles:
        fields: list[tuple[str, str]] = [("entrypoint", role.entrypoint)]
        fields += [(f"args[{i}]", a) for i, a in enumerate(role.args)]
        fields += [(f"env.{k}", v) for k, v in role.env.items()]
        for i, m in enumerate(role.mounts):
            for attr in ("src_path", "dst_path"):
                val = getattr(m, attr, None)
                if val:
                    fields.append((f"mounts[{i}].{attr}", val))
        for where, value in fields:
            if not isinstance(value, str):
                continue
            for name in sorted(unknown_macro_names(value)):
                yield Diagnostic(
                    code="TPX204",
                    severity=Severity.WARNING,
                    role=role.name,
                    field=where,
                    message=(
                        f"${{{name}}} is not a launcher macro"
                        f" (known: {', '.join(sorted(KNOWN_MACROS))}); it will"
                        " pass through to the replica shell unexpanded by the"
                        " launcher"
                    ),
                    hint=(
                        f"use $${{{name}}} to make runtime shell expansion"
                        " explicit, or fix the macro name"
                    ),
                )


@rule("ports")
def check_ports(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX210-TPX211: duplicate and out-of-range ports in ``port_map``."""
    for role in ctx.app.roles:
        by_port: dict[int, str] = {}
        for name, port in role.port_map.items():
            if not 0 < port < 65536:
                yield Diagnostic(
                    code="TPX211",
                    severity=Severity.ERROR,
                    role=role.name,
                    field=f"port_map.{name}",
                    message=f"port {port} for {name!r} is out of range 1-65535",
                    hint="pick a valid TCP port",
                )
            elif port in by_port:
                yield Diagnostic(
                    code="TPX210",
                    severity=Severity.ERROR,
                    role=role.name,
                    field=f"port_map.{name}",
                    message=(
                        f"port {port} is mapped twice"
                        f" ({by_port[port]!r} and {name!r})"
                    ),
                    hint="give each named port a distinct number",
                )
            else:
                by_port[port] = name


@rule("serve_ports")
def check_serve_ports(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX212: a serve-shaped role (its args bind a ``--port``) whose port
    has no ``port_map`` entry — routers and serve pools discover replica
    endpoints through the port map, so an unmapped server port is
    unreachable through every launcher surface that consumes it."""
    for role in ctx.app.roles:
        args = [str(a) for a in role.args]
        ports: list[tuple[int, int]] = []  # (arg index, port)
        for i, a in enumerate(args):
            if a == "--port" and i + 1 < len(args):
                raw = args[i + 1]
            elif a.startswith("--port="):
                raw = a.split("=", 1)[1]
            else:
                continue
            try:
                ports.append((i, int(raw)))
            except ValueError:
                continue
        mapped = set(role.port_map.values())
        for i, port in ports:
            if port == 0:
                continue  # ephemeral: the server reports its bound port
            if port not in mapped:
                yield Diagnostic(
                    code="TPX212",
                    severity=Severity.WARNING,
                    role=role.name,
                    field=f"args[{i}]",
                    message=(
                        f"role binds --port {port} but port_map has no"
                        f" entry for it"
                        + (
                            f" (mapped: {sorted(mapped)})"
                            if mapped
                            else " (port_map is empty)"
                        )
                    ),
                    hint=(
                        f'add port_map={{"http": {port}}} to the role so'
                        " routers and serve pools can reach it"
                    ),
                )


@rule("serve_disagg")
def check_serve_disagg(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX213: a disaggregated serving role (``--serve-role prefill`` or
    ``decode``) with no KV transfer path declared — neither a
    ``--kv-transfer`` arg nor ``tpx/kv_transfer`` role metadata. A
    prefill gang with nowhere to stream its computed KV blocks (or a
    decode gang no prefill can reach) is an assembly error: every
    request would prefill and then fail, so it is an ERROR at submit,
    before any chip is provisioned."""
    from torchx_tpu.serve.kv_transfer import ROLE_METADATA_KEY

    def _flag_value(args: list[str], flag: str) -> Optional[str]:
        for i, a in enumerate(args):
            if a == flag and i + 1 < len(args):
                return args[i + 1]
            if a.startswith(flag + "="):
                return a.split("=", 1)[1]
        return None

    for role in ctx.app.roles:
        args = [str(a) for a in role.args]
        serve_role = _flag_value(args, "--serve-role")
        if serve_role not in ("prefill", "decode"):
            continue
        if _flag_value(args, "--kv-transfer"):
            continue
        if role.metadata.get(ROLE_METADATA_KEY):
            continue
        yield Diagnostic(
            code="TPX213",
            severity=Severity.ERROR,
            role=role.name,
            field="args",
            message=(
                f"role declares --serve-role {serve_role} but no KV"
                f" transfer path (no --kv-transfer arg and no"
                f" {ROLE_METADATA_KEY!r} metadata)"
            ),
            hint=(
                "declare the prefill->decode path: --kv-transfer"
                " http:<decode-url>[,...] | file:<dir> | local, or use"
                " components.serve.generate_server_disagg which wires"
                " both roles"
            ),
        )


@rule("serve_slo")
def check_serve_slo(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX214: a role declaring SLO specs (``--slo`` args or ``tpx/slo``
    metadata) on a backend whose capability profile has no ``/metricz``
    scrape path. The telemetry plane's burn rates come from scraping
    replica metrics; on an unreachable backend every SLO over replica
    metrics sees zero samples, so the burn stays zero and the alert can
    never fire — a silent no-op, hence a WARNING before submit."""
    from torchx_tpu.obs.slo import ROLE_METADATA_KEY as SLO_METADATA_KEY

    cap = ctx.capabilities
    if ctx.scheduler is None or cap is None or cap.metricz_scrape:
        return
    for role in ctx.app.roles:
        args = [str(a) for a in role.args]
        has_slo = any(
            a == "--slo" or a.startswith("--slo=") for a in args
        ) or bool(role.metadata.get(SLO_METADATA_KEY))
        if not has_slo:
            continue
        yield Diagnostic(
            code="TPX214",
            severity=Severity.WARNING,
            role=role.name,
            field="args",
            message=(
                f"role declares SLO specs but scheduler"
                f" {ctx.scheduler!r} has no /metricz scrape path"
                " (metricz_scrape=False); burn rates over replica"
                " metrics will stay zero and the alerts can never fire"
            ),
            hint=(
                "target a scrape-reachable backend (local, docker, gke,"
                " slurm), or push metrics via the obs textfile sink and"
                " drop the replica-scrape SLOs"
            ),
        )


@rule("profile_scrape")
def check_profile_scrape(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX215: step profiling enabled (the trainer's ``--profile`` flag
    or ``TPX_PROFILE=1`` in the role env) on a backend whose capability
    profile has no ``/metricz`` scrape path. The profiler still writes
    its per-step journal and ``tpx profile`` still renders it from the
    replica's obs dir, but the ``tpx_profile_*`` summary gauges are
    published via replica scrape — unreachable backend means no fleet
    MFU / data-wait panels in ``tpx top``, which is usually why
    profiling was turned on. WARNING, not ERROR: local-only attribution
    is still useful."""
    cap = ctx.capabilities
    if ctx.scheduler is None or cap is None or cap.metricz_scrape:
        return
    for role in ctx.app.roles:
        # exact-flag match: --profile-dir (the xprof trace flag) is a
        # different feature and must not trigger this rule
        enabled = any(
            str(a) == "--profile" for a in role.args
        ) or str(role.env.get(s.ENV_TPX_PROFILE, "")).lower() in (
            "1",
            "true",
            "yes",
            "on",
        )
        if not enabled:
            continue
        yield Diagnostic(
            code="TPX215",
            severity=Severity.WARNING,
            role=role.name,
            field="args",
            message=(
                f"role enables step profiling but scheduler"
                f" {ctx.scheduler!r} has no /metricz scrape path"
                " (metricz_scrape=False); tpx_profile_* summaries stay"
                " local to the replica's obs dir and tpx top shows no"
                " MFU / data-wait panels"
            ),
            hint=(
                "target a scrape-reachable backend (local, docker, gke,"
                " slurm) to publish the summaries, or read them locally"
                " with `tpx profile` / the obs textfile sink"
            ),
        )


@rule("mounts")
def check_mounts(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX220-TPX221: duplicate destinations and relative paths in mounts."""
    for role in ctx.app.roles:
        seen: dict[str, int] = {}
        for i, m in enumerate(role.mounts):
            dst = getattr(m, "dst_path", None)
            if not dst:
                continue
            if dst in seen:
                yield Diagnostic(
                    code="TPX220",
                    severity=Severity.ERROR,
                    role=role.name,
                    field=f"mounts[{i}].dst_path",
                    message=(
                        f"mount destination {dst!r} is used by both"
                        f" mounts[{seen[dst]}] and mounts[{i}]"
                    ),
                    hint="each mount needs a distinct destination path",
                )
            else:
                seen[dst] = i
            if not dst.startswith("/") and "${" not in dst:
                yield Diagnostic(
                    code="TPX221",
                    severity=Severity.WARNING,
                    role=role.name,
                    field=f"mounts[{i}].dst_path",
                    message=f"mount destination {dst!r} is not absolute",
                    hint="use an absolute container path",
                )


# ---------------------------------------------------------------------------
# TPX3xx — scheduler capability fit
# ---------------------------------------------------------------------------


@rule("capabilities")
def check_capabilities(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX300-TPX307: AppDef features the target backend cannot honor."""
    if ctx.scheduler is None:
        return
    cap = ctx.capabilities
    if cap is None:
        yield Diagnostic(
            code="TPX300",
            severity=Severity.INFO,
            message=(
                f"no capability profile for scheduler {ctx.scheduler!r};"
                " capability rules skipped"
            ),
            hint=(
                "builtin backends declare CAPABILITIES in their module;"
                " plugins can set Scheduler.capabilities"
            ),
        )
        return
    app = ctx.app
    if len(app.roles) > 1 and not cap.multi_role:
        yield Diagnostic(
            code="TPX303",
            severity=Severity.ERROR,
            field="roles",
            message=(
                f"scheduler {ctx.scheduler!r} launches exactly one role per"
                f" job; AppDef has {len(app.roles)}"
            ),
            hint="split the app or pick a multi-role backend (gke, slurm)",
        )
    if not cap.delete:
        yield Diagnostic(
            code="TPX302",
            severity=Severity.WARNING,
            message=(
                f"scheduler {ctx.scheduler!r} has no delete(); supervised"
                " resubmission cannot clean up terminal attempts"
            ),
            hint="expect leftover terminal jobs when using tpx supervise",
        )
    for role in app.roles:
        if role.mounts and not cap.mounts:
            yield Diagnostic(
                code="TPX301",
                severity=Severity.ERROR,
                role=role.name,
                field="mounts",
                message=(
                    f"scheduler {ctx.scheduler!r} does not materialize"
                    f" mounts; {len(role.mounts)} mount(s) would be silently"
                    " dropped"
                ),
                hint="remove the mounts or use local_docker / gke",
            )
        if cap.requires_tpu and (role.resource is None or role.resource.tpu is None):
            yield Diagnostic(
                code="TPX305",
                severity=Severity.ERROR,
                role=role.name,
                field="resource.tpu",
                message=(
                    f"scheduler {ctx.scheduler!r} only provisions TPU slices;"
                    f" role {role.name!r} has no resource.tpu"
                ),
                hint="set resource.tpu = TpuSlice(...) or pick another backend",
            )
        if (
            role.resource is not None
            and role.resource.tpu is not None
            and role.num_replicas > 1
            and not cap.multislice
        ):
            yield Diagnostic(
                code="TPX304",
                severity=Severity.ERROR,
                role=role.name,
                field="num_replicas",
                message=(
                    f"scheduler {ctx.scheduler!r} cannot wire multi-slice"
                    f" DCN training (TPU role with num_replicas="
                    f"{role.num_replicas})"
                ),
                hint="use num_replicas=1 or a multislice backend (gke)",
            )
        if role.max_retries > 0 and not cap.native_retries:
            yield Diagnostic(
                code="TPX306",
                severity=Severity.WARNING,
                role=role.name,
                field="max_retries",
                message=(
                    f"scheduler {ctx.scheduler!r} does not honor"
                    f" max_retries={role.max_retries} natively"
                ),
                hint="run under `tpx supervise` for client-side resubmission",
            )
        if (
            cap.concrete_resources
            and (role.resource is None or role.resource.tpu is None)
            and (role.resource is None or role.resource.cpu <= 0 or role.resource.memMB <= 0)
        ):
            yield Diagnostic(
                code="TPX307",
                severity=Severity.WARNING,
                role=role.name,
                field="resource",
                message=(
                    f"scheduler {ctx.scheduler!r} builds concrete resource"
                    " requests but cpu/memMB are unset; backend defaults"
                    " apply"
                ),
                hint="set Resource.cpu and Resource.memMB explicitly",
            )


# ---------------------------------------------------------------------------
# TPX4xx — supervisor / retry coherence
# ---------------------------------------------------------------------------


@rule("retries")
def check_retries(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX401-TPX404: retry budgets and policies that cannot do what they
    promise (gang semantics, preemption classification, resume injection)."""
    cap = ctx.capabilities
    policy = ctx.policy
    for role in ctx.app.roles:
        if role.max_retries < 0:
            yield Diagnostic(
                code="TPX402",
                severity=Severity.ERROR,
                role=role.name,
                field="max_retries",
                message=f"max_retries must be >= 0, got {role.max_retries}",
                hint="use 0 to disable retries",
            )
        if (
            role.resource is not None
            and role.resource.tpu is not None
            and role.resource.tpu.hosts > 1
            and role.retry_policy == RetryPolicy.REPLICA
        ):
            yield Diagnostic(
                code="TPX401",
                severity=Severity.WARNING,
                role=role.name,
                field="retry_policy",
                message=(
                    "RetryPolicy.REPLICA on a multi-host TPU role: restarting"
                    " one host cannot rejoin the ICI collective — the whole"
                    " gang must restart"
                ),
                hint="use RetryPolicy.APPLICATION (the TPU default)",
            )
        if policy is not None and policy.resume_env in role.env:
            yield Diagnostic(
                code="TPX404",
                severity=Severity.WARNING,
                role=role.name,
                field=f"env.{policy.resume_env}",
                message=(
                    f"role sets {policy.resume_env!r} but the supervisor"
                    " injects it from the checkpoint manifest on every"
                    " resubmission; the role value will be overwritten"
                ),
                hint="drop it from Role.env and let the supervisor drive resume",
            )
    if (
        policy is not None
        and policy.max_preemptions > 0
        and cap is not None
        and not cap.classifies_preemption
    ):
        yield Diagnostic(
            code="TPX403",
            severity=Severity.WARNING,
            message=(
                f"policy allows {policy.max_preemptions} preemption"
                f" resubmits but scheduler {ctx.scheduler!r} cannot classify"
                " preemptions — they will be counted as app errors"
                f" (budget {policy.max_app_retries})"
            ),
            hint=(
                "raise max_app_retries or use a backend that classifies"
                " preemption (gke, tpu_vm, slurm, local)"
            ),
        )


# ---------------------------------------------------------------------------
# TPX5xx — control-plane resilience coherence
# ---------------------------------------------------------------------------

#: backends where a fault plan only sabotages the operator's own machine;
#: anywhere else it corrupts a real cloud submission.
_FAULT_PLAN_SAFE_SCHEDULERS = frozenset({"local", "local_docker"})


@rule("resilience")
def check_resilience(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX501-TPX502: resilience knobs that compose into surprises.

    Three restart layers can stack: the backend's native per-role restarts
    (``Role.max_retries`` honored in place), the supervisor's per-class
    resubmission budgets, and the control-plane seam's own call retries.
    The first two multiply — every supervisor resubmit re-arms the full
    native budget — which is easy to configure by accident and miserable
    to debug at 3am (TPX501). And a ``TPX_FAULT_PLAN`` chaos drill left in
    the environment must never ride along into a real cloud submission
    (TPX502)."""
    policy = ctx.policy
    cap = ctx.capabilities
    if policy is not None and cap is not None and cap.native_retries:
        supervisor_budget = (
            policy.max_preemptions
            + policy.max_infra_retries
            + policy.max_app_retries
        )
        native = max((r.max_retries for r in ctx.app.roles), default=0)
        if supervisor_budget > 0 and native > 0:
            worst = (supervisor_budget + 1) * (native + 1) - 1
            yield Diagnostic(
                code="TPX501",
                severity=Severity.WARNING,
                field="max_retries",
                message=(
                    f"supervisor budgets ({supervisor_budget} resubmits)"
                    f" stack MULTIPLICATIVELY with scheduler"
                    f" {ctx.scheduler!r}'s native max_retries ({native}):"
                    f" every resubmit re-arms the full native budget, up to"
                    f" {worst} total restarts"
                ),
                hint=(
                    "set Role.max_retries=0 under tpx supervise (let the"
                    " supervisor own restarts), or skip supervise and keep"
                    " native retries"
                ),
            )
    if ctx.scheduler and ctx.scheduler not in _FAULT_PLAN_SAFE_SCHEDULERS:
        from torchx_tpu.resilience.faults import fault_plan_active

        if fault_plan_active():
            yield Diagnostic(
                code="TPX502",
                severity=Severity.ERROR,
                field=s.ENV_TPX_FAULT_PLAN,
                message=(
                    f"{s.ENV_TPX_FAULT_PLAN} is set but the target scheduler"
                    f" is {ctx.scheduler!r}: a fault-injection drill against"
                    " a real control plane fabricates failures on live cloud"
                    " calls (retries, breaker trips, even aborted submits)"
                ),
                hint=(
                    "unset TPX_FAULT_PLAN, or drill against the local /"
                    " local_docker schedulers"
                ),
            )


#: role-arg spellings that tell the app where to checkpoint; if none
#: appears anywhere the app never writes the directory the supervisor
#: watches for resume steps.
_CKPT_DIR_FLAGS = ("--ckpt-dir", "--checkpoint-dir", "--ckpt_dir")


@rule("recovery")
def check_recovery(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX503: supervision configured for checkpoint-resume but the job
    never checkpoints.

    ``SupervisorPolicy.checkpoint_dir`` makes every resubmission inject
    ``TPX_RESUME_STEP`` from the checkpoint manifest — but the manifest
    only exists if the *application* saves checkpoints there. A policy
    with resume retries whose roles pass no checkpoint-dir flag restarts
    from step 0 on every preemption: the retries "work" while silently
    discarding all progress. Catch the incoherence before submit."""
    policy = ctx.policy
    if policy is None or not policy.checkpoint_dir:
        return
    resume_budget = (
        policy.max_preemptions
        + policy.max_infra_retries
        + policy.max_hang_retries
    )
    if resume_budget <= 0:
        return
    for role in ctx.app.roles:
        args = list(role.args) + [role.entrypoint]
        if any(flag in str(a) for a in args for flag in _CKPT_DIR_FLAGS):
            return
    yield Diagnostic(
        code="TPX503",
        severity=Severity.WARNING,
        field="checkpoint_dir",
        message=(
            f"policy watches checkpoint_dir={policy.checkpoint_dir!r} with"
            f" {resume_budget} resume retries budgeted, but no role passes a"
            f" checkpoint-dir flag ({'/'.join(_CKPT_DIR_FLAGS)}) — every"
            " resubmission will restart from step 0"
        ),
        hint=(
            "point the app at the same directory (e.g."
            f" --ckpt-dir {policy.checkpoint_dir}) so saved steps feed"
            " TPX_RESUME_STEP, or drop checkpoint_dir from the policy"
        ),
    )


# ---------------------------------------------------------------------------
# TPX6xx — control-plane (daemon / watch) coherence
# ---------------------------------------------------------------------------


@rule("control-plane")
def check_control_plane(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX601: hang detection expects event latency the backend can't give.

    Under the control daemon (``TPX_CONTROL_ADDR`` set), supervision
    waits ride the reconciler's watch streams — terminal transitions and
    gang-health signals arrive at event latency on backends that declare
    the ``watch`` capability (local sidecars, GKE's kubectl stream). On a
    backend WITHOUT it, the same interface silently degrades to the
    generic poll adapter, so a policy that budgets hang detection
    (``hang_deadline_seconds``) will observe hangs only at the watch poll
    interval — worth knowing before the 3am page arrives late."""
    policy = ctx.policy
    cap = ctx.capabilities
    if policy is None or cap is None:
        return
    if getattr(policy, "hang_deadline_seconds", 0) <= 0:
        return
    if not os.environ.get(s.ENV_TPX_CONTROL_ADDR, "").strip():
        return
    if cap.watch:
        return
    yield Diagnostic(
        code="TPX601",
        severity=Severity.WARNING,
        field="hang_deadline_seconds",
        message=(
            f"supervisor hang detection"
            f" (hang_deadline_seconds={policy.hang_deadline_seconds:g}) runs"
            f" through the control daemon ({s.ENV_TPX_CONTROL_ADDR} is set),"
            f" but scheduler {ctx.scheduler!r} has no native watch source —"
            " state changes surface at the watch POLL interval, so"
            " hang-detection latency degrades by up to that interval"
        ),
        hint=(
            "target a watch-capable backend (local, gke), tighten"
            f" {s.ENV_TPX_WATCH_INTERVAL}, or run this job outside the"
            " daemon (unset TPX_CONTROL_ADDR) to poll directly"
        ),
    )


@rule("fleet-class")
def check_fleet_class(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX602: a preemptible-class gang with no way to survive preemption.

    Under the fleet scheduler, ``batch`` and ``preemptible`` classes are
    the preemption market's victims: a higher class that cannot place
    will shrink them (elastic reshape) or checkpoint-preempt them. A role
    in one of those classes that is neither elastic
    (``SupervisorPolicy.elastic_reshape``) nor checkpointing (no
    checkpoint-dir flag, same detection as TPX503) loses ALL progress on
    every market action — it runs, but every preemption restarts it from
    step 0. The class is read from ``role.metadata["fleet/class"]`` or
    the injected ``$TPX_FLEET_CLASS`` role env."""
    if ctx.policy is not None and getattr(ctx.policy, "elastic_reshape", False):
        return
    for role in ctx.app.roles:
        klass = str(
            role.metadata.get("fleet/class")
            or role.env.get(s.ENV_TPX_FLEET_CLASS)
            or ""
        ).strip()
        if klass not in ("batch", "preemptible"):
            continue
        args = list(role.args) + [role.entrypoint]
        if any(flag in str(a) for a in args for flag in _CKPT_DIR_FLAGS):
            continue
        yield Diagnostic(
            code="TPX602",
            severity=Severity.WARNING,
            field="fleet/class",
            message=(
                f"role {role.name!r} runs in fleet class {klass!r} — a"
                " preemption-market victim class — but is neither elastic"
                " (no SupervisorPolicy.elastic_reshape) nor checkpointing"
                f" (no {'/'.join(_CKPT_DIR_FLAGS)} flag): every market"
                " shrink or preemption will cost its full progress"
            ),
            hint=(
                "make the gang elastic (policy elastic_reshape + a mesh"
                " spec, submit with elastic=true) so the market shrinks it"
                " instead of killing it, or pass a checkpoint-dir flag so"
                " a preempted attempt resumes from its last step"
            ),
        )


@rule("promotion-scrape")
def check_promotion_scrape(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX603: a promotion stage on a backend the canary gate can't see.

    The pipeline engine's promote stage gates promote-to-100% on BOTH the
    eval score and the SLO engine's live burn rate over the canary
    replicas. Burn rates come from scraping replica ``/metricz``; on a
    backend whose capability profile has no scrape path the burn signal
    sees zero samples, so the canary gate silently degrades to
    eval-score-only — an SLO regression on the canary would promote
    anyway. Promotion stages are recognized by the
    ``tpx/pipeline=promote`` role metadata the pipeline executor stamps."""
    from torchx_tpu.pipelines.dag import ROLE_METADATA_KEY

    cap = ctx.capabilities
    if ctx.scheduler is None or cap is None or cap.metricz_scrape:
        return
    for role in ctx.app.roles:
        if role.metadata.get(ROLE_METADATA_KEY) != "promote":
            continue
        yield Diagnostic(
            code="TPX603",
            severity=Severity.WARNING,
            role=role.name,
            field="metadata",
            message=(
                f"promotion stage targets scheduler {ctx.scheduler!r}"
                " which has no /metricz scrape path"
                " (metricz_scrape=False): the canary burn-rate gate sees"
                " zero samples and silently degrades to eval-score-only —"
                " an SLO regression on the canary replicas would be"
                " promoted to 100%"
            ),
            hint=(
                "run the promote stage on a scrape-reachable backend"
                " (local, docker, gke, slurm) so the burn gate has"
                " samples, or accept eval-score-only gating and lower the"
                " eval threshold margin accordingly"
            ),
        )


def check_sim_scenario(scenario: Mapping[str, Any]) -> Iterator[Diagnostic]:
    """TPX604: a simulation scenario naming a backend other than ``sim``.

    Not an AppDef rule — scenarios are plain dicts, so ``tpx sim`` calls
    this directly instead of going through the engine. The virtual-time
    harness only ever drives :class:`~torchx_tpu.sim.executor
    .SimExecutor`; a scenario declaring ``"backend": "gke"`` (say,
    copied from a production job file) still runs entirely in the
    simulator, and an operator reading the journal could mistake modeled
    placements for real ones. WARNING, never gating: the run is valid,
    the label is misleading."""
    backend = scenario.get("backend")
    if backend is None or str(backend) == "sim":
        return
    yield Diagnostic(
        code="TPX604",
        severity=Severity.WARNING,
        field="backend",
        message=(
            f"scenario {str(scenario.get('name', '?'))!r} names backend"
            f" {str(backend)!r}, but the simulator only drives the"
            " virtual-time executor — every placement in the journal is"
            " modeled, none touch a real scheduler"
        ),
        hint=(
            'set "backend": "sim" (or drop the key) so the journal'
            " cannot be mistaken for a real-backend run"
        ),
    )


def check_federation_config(
    config: Mapping[str, Any]
) -> Iterator[Diagnostic]:
    """TPX605: a federation setup that cannot actually fail over.

    Like TPX604, not an AppDef rule — federation configs (scenario dicts
    with a ``cells`` list, or ``tpx cell`` registry snapshots) are plain
    dicts, called directly by the CLI. Two shapes warn:

    * a single registered cell: every routing decision has exactly one
      answer, so a drain or daemon loss drops traffic — the federation
      layer is pure overhead until a second cell exists;
    * multiple cells with a promotion wave configured but per-cell
      rollback disabled (``rollback: false``, or a promote stage whose
      ``burn_threshold`` can never fire): a bad candidate promoted into
      region 1 rolls on into region 2 — the wave's whole point is that
      it halts.

    WARNING, never gating: both setups run, they just degrade the
    property the operator presumably wanted."""
    cells = list(config.get("cells") or [])
    if len(cells) < 2:
        yield Diagnostic(
            code="TPX605",
            severity=Severity.WARNING,
            field="cells",
            message=(
                f"federation config has {len(cells)} cell(s) — no"
                " failover is possible: a drain or daemon loss leaves"
                " the router nowhere to spill"
            ),
            hint=(
                "register at least two cells (`tpx cell add`) or run"
                " single-cell without the federation layer"
            ),
        )
        return
    promote = config.get("promote")
    stages: list[Mapping[str, Any]] = []
    if isinstance(promote, Mapping):
        stages = [promote]
    for entry in config.get("pipelines") or []:
        spec = entry.get("spec") if isinstance(entry, Mapping) else None
        if isinstance(spec, Mapping):
            for stage in spec.get("stages") or []:
                if (
                    isinstance(stage, Mapping)
                    and str(stage.get("kind", "")) == "promote"
                ):
                    stages.append(stage)
    for stage in stages:
        rollback_off = stage.get("rollback") is False
        try:
            threshold = float(stage.get("burn_threshold", 1.0))
        except (TypeError, ValueError):
            threshold = 1.0
        if rollback_off or threshold <= 0.0 or not math.isfinite(threshold):
            name = str(stage.get("name", "promote"))
            yield Diagnostic(
                code="TPX605",
                severity=Severity.WARNING,
                field=f"promote.{name}",
                message=(
                    f"multi-cell promotion stage {name!r} has per-cell"
                    " rollback disabled"
                    + (
                        ""
                        if rollback_off
                        else f" (burn_threshold={threshold!r} can never"
                        " fire)"
                    )
                    + " — a bad candidate halted in one region will"
                    " still roll into the next"
                ),
                hint=(
                    "enable rollback and set a finite burn_threshold > 0"
                    " on every promote stage of a multi-cell wave"
                ),
            )


# ---------------------------------------------------------------------------
# TPX7xx — deep preflight: static sharding / HBM / collective analysis
# ---------------------------------------------------------------------------


@rule("deep-preflight")
def check_deep_preflight(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX700-TPX704: the jax-free static analysis pass.

    For every role whose args resolve into a
    :class:`~torchx_tpu.analyze.plan.ParallelPlan` (a recognizable
    ``--config`` plus mesh/topology facts), propagate named shardings
    through the train/serve step, compute the static HBM fit and classify
    per-axis collective traffic ICI vs DCN — the full report is
    ``tpx explain``; this rule feeds the same diagnostics into the submit
    gate. Roles with no resolvable plan are silently skipped here (the
    TPX110 heuristic covers them); ``tpx explain`` additionally reports
    the skip as TPX705 info.
    """
    from torchx_tpu.analyze.explain import deep_preflight

    for role in ctx.app.roles:
        _plan, diags = deep_preflight(role)
        for d in diags:
            if d.code == "TPX705":
                continue  # explain-only: the gate stays quiet on skips
            yield d


@rule("plan-artifact")
def check_plan_artifact(ctx: RuleContext) -> Iterator[Diagnostic]:
    """TPX706/TPX707: the tuned-plan pin.

    When ``$TPX_PLAN_ARTIFACT`` points at a ``tpx tune`` winner artifact,
    every plan-shaped role must resolve to the SAME tuned knobs (config,
    mesh, batch, seq, remat policy, int8) — divergence is TPX706, and an
    artifact that cannot be trusted (unreadable, malformed, content
    digest mismatch) is TPX707. Roles with no resolvable plan are
    skipped: the pin constrains tuned trainers, not sidecars. Unset pin
    = rule silent, so nothing changes for untuned submits.
    """
    from torchx_tpu.analyze.explain import (
        artifact_diff_diagnostics,
        deep_preflight,
    )
    from torchx_tpu.tune.artifact import pinned_artifact_path

    path = pinned_artifact_path()
    if not path:
        return
    broken_reported = False
    for role in ctx.app.roles:
        plan, _diags = deep_preflight(role)
        if plan is None:
            continue
        diags, _detail = artifact_diff_diagnostics(path, role.name, plan)
        for d in diags:
            if d.code == "TPX707":
                if broken_reported:
                    continue  # one broken-artifact error, not one per role
                broken_reported = True
            yield d
