"""Preflight static analysis: coded diagnostics before a job reaches a queue.

Slice capacity is the scarce resource — a malformed AppDef that dies minutes
later on a cluster is the most expensive way to find a typo. This subsystem
statically evaluates an :class:`~torchx_tpu.specs.api.AppDef` (plus the
target scheduler and run opts) against a pluggable rule registry and emits
coded diagnostics, each with a severity, a role/field location, a message
and a fix hint.

Wired in three places:

* ``Runner.dryrun`` / ``Runner.run`` refuse to submit on error-severity
  diagnostics (raising :class:`LintError`); bypass with ``no_lint=True``,
  ``--no-lint`` or ``TPX_NO_LINT=1``.
* ``tpx lint <component|appdef.json> [--scheduler S] [--json]`` runs the
  same analysis standalone and exits non-zero on errors.
* component source checks (``specs/file_linter.py``) report through the
  same :class:`Diagnostic` model, so components and AppDefs share one
  report format.

Every run emits a ``launcher.lint`` span and diagnostic-count metrics
(``tpx_lint_runs_total``, ``tpx_lint_diagnostics_total``) through the obs
pipeline.

Diagnostic codes
----------------

| code | severity | meaning | fix hint |
|---|---|---|---|
| TPX001 | error | component source has a syntax error, or the function was not found | point at ``path/to/file.py:fn`` or a name from ``tpx builtins`` |
| TPX002 | error | component parameter is missing a type annotation | annotate every parameter |
| TPX003 | error | component parameter type is not CLI-renderable | use str/int/float/bool, Optional/list/dict of those |
| TPX004 | error | component takes ``**kwargs`` | enumerate parameters explicitly |
| TPX005 | error | component return annotation is not ``-> AppDef`` | components must return an AppDef |
| TPX006 | warning | component has no docstring | add a google-style docstring (it becomes the CLI help) |
| TPX007 | info | component could not be materialized with the given args; AppDef-level rules skipped | pass component arguments after the name |
| TPX010 | error | AppDef has no roles | add at least one Role |
| TPX011 | error | role has no entrypoint | set Role.entrypoint |
| TPX012 | error | ``num_replicas <= 0`` | set num_replicas >= 1 |
| TPX013 | error | ``min_replicas`` outside ``(0, num_replicas]`` | lower min_replicas or raise num_replicas |
| TPX014 | error | duplicate role names in one AppDef | make role names unique |
| TPX015 | warning | role has no image | container backends need an image |
| TPX101 | error | no such TPU slice: chip count impossible for the generation (multi-host slices are built from fixed-size host VMs; v5e/v6e pods cap at 256 chips) | use a valid chip count for the generation |
| TPX102 | error | topology dimensionality does not match the generation (v5e/v6e are 2D meshes, v4/v5p are 3D tori) | use a shape like ``4x8`` (v5e) or ``2x2x4`` (v4) |
| TPX103 | error | TPU-looking key in ``resource.devices`` | TPU chips are allocated via ``resource.tpu``, never devices |
| TPX110 | warning | ``--mesh`` pairs expert parallelism (``ep``) with ``fsdp``/``sp`` sharding: embedding/expert gathers reshard dim-sharded → batch/seq-sharded, which GSPMD partitions by involuntary full rematerialization unless gather outputs carry explicit sharding constraints (heuristic fallback — when the role resolves into a full parallelism plan, TPX700 propagation supersedes this) | pin gather outputs with ``with_sharding_constraint``, or use ``torchx_tpu.examples.train_llama`` which already does |
| TPX111 | error | unknown mesh axis name in a ``--mesh`` role arg | use the trainer mesh axes ``pp/dp/fsdp/ep/tp/sp`` |
| TPX112 | warning | ``--kernels pallas`` will silently fall back to the reference XLA ops: the role has no TPU resource, or the config/seq shapes cannot tile the fused kernels (flash attention needs head_dim 64/128/256 and a 128-divisible sequence; the fused norm needs a lane-aligned dim) | run on TPU with tileable shapes, or drop the flag (``--kernels interpret`` is the parity-testing path) |
| TPX201 | error | role env overrides a launcher-injected identity/rendezvous var (``TPX_REPLICA_ID``, ``MEGASCALE_*``, ...) | remove it — every scheduler injects it |
| TPX202 | warning | env var uses a reserved prefix (``TPX_``/``TPU_``/``MEGASCALE_``) but is not a documented knob | rename it |
| TPX203 | info | ``JAX_*`` env var set (JAX runtime config) | make sure it is intentional |
| TPX204 | warning | ``${...}`` placeholder is not a launcher macro | use ``$${...}`` for runtime shell expansion, or fix the macro name |
| TPX210 | error | two named ports map to the same number | give each port a distinct number |
| TPX211 | error | port outside 1-65535 | pick a valid TCP port |
| TPX212 | warning | serve-shaped role binds ``--port`` with no matching ``port_map`` entry | map the port so routers/serve pools can reach it |
| TPX213 | error | disaggregated serving role (``--serve-role prefill``/``decode``) declares no KV transfer path | add ``--kv-transfer`` or ``tpx/kv_transfer`` role metadata (``generate_server_disagg`` wires both) |
| TPX214 | warning | role declares SLO specs (``--slo`` / ``tpx/slo`` metadata) but the backend has no ``/metricz`` scrape path | target a scrape-reachable backend or drop the replica-scrape SLOs |
| TPX215 | warning | step profiling enabled (``--profile`` / ``TPX_PROFILE=1``) but the backend has no ``/metricz`` scrape path — ``tpx_profile_*`` summaries stay local to the replica's obs dir | target a scrape-reachable backend, or read the attribution locally with ``tpx profile`` |
| TPX220 | error | two mounts share a destination path | each mount needs a distinct dst |
| TPX221 | warning | mount destination is not absolute | use an absolute container path |
| TPX300 | info | no capability profile for the scheduler; capability rules skipped | builtin backends declare ``CAPABILITIES`` |
| TPX301 | error | mounts on a backend that does not materialize them | remove mounts or use local_docker / gke |
| TPX302 | warning | backend has no ``delete()``: supervised resubmits cannot clean up terminal attempts | expect leftover terminal jobs |
| TPX303 | error | multi-role AppDef on a single-role backend | split the app or use gke / slurm |
| TPX304 | error | multi-slice TPU role (``num_replicas > 1``) on a backend without DCN wiring | use num_replicas=1 or gke |
| TPX305 | error | backend only provisions TPU slices but the role has no ``resource.tpu`` | set resource.tpu or pick another backend |
| TPX306 | warning | ``max_retries`` set but the backend has no native restarts | run under ``tpx supervise`` |
| TPX307 | warning | backend builds concrete resource requests but cpu/memMB are unset | set Resource.cpu / Resource.memMB |
| TPX401 | warning | ``RetryPolicy.REPLICA`` on a multi-host TPU role (one host cannot rejoin the ICI collective) | use RetryPolicy.APPLICATION |
| TPX402 | error | ``max_retries < 0`` | use 0 to disable retries |
| TPX403 | warning | supervisor preemption budget on a backend that cannot classify preemptions | raise max_app_retries or switch backend |
| TPX404 | warning | role sets the supervisor's resume env var (it is injected on every resubmission) | let the supervisor drive resume |
| TPX501 | warning | supervisor resubmit budgets stack multiplicatively with the backend's native ``max_retries`` restarts | set max_retries=0 under ``tpx supervise`` |
| TPX502 | error | ``TPX_FAULT_PLAN`` set while submitting to a non-local backend (chaos drill would corrupt real cloud calls) | unset it or drill against local / local_docker |
| TPX503 | warning | policy budgets checkpoint-resume retries but no role passes a checkpoint-dir flag (every resubmit restarts from step 0) | pass ``--ckpt-dir`` to the app or drop ``checkpoint_dir`` |
| TPX601 | warning | hang detection under the control daemon (``TPX_CONTROL_ADDR``) on a backend without the ``watch`` capability — state changes surface at the watch poll interval | use a watch-capable backend, tighten ``TPX_WATCH_INTERVAL``, or unset ``TPX_CONTROL_ADDR`` |
| TPX602 | warning | fleet class ``batch``/``preemptible`` (a preemption-market victim) with neither ``elastic_reshape`` nor a checkpoint-dir flag — every market shrink/preemption costs full progress | make the gang elastic (policy ``elastic_reshape`` + mesh, submit ``elastic=true``) or pass ``--ckpt-dir`` |
| TPX603 | warning | pipeline promotion stage (``tpx/pipeline=promote`` metadata) on a backend without ``/metricz`` scrape — the canary burn-rate gate sees zero samples and silently degrades to eval-score-only | run the promote stage on a scrape-reachable backend (local, docker, gke, slurm) or accept eval-score-only gating |
| TPX604 | warning | simulation scenario names a backend other than ``sim`` — the virtual-time harness only drives the modeled executor, so every journaled placement is simulated regardless of the label | set ``"backend": "sim"`` (or drop the key) so the journal cannot be mistaken for a real-backend run |
| TPX605 | warning | federation config with a single cell (no failover possible — a drain or daemon loss leaves the router nowhere to spill), or a multi-cell promotion wave without per-cell rollback enabled (a bad candidate halted in one region still rolls into the next) | register at least two cells (``tpx cell add``); enable rollback with a finite ``burn_threshold > 0`` on every promote stage of a multi-cell wave |
| TPX700 | error | deep preflight: sharding propagation found a resharding boundary GSPMD resolves by involuntary full rematerialization (dim-sharded gather/dispatch into a batch/seq-sharded consumer with no output constraint) | pin the gather/combine output with ``with_sharding_constraint`` (see ``models/llama.py forward_features``), or train with ``torchx_tpu.examples.train_llama`` |
| TPX701 | error | deep preflight: static HBM fit exceeded — params + optimizer + gradients + activations + logits outgrow the per-chip budget under the headroom | raise ``fsdp``/``tp``, lower ``--batch``/``--seq``, or use ``--remat-policy full`` |
| TPX702 | warning | deep preflight: a DCN-classified mesh axis (``fsdp``/``ep``/``tp``/``sp``) carries ICI-scale collective traffic — cross-slice bandwidth will pace every step | keep fsdp/ep/tp/sp inside a slice; put only dp/pp on the cross-slice dimension |
| TPX703 | error | deep preflight: the role is plan-shaped but the ``--mesh`` spec cannot resolve onto its device count | make the axis sizes multiply out to slices × chips (or replicas × nproc) |
| TPX704 | warning | deep preflight: a serve-shaped role's params + KV pool do not fit the per-chip HBM | lower ``--max-batch``, shorten ``max_seq``, or use a larger-HBM generation |
| TPX705 | info | deep preflight skipped: no parallelism plan resolvable from the role args (``tpx explain`` only — the submit gate falls back to the TPX110 heuristic) | use a builtin ``--config`` name to enable static sharding/HBM analysis |
| TPX706 | error | the role's resolved plan diverges from the pinned ``tpx tune`` artifact (``$TPX_PLAN_ARTIFACT``): a tuned knob (config/mesh/batch/seq/remat/int8) was changed after tuning | re-run ``tpx tune`` for the new config, or fix the drifted flag to match the artifact (the message lists each diverging field) |
| TPX707 | error | the pinned ``$TPX_PLAN_ARTIFACT`` file is unreadable, malformed, or fails its content digest (edited by hand?) | re-emit the artifact with ``tpx tune``, or unset ``TPX_PLAN_ARTIFACT`` to submit unpinned |
| TPX901 | error | selfcheck: a jax-free layer imports jax eagerly — directly or through a chain of module-level imports (``tpx selfcheck``, whole-program import graph) | make the first edge of the evidence chain a function-local import |
| TPX910 | error | selfcheck: raw ``time.time/sleep/monotonic()`` call in a sim-hosted module (derived by reachability from ``sim/harness.py``) outside the clock seams | accept injected ``clock``/``sleep`` callables defaulting to the real ones |
| TPX920 | error | selfcheck: unguarded mutable attribute write in a class whose instances cross threads (thread-entry evidence in the message) | wrap the write in ``with self._lock:`` |
| TPX921 | warning | selfcheck: thread-crossing class allocates no lock at all | allocate ``self._lock = threading.Lock()`` in ``__init__`` |
| TPX930 | error | selfcheck: append handle on a journal path with no flush+fsync before the write is claimed durable | append through ``util.jsonl.append_jsonl`` |
| TPX931 | warning | selfcheck: state-file rewrite (``open(*.json, "w")``) without tmp + fsync + ``os.replace`` | rewrite through ``util.jsonl.rewrite_json`` |
| TPX932 | warning | selfcheck: journal reader hand-rolls ``json.loads`` per line instead of the torn-line-holdback helper | read through ``util.jsonl.iter_jsonl`` |
| TPX940 | warning | selfcheck: raw ``"TPX*"`` env literal outside ``settings.py`` bypasses the env registry | add/reuse an ``ENV_*`` constant in ``torchx_tpu/settings.py`` |
| TPX950 | error | selfcheck: raw ``subprocess.*`` in ``schedulers/`` outside the resilient ``_run_cmd``/``_popen`` seam | route it through the backend's ``_run_cmd`` |

The TPX9xx rows are emitted by ``tpx selfcheck``
(:mod:`torchx_tpu.analyze.selfcheck`), the whole-program invariant
analyzer over the launcher's own source tree, not by the submit-path
``analyze()`` gate.
"""

from torchx_tpu.analyze.diagnostics import (
    Diagnostic,
    LintError,
    LintReport,
    Severity,
)
from torchx_tpu.analyze.engine import analyze, analyze_component, capabilities_for
from torchx_tpu.analyze.explain import ExplainReport, deep_preflight, explain
from torchx_tpu.analyze.plan import (
    MODEL_SHAPES,
    ModelShape,
    ParallelPlan,
    PlanError,
    plan_from_role,
)
from torchx_tpu.analyze.propagation import Boundary, ShardingFlow, propagate
from torchx_tpu.analyze.rules import (
    RuleContext,
    all_rules,
    register_rule,
    rule,
)

__all__ = [
    "Severity",
    "Diagnostic",
    "LintReport",
    "LintError",
    "RuleContext",
    "rule",
    "register_rule",
    "all_rules",
    "analyze",
    "analyze_component",
    "capabilities_for",
    "ExplainReport",
    "explain",
    "deep_preflight",
    "ModelShape",
    "MODEL_SHAPES",
    "ParallelPlan",
    "PlanError",
    "plan_from_role",
    "Boundary",
    "ShardingFlow",
    "propagate",
]
