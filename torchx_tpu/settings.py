"""Central registry of every environment variable the framework reads/writes.

Reference analog: torchx/settings.py:1-37 (all ``TORCHX_*`` env constants
centralized in one module). We use the ``TPX_`` prefix.

Variables fall into three groups:

* client-side knobs read by the Runner / CLI,
* in-job variables injected by schedulers into every replica,
* TPU runtime variables owned by the platform (GKE / libtpu) that the
  launcher must cooperate with rather than own.
"""

# ---------------------------------------------------------------------------
# Client side
# ---------------------------------------------------------------------------

# Points at an explicit config file, overriding the lookup chain
# (CLI > $TPXCONFIG > $HOME/.tpxconfig > CWD). See runner/config.py.
ENV_TPXCONFIG = "TPXCONFIG"

# Comma list of extra named-resource modules to load (module[:fn] specs).
ENV_TPX_CUSTOM_NAMED_RESOURCES = "TPX_CUSTOM_NAMED_RESOURCES"

# Bitmask controlling which plugin sources are consulted (see plugins/).
ENV_TPX_PLUGINS_SOURCE = "TPX_PLUGINS_SOURCE"

# Propagates the client session id into subprocesses for event correlation.
ENV_TPX_INTERNAL_SESSION_ID = "TPX_INTERNAL_SESSION_ID"

# Scheduler params harvested by the Runner from the environment, e.g.
# TPX_PARAMS_LOG_DIR=... (analog of TORCHX_* param harvesting,
# reference torchx/runner/api.py:128-134).
ENV_TPX_PARAMS_PREFIX = "TPX_PARAMS_"

# Telemetry destination for the events logger ("null"/"console"/"log"/
# "jsonl"/... — see runner/events/handlers.py).
ENV_TPX_EVENT_DESTINATION = "TPX_EVENT_DESTINATION"

# Tracing master switch: "0"/"false"/"off" disables span emission and the
# durable JSONL/metrics sinks (default: on — the launch path is low-rate).
ENV_TPX_TRACE = "TPX_TRACE"

# Root directory for durable observability output; defaults to
# ~/.torchx_tpu/obs (one subdir per client session). See obs/sinks.py.
ENV_TPX_OBS_DIR = "TPX_OBS_DIR"

# Step-profiler master switch: "1"/"true"/"yes"/"on" enables the trainer's
# per-step phase attribution (equivalent to its ``--profile`` flag),
# appending profile.jsonl under the obs session dir. See obs/profile.py.
ENV_TPX_PROFILE = "TPX_PROFILE"

# Escape hatch for the preflight analyzer gate in Runner.dryrun/run:
# "1"/"true"/"yes"/"on" skips linting entirely (same effect as the
# ``--no-lint`` CLI flag / ``no_lint=True`` Runner argument). Diagnostics
# are documented in docs/api/analyze.md; see torchx_tpu/analyze/.
ENV_TPX_NO_LINT = "TPX_NO_LINT"

# Per-call deadline (seconds) for control-plane subprocesses (gcloud /
# kubectl / sbatch / squeue ...) issued through the resilient seam
# (torchx_tpu/resilience/call.py). Unset = DEFAULT_CONTROL_PLANE_TIMEOUT;
# "0"/"off"/"none" disables the deadline entirely.
ENV_TPX_CONTROL_PLANE_TIMEOUT = "TPX_CONTROL_PLANE_TIMEOUT"

# Default for ENV_TPX_CONTROL_PLANE_TIMEOUT: generous enough for a slow
# gcloud auth refresh, small enough that a hung CLI degrades into one
# classified TIMEOUT failure instead of wedging a supervise loop.
DEFAULT_CONTROL_PLANE_TIMEOUT = 60.0

# Deterministic control-plane fault plan (inline JSON or a path to a JSON
# file) consumed by torchx_tpu/resilience/faults.py: inject transient /
# permanent / timeout / garbage-stdout failures on the Nth matching call,
# per backend+op. The control-plane counterpart of the local scheduler's
# TPX_SIMULATE_PREEMPTION_EXIT job-failure drill. The preflight analyzer
# errors (TPX502) when a plan is armed for a non-local submit.
ENV_TPX_FAULT_PLAN = "TPX_FAULT_PLAN"

# Root directory for durable supervisor session state (attempt ledger +
# resume metadata); defaults to ~/.torchx_tpu/supervisor — one subdir per
# supervise session, the ~/.torchx_tpu/obs/<session>/ convention. See
# torchx_tpu/supervisor/ledger.py and `tpx supervise --resume`.
ENV_TPX_SUPERVISOR_DIR = "TPX_SUPERVISOR_DIR"

# TTL (seconds) for the Runner's describe cache: passive readers
# (status/describe, supervision double-polls) within the TTL share one
# backend call; wait() polls always refresh (cache writer) and terminal
# states are pinned forever (immutable, so never stale). "0" disables
# caching for non-terminal states. Default DEFAULT_DESCRIBE_CACHE_TTL.
ENV_TPX_DESCRIBE_CACHE_TTL = "TPX_DESCRIBE_CACHE_TTL"

# Default for ENV_TPX_DESCRIBE_CACHE_TTL: shorter than any poll interval
# the Runner uses, so back-to-back polls from stacked layers coalesce but
# successive wait ticks always observe fresh state.
DEFAULT_DESCRIBE_CACHE_TTL = 1.0

# State root for the config autotuner (`tpx tune`): per-run trial
# journals + the persisted per-generation cost-model calibration table
# (torchx_tpu/tune/). Default ~/.torchx_tpu/tune.
ENV_TPX_TUNE_DIR = "TPX_TUNE_DIR"

# Device count assumed by `tpx tune` when --devices is not passed
# (defaults to 8, one v5p host).
ENV_TPX_TUNE_DEVICES = "TPX_TUNE_DEVICES"

# Path to a tune plan artifact (torchx_tpu/tune/artifact.py) pinned for
# submission: the submit gate (rules.check_plan_artifact) diffs every
# plan-shaped role against it and errors on divergence (TPX706) or an
# unreadable/digest-mismatched artifact (TPX707). Unset = no pinning.
ENV_TPX_PLAN_ARTIFACT = "TPX_PLAN_ARTIFACT"

# Address ("host:port") of a running `tpx control` daemon. When set, the
# CLI transparently proxies submit/status/list/cancel/log through the
# daemon's HTTP API instead of driving schedulers directly — thousands of
# callers then share ONE reconciler and ONE describe path per backend.
# Unset = direct-runner mode (the pre-daemon behavior, unchanged).
ENV_TPX_CONTROL_ADDR = "TPX_CONTROL_ADDR"

# Bearer token presented to the control daemon. Falls back to the token
# recorded in the daemon's discovery file ($TPX_CONTROL_DIR/control.json).
ENV_TPX_CONTROL_TOKEN = "TPX_CONTROL_TOKEN"

# State root for the control plane: the daemon's discovery file and the
# sharded job-state store live here. Default ~/.torchx_tpu/control.
ENV_TPX_CONTROL_DIR = "TPX_CONTROL_DIR"

# Minimum interval (seconds) between full-registry metrics textfile
# re-renders by the prom event handler (obs/sinks.py); events arriving
# inside the window mark the registry dirty and a final flush on handler
# close writes them. "0" restores flush-on-every-event.
ENV_TPX_METRICS_MIN_INTERVAL = "TPX_METRICS_MIN_INTERVAL"
DEFAULT_METRICS_MIN_INTERVAL = 2.0

# Scrape/ingest interval (seconds) of the control daemon's telemetry
# collector (obs/telemetry.py): replica /metricz scrapes + obs-session
# textfile ingestion each cycle, followed by one SLO evaluation.
ENV_TPX_TELEMETRY_INTERVAL = "TPX_TELEMETRY_INTERVAL"
DEFAULT_TELEMETRY_INTERVAL = 5.0

# Bounded per-series ring-buffer capacity (samples) of the telemetry
# collector's metric store. At the default 5s interval, 720 samples is
# one hour of history per series.
DEFAULT_TELEMETRY_CAPACITY = 720

# Poll interval (seconds) for watch adapters that fall back to polling
# (generic backends) and for the local scheduler's sidecar mtime watcher.
# Watch streams coalesce N callers into one scan, so this can be much
# tighter than Runner.wait's per-caller interval without amplifying
# control-plane calls.
ENV_TPX_WATCH_INTERVAL = "TPX_WATCH_INTERVAL"
DEFAULT_WATCH_INTERVAL = 1.0

# Default per-tenant cap on concurrently active (non-terminal) jobs
# submitted through the control daemon; submits past the cap get HTTP 429
# (daemon-only mode; with the fleet scheduler enabled submits queue instead).
DEFAULT_CONTROL_TENANT_CAP = 64

# Seconds a 429'd client should wait before resubmitting (the daemon's
# Retry-After header and the retry_after_seconds field of the error body).
CONTROL_RETRY_AFTER_SECONDS = 5

# Bounded 429 retry budget of ControlClient: how many times a throttled
# request sleeps out the daemon's Retry-After hint and retries before the
# 429 surfaces to the caller. A 429'd request never executed, so the
# retry is replay-safe (unlike transport errors on submits).
CONTROL_429_MAX_RETRIES = 3

# Ceiling (seconds) on a single Retry-After sleep honored by the client —
# a daemon bug (or a hostile proxy) must not park a CLI for an hour.
CONTROL_429_RETRY_CAP_SECONDS = 30.0

# This control daemon's cell name within a federation. Every journal
# record, /healthz reply and metric the daemon emits carries it, so a
# federation router (torchx_tpu/federation/) can address N regional
# daemons as cells. Unset = "default" (single-cell, pre-federation
# behavior unchanged).
ENV_TPX_CELL = "TPX_CELL"
DEFAULT_CELL_NAME = "default"

# State root of the federation layer: the durable cell registry
# (cells.jsonl) lives here. Default ~/.torchx_tpu/federation.
ENV_TPX_FEDERATION_DIR = "TPX_FEDERATION_DIR"

# Long-window SLO burn rate at/above which the federation router stops
# preferring a cell and spills new traffic to the next-best cell (the
# cell stays admissible as a last resort — never a hard fail while any
# cell answers).
DEFAULT_FEDERATION_BURN_BUDGET = 1.0

# Per-cell circuit breaker of the federation router: consecutive
# transport failures before the cell is skipped without a dial, and how
# long it sits out before a half-open probe.
FEDERATION_BREAKER_TRIP_AFTER = 3
FEDERATION_BREAKER_COOLDOWN_SECONDS = 5.0

# ---------------------------------------------------------------------------
# In-job (injected by schedulers into every replica)
# ---------------------------------------------------------------------------

# App handle / id of the surrounding job.
ENV_TPX_APP_ID = "TPX_APP_ID"
ENV_TPX_JOB_ID = "TPX_JOB_ID"  # full handle scheme://session/app_id

# Replica identity within the role's gang. TPX_REPLICA_ID, when present, is
# the GLOBAL process id across all slices of the role (0..TPX_NUM_REPLICAS-1).
ENV_TPX_REPLICA_ID = "TPX_REPLICA_ID"
ENV_TPX_ROLE_NAME = "TPX_ROLE_NAME"
ENV_TPX_NUM_REPLICAS = "TPX_NUM_REPLICAS"

# Multi-slice decomposition of the global id. Backends that cannot compute
# arithmetic at pod start (kubelet env expansion is substitution-only) inject
# these three instead of TPX_REPLICA_ID and the bootstrap derives
# ``replica_id = slice_id * hosts_per_slice + host_id``.
ENV_TPX_SLICE_ID = "TPX_SLICE_ID"

# Fault-injection hook for the example apps (examples/compute_mesh_size):
# "1" always throws, "once:/path/marker" throws only on the first attempt.
# _REPLICA scopes the fault to one replica of the gang. Used by
# retry/elastic-restart e2e tests to prove a gang recovers.
ENV_TPX_EXAMPLE_THROWS = "TPX_EXAMPLE_THROWS"
ENV_TPX_EXAMPLE_THROWS_REPLICA = "TPX_EXAMPLE_THROWS_REPLICA"
ENV_TPX_HOST_ID = "TPX_HOST_ID"  # host index within the slice
ENV_TPX_HOSTS_PER_SLICE = "TPX_HOSTS_PER_SLICE"

# Elastic lower bound of the gang (replicas may legally shrink to this on
# restart after host loss; see local_scheduler._try_elastic_restart).
ENV_TPX_MIN_REPLICAS = "TPX_MIN_REPLICAS"

# Host that replica 0 of role 0 runs on -- the SPMD coordinator. The *name*
# of the env var holding it is what ``macros.coordinator_env`` substitutes
# (reference analog: rank0_env, torchx/specs/api.py:216-222).
ENV_TPX_COORDINATOR_HOST = "TPX_COORDINATOR_HOST"

# Default port for jax.distributed coordinator service (analog of c10d 29500).
TPX_COORDINATOR_PORT = 8476

# File each replica writes a structured error JSON into on failure
# (reference analog: TORCHELASTIC_ERROR_FILE, local_scheduler.py:996-1001).
ENV_TPX_ERROR_FILE = "TPX_ERROR_FILE"

# Per-replica log directory.
ENV_TPX_LOG_DIR = "TPX_LOG_DIR"

# Trace correlation: the client injects these at submit so in-job spans
# (spmd_main bootstrap, the trainer's heartbeats) join the client-side trace
# instead of starting orphan traces. See obs/trace.py.
ENV_TPX_TRACE_ID = "TPX_TRACE_ID"
ENV_TPX_PARENT_SPAN = "TPX_PARENT_SPAN"

# Checkpoint step a resubmitted (supervised) run should resume from. The
# supervisor injects it from the checkpoint manifest before every
# resubmission; Checkpointer.resume_step_from_env() is the in-job reader.
ENV_TPX_RESUME_STEP = "TPX_RESUME_STEP"

# Mesh spec override (--mesh syntax, e.g. "pp=1,dp=1,fsdp=4,ep=1,tp=1,sp=1")
# the supervisor injects when an elastic reshape degrades the mesh after a
# preemption/hang; trainers honor it over their --mesh flag so a resubmitted
# attempt comes up on the surviving capacity.
ENV_TPX_MESH = "TPX_MESH"

# Injected by the fleet scheduler into every replica it places: the fleet
# job id (stable across shrink/grow reshapes) and the gang's priority
# class, so in-job tooling and log lines can be joined back to the
# scheduling decision that produced them.
ENV_TPX_FLEET_JOB = "TPX_FLEET_JOB"
ENV_TPX_FLEET_CLASS = "TPX_FLEET_CLASS"

# Preemption drill knob for the LOCAL scheduler only: when a role env sets
# this to an integer exit code, a replica exiting with that code marks the
# attempt PREEMPTED (classified FailureClass.PREEMPTION) instead of FAILED,
# so `tpx supervise` retry/backoff/resume handling can be exercised end to
# end without spot capacity. Unset = no behavior change.
ENV_TPX_SIMULATE_PREEMPTION_EXIT = "TPX_SIMULATE_PREEMPTION_EXIT"

# Manifest file the Checkpointer maintains next to its step dirs: a small
# JSON record of the latest finalized step, readable by the client-side
# supervisor WITHOUT importing jax/orbax (see supervisor/api.py).
CHECKPOINT_MANIFEST = "MANIFEST.json"

# Experiment tracking (reference analog: TORCHX_TRACKERS family,
# torchx/tracker/api.py:209-239).
ENV_TPX_TRACKERS = "TPX_TRACKERS"
ENV_TPX_TRACKER_PREFIX = "TPX_TRACKER_"  # TPX_TRACKER_<NAME>_CONFIG
ENV_TPX_PARENT_RUN_ID = "TPX_PARENT_RUN_ID"

# ---------------------------------------------------------------------------
# TPU platform variables (owned by GKE / libtpu / JAX; the launcher reads or
# forwards these but does not invent them)
# ---------------------------------------------------------------------------

# Injected by GKE on TPU node pools; authoritative host list for a slice.
ENV_TPU_WORKER_ID = "TPU_WORKER_ID"
ENV_TPU_WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
ENV_TPU_SKIP_MDS_QUERY = "TPU_SKIP_MDS_QUERY"

# Host-local chip partitioning (used by the local scheduler to split one
# host's chips between replicas -- analog of auto_set_CUDA_VISIBLE_DEVICES,
# reference local_scheduler.py:855-945).
ENV_TPU_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"
ENV_TPU_PROCESS_BOUNDS = "TPU_PROCESS_BOUNDS"
ENV_TPU_CHIPS_PER_PROCESS_BOUNDS = "TPU_CHIPS_PER_PROCESS_BOUNDS"

# Simulation: run "TPU" jobs on CPU with N virtual devices.
ENV_JAX_PLATFORMS = "JAX_PLATFORMS"
ENV_XLA_FLAGS = "XLA_FLAGS"

# Multi-slice (DCN) wiring -- analog of the EFA device plumbing in the
# reference (named_resources_aws.py:40, kubernetes_scheduler.py:346-358).
ENV_MEGASCALE_COORDINATOR_ADDRESS = "MEGASCALE_COORDINATOR_ADDRESS"
ENV_MEGASCALE_NUM_SLICES = "MEGASCALE_NUM_SLICES"
ENV_MEGASCALE_SLICE_ID = "MEGASCALE_SLICE_ID"

# RMSNorm backward selection when the call site says "auto": "never"
# (default — plain XLA backward, measured fastest on v5e at batch 2),
# "pallas" (the fused dx+dw kernel; re-evaluate at batch >= 8), or
# "interpret" (Pallas interpreter — CPU tests). See ops/norms.py.
ENV_TPX_FUSED_NORM = "TPX_FUSED_NORM"
