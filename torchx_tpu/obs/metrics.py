"""In-process metrics registry with a Prometheus-textfile exporter.

Counters, gauges, and fixed-bucket histograms, labeled, thread-safe, and
dependency-free — the launch path is low-rate, so a dict behind a lock is
the right amount of machinery. :meth:`MetricsRegistry.render` emits the
Prometheus text exposition format; :func:`torchx_tpu.obs.sinks.flush_metrics`
writes it atomically to a per-process ``.prom`` textfile that a node
exporter (or ``tpx trace --metrics``) picks up.

The module-level instruments below are the launcher's standard metrics:
API latency, poll counts, retries per failure class, backoff time, and
launch latency (submit-to-app-id client-side, launch-to-first-step
in-job).
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Mapping, Optional, Sequence

LabelValues = tuple[str, ...]

#: default histogram buckets (seconds), tuned for launcher latencies:
#: sub-second API calls up to multi-minute scheduling waits.
DEFAULT_BUCKETS: tuple[float, ...] = (
    0.005,
    0.025,
    0.1,
    0.5,
    1.0,
    5.0,
    15.0,
    30.0,
    60.0,
    120.0,
    300.0,
    600.0,
)


def _format_labels(names: Sequence[str], values: LabelValues) -> str:
    if not names:
        return ""
    pairs = ",".join(
        f'{n}="{_escape(v)}"' for n, v in zip(names, values)
    )
    return "{" + pairs + "}"


def _escape(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _format_value(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


class _Metric:
    """Shared label plumbing for all instrument types."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Sequence[str]) -> None:  # noqa: A002
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()

    def _key(self, labels: Mapping[str, str]) -> LabelValues:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} takes labels {self.labelnames},"
                f" got {tuple(labels)}"
            )
        return tuple(str(labels[n]) for n in self.labelnames)

    def render(self) -> list[str]:
        """One Prometheus text-format sample line per labeled series."""
        raise NotImplementedError


class Counter(_Metric):
    """Monotonically increasing count (e.g. polls, retries)."""

    kind = "counter"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = ()) -> None:  # noqa: A002
        super().__init__(name, help, labelnames)
        self._values: dict[LabelValues, float] = {}

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        """Add ``amount`` (must be >= 0) to the labeled series."""
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        """Current value of the labeled series (0 if never incremented)."""
        return self._values.get(self._key(labels), 0.0)

    def render(self) -> list[str]:
        with self._lock:
            return [
                f"{self.name}{_format_labels(self.labelnames, k)} {_format_value(v)}"
                for k, v in sorted(self._values.items())
            ]


class Gauge(_Metric):
    """A value that can go up and down (e.g. active attempts)."""

    kind = "gauge"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = ()) -> None:  # noqa: A002
        super().__init__(name, help, labelnames)
        self._values: dict[LabelValues, float] = {}

    def set(self, value: float, **labels: str) -> None:
        """Set the labeled series to ``value``."""
        key = self._key(labels)
        with self._lock:
            self._values[key] = float(value)

    def value(self, **labels: str) -> float:
        """Current value of the labeled series (0 if never set)."""
        return self._values.get(self._key(labels), 0.0)

    def render(self) -> list[str]:
        with self._lock:
            return [
                f"{self.name}{_format_labels(self.labelnames, k)} {_format_value(v)}"
                for k, v in sorted(self._values.items())
            ]


class Histogram(_Metric):
    """Fixed-bucket distribution (cumulative buckets, Prometheus style)."""

    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str,  # noqa: A002
        labelnames: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_BUCKETS,
    ) -> None:
        super().__init__(name, help, labelnames)
        if list(buckets) != sorted(buckets) or not buckets:
            raise ValueError(f"histogram {name} buckets must be sorted and non-empty")
        self.buckets = tuple(float(b) for b in buckets)
        # per-series: [bucket counts..., +Inf count], sum
        self._counts: dict[LabelValues, list[int]] = {}
        self._sums: dict[LabelValues, float] = {}

    def observe(self, value: float, **labels: str) -> None:
        """Record one observation into the labeled series."""
        key = self._key(labels)
        idx = bisect_left(self.buckets, value)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * (len(self.buckets) + 1))
            counts[idx] += 1
            self._sums[key] = self._sums.get(key, 0.0) + float(value)

    def count(self, **labels: str) -> int:
        """Total observations in the labeled series."""
        return sum(self._counts.get(self._key(labels), ()))

    def sum(self, **labels: str) -> float:
        """Sum of observations in the labeled series."""
        return self._sums.get(self._key(labels), 0.0)

    def render(self) -> list[str]:
        lines = []
        with self._lock:
            for key in sorted(self._counts):
                counts = self._counts[key]
                cumulative = 0
                names = (*self.labelnames, "le")
                for bound, n in zip(self.buckets, counts):
                    cumulative += n
                    values = (*key, _format_value(bound))
                    lines.append(
                        f"{self.name}_bucket{_format_labels(names, values)} {cumulative}"
                    )
                cumulative += counts[-1]
                values = (*key, "+Inf")
                lines.append(
                    f"{self.name}_bucket{_format_labels(names, values)} {cumulative}"
                )
                lines.append(
                    f"{self.name}_sum{_format_labels(self.labelnames, key)}"
                    f" {_format_value(self._sums[key])}"
                )
                lines.append(
                    f"{self.name}_count{_format_labels(self.labelnames, key)}"
                    f" {cumulative}"
                )
        return lines


class MetricsRegistry:
    """Name-keyed collection of instruments; ``counter``/``gauge``/
    ``histogram`` are get-or-create (idempotent across modules), and
    :meth:`render` emits the whole registry in Prometheus text format."""

    def __init__(self) -> None:
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def __repr__(self) -> str:
        # stable (no object address): this repr lands in generated docs
        return f"MetricsRegistry({sorted(self._metrics)})"

    def _get_or_create(self, cls, name: str, *args, **kwargs) -> _Metric:  # noqa: ANN001,ANN002
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls):
                    raise ValueError(
                        f"metric {name} already registered as {existing.kind}"
                    )
                return existing
            metric = cls(name, *args, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(
        self, name: str, help: str, labelnames: Sequence[str] = ()  # noqa: A002
    ) -> Counter:
        """Get or create a :class:`Counter`."""
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(
        self, name: str, help: str, labelnames: Sequence[str] = ()  # noqa: A002
    ) -> Gauge:
        """Get or create a :class:`Gauge`."""
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(
        self,
        name: str,
        help: str,  # noqa: A002
        labelnames: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_BUCKETS,
    ) -> Histogram:
        """Get or create a :class:`Histogram` with fixed ``buckets``."""
        return self._get_or_create(Histogram, name, help, labelnames, buckets)

    def get(self, name: str) -> Optional[_Metric]:
        """The registered instrument, or None."""
        return self._metrics.get(name)

    def render(self) -> str:
        """The whole registry in Prometheus text exposition format
        (HELP/TYPE headers + one line per labeled series). Series-less
        instruments render headers only, so the page documents every
        metric the launcher can emit."""
        out: list[str] = []
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        for m in metrics:
            out.append(f"# HELP {m.name} {m.help}")
            out.append(f"# TYPE {m.name} {m.kind}")
            out.extend(m.render())
        return "\n".join(out) + "\n"


#: the process-wide registry every instrument below lives in.
REGISTRY = MetricsRegistry()

#: latency of each Runner API call, by api + scheduler.
API_LATENCY = REGISTRY.histogram(
    "tpx_api_latency_seconds",
    "Runner API call latency in seconds",
    ("api", "scheduler"),
)

#: Runner API call count by api + scheduler + outcome ("ok"/"error").
API_CALLS = REGISTRY.counter(
    "tpx_api_calls_total",
    "Runner API calls",
    ("api", "scheduler", "status"),
)

#: status polls issued by Runner.wait, by scheduler.
WAIT_POLLS = REGISTRY.counter(
    "tpx_wait_polls_total",
    "status polls issued while waiting for a terminal state",
    ("scheduler",),
)

#: supervisor resubmissions, by failure class.
RETRIES = REGISTRY.counter(
    "tpx_supervisor_retries_total",
    "supervisor resubmissions by failure class",
    ("failure_class",),
)

#: total seconds the supervisor spent in backoff sleeps.
BACKOFF_SECONDS = REGISTRY.counter(
    "tpx_supervisor_backoff_seconds_total",
    "total supervisor backoff sleep seconds",
)

#: unhealthy gang verdicts from the gang monitor (status still RUNNING),
#: by verdict kind (HANG / PARTIAL_LOSS / STRAGGLER).
GANG_UNHEALTHY = REGISTRY.counter(
    "tpx_gang_unhealthy_total",
    "unhealthy gang-health verdicts by kind",
    ("kind",),
)

#: elastic mesh reshapes computed for a resubmission (dp/fsdp shrunk to
#: fit surviving capacity).
GANG_RESHAPES = REGISTRY.counter(
    "tpx_gang_reshapes_total",
    "elastic mesh reshapes applied on resubmit",
)

#: client-side launch latency: schedule() call to app_id in hand.
LAUNCH_SECONDS = REGISTRY.histogram(
    "tpx_launch_seconds",
    "scheduler submit latency (schedule call to app id) in seconds",
    ("scheduler",),
)

#: in-job launch-to-first-step latency (reported by train heartbeats).
LAUNCH_TO_FIRST_STEP = REGISTRY.histogram(
    "tpx_launch_to_first_step_seconds",
    "process start to first completed training step in seconds",
)

#: steady-state training step time, by phase: "total" = wall time per
#: step, "data_wait" = the slice of it the host spent blocked on input
#: (prefetcher queue waits). Fed at each log fence with the window's
#: per-step averages — the ``step.*`` trace-family counterpart.
STEP_SECONDS = REGISTRY.histogram(
    "tpx_step_seconds",
    "training step seconds by phase (total / data_wait)",
    ("phase",),
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0),
)

#: step-profiler summary exports (obs/profile.py): the last profiled
#: run's per-step attribution, published so the telemetry plane and
#: ``tpx top`` can surface fleet-wide MFU / data-wait / overlap without
#: reading any profile journal. Gauges (not histograms): each profiled
#: run overwrites its process's snapshot.
PROFILE_PHASE_SECONDS = REGISTRY.gauge(
    "tpx_profile_phase_seconds",
    "profiled per-step seconds by attribution phase",
    ("phase",),
)

#: model FLOPs utilization of the last profiled run.
PROFILE_MFU = REGISTRY.gauge(
    "tpx_profile_mfu",
    "model FLOPs utilization measured by the step profiler",
)

#: fraction of profiled step time the host spent blocked on input.
PROFILE_DATA_WAIT_FRAC = REGISTRY.gauge(
    "tpx_profile_data_wait_frac",
    "fraction of profiled step time spent waiting on input",
)

#: collective overlap fraction (1 - exposed/modeled comm time).
PROFILE_OVERLAP_FRAC = REGISTRY.gauge(
    "tpx_profile_overlap_frac",
    "profiled collective overlap fraction (1 - exposed/modeled comm)",
)

#: per-stage breakdown of launch-to-first-step (the ``launch.*`` span
#: family): import / backend_init / init_state / restore / data_setup /
#: compile / first_step — makes launch regressions attributable.
LAUNCH_STAGE_SECONDS = REGISTRY.histogram(
    "tpx_launch_stage_seconds",
    "seconds spent per launch bootstrap stage",
    ("stage",),
)

#: Runner describe-cache hits (TTL-fresh, pinned-terminal, or coalesced
#: onto an in-flight fetch), by scheduler.
DESCRIBE_CACHE_HITS = REGISTRY.counter(
    "tpx_describe_cache_hits_total",
    "describe calls served from the Runner describe cache",
    ("scheduler",),
)

#: Runner describe-cache misses (a real backend describe was issued).
DESCRIBE_CACHE_MISSES = REGISTRY.counter(
    "tpx_describe_cache_misses_total",
    "describe calls that went through to the scheduler backend",
    ("scheduler",),
)

#: preflight lint runs, by entry point ("runner"/"cli") and outcome
#: ("clean"/"errors").
LINT_RUNS = REGISTRY.counter(
    "tpx_lint_runs_total",
    "preflight analyzer runs",
    ("gate", "status"),
)

#: diagnostics emitted by the preflight analyzer, by code + severity.
LINT_DIAGNOSTICS = REGISTRY.counter(
    "tpx_lint_diagnostics_total",
    "preflight diagnostics emitted",
    ("code", "severity"),
)

#: deep-preflight (``tpx explain``) runs, by entry point and outcome.
EXPLAIN_RUNS = REGISTRY.counter(
    "tpx_explain_runs_total",
    "deep-preflight analyzer runs",
    ("gate", "status"),
)

#: TPX7xx diagnostics emitted by the deep preflight, by code + severity.
EXPLAIN_DIAGNOSTICS = REGISTRY.counter(
    "tpx_explain_diagnostics_total",
    "deep-preflight diagnostics emitted",
    ("code", "severity"),
)

#: statically-predicted per-chip HBM usage of the last explained plan,
#: by role — compared against the measured/compiled numbers in BENCH.
EXPLAIN_HBM_TOTAL_BYTES = REGISTRY.gauge(
    "tpx_explain_hbm_total_bytes",
    "per-chip HBM bytes the deep preflight predicts for a role's plan",
    ("role",),
)

#: candidates the config autotuner (``tpx tune``) enumerated, by model
#: config — the top of the prune funnel.
TUNE_CANDIDATES = REGISTRY.counter(
    "tpx_tune_candidates_total",
    "autotuner candidates enumerated from the search space",
    ("config",),
)

#: autotuner candidates killed before any device time, by prune stage
#: ("static" = deep-preflight verdict, "aot" = XLA AOT memory fit) and
#: the diagnostic code / verdict that killed them.
TUNE_PRUNED = REGISTRY.counter(
    "tpx_tune_pruned_total",
    "autotuner candidates pruned with zero device seconds",
    ("stage", "code"),
)

#: autotuner trials that reached a device, by outcome ("ok"/"failed").
TUNE_MEASURED = REGISTRY.counter(
    "tpx_tune_measured_total",
    "autotuner measured trials",
    ("status",),
)

#: control-plane calls issued through the resilient seam, by backend +
#: logical op + outcome ("ok"/"error"/"rejected" — rejected means the
#: backend's circuit breaker refused the call).
CONTROL_PLANE_CALLS = REGISTRY.counter(
    "tpx_control_plane_calls_total",
    "control-plane calls issued through the resilient seam",
    ("backend", "op", "status"),
)

#: control-plane call retries, by backend + op + classified failure kind.
CONTROL_PLANE_RETRIES = REGISTRY.counter(
    "tpx_control_plane_retries_total",
    "control-plane call retries by failure kind",
    ("backend", "op", "kind"),
)

#: per-backend circuit breaker state (0 closed, 1 half-open, 2 open).
BREAKER_STATE = REGISTRY.gauge(
    "tpx_control_plane_breaker_state",
    "control-plane circuit breaker state (0 closed, 1 half-open, 2 open)",
    ("backend",),
)

#: serving-engine slot occupancy: fraction of decode slots holding an
#: active sequence this step (sustained occupancy is what keeps
#: HBM-bandwidth-bound decode fed — the continuous-batching win).
SERVE_OCCUPANCY = REGISTRY.gauge(
    "tpx_serve_slot_occupancy",
    "fraction of decode slots active in the serving engine",
)

#: decode slots currently holding an active sequence.
SERVE_SLOTS_ACTIVE = REGISTRY.gauge(
    "tpx_serve_slots_active",
    "decode slots currently active in the serving engine",
)

#: requests admitted but not yet completed, waiting for a free slot.
SERVE_QUEUE_DEPTH = REGISTRY.gauge(
    "tpx_serve_queue_depth",
    "requests waiting for a decode slot in the serving engine",
)

#: paged KV blocks currently allocated to live sequences.
SERVE_KV_BLOCKS_USED = REGISTRY.gauge(
    "tpx_serve_kv_blocks_used",
    "paged KV-cache blocks held by active sequences",
)

#: recurrent state the engine holds beside its paged K/V, over all slots' rows
#: (state-space layers; 0 for a model whose every cache is attention's).
SERVE_STATE_BYTES = REGISTRY.gauge(
    "tpx_serve_state_bytes",
    "bytes of recurrent (state-space) state held for the engine's slots",
)

#: rows of paged cache the engine's slots hold where a slot's rows are not its
#: tokens (EVA attention: a window's rows, the pooled rows behind it, those
#: staged); not set for a model that holds a row a token.
SERVE_CACHE_ROWS_HELD = REGISTRY.gauge(
    "tpx_serve_cache_rows_held",
    "rows of paged cache held by the engine's slots (a cache whose rows are not its tokens)",
)

#: decode tokens produced, by phase ("prefill" first tokens vs "decode").
SERVE_TOKENS = REGISTRY.counter(
    "tpx_serve_tokens_total",
    "tokens produced by the serving engine",
    ("phase",),
)

#: completed requests, by outcome ("ok"/"error").
SERVE_REQUESTS = REGISTRY.counter(
    "tpx_serve_requests_total",
    "requests completed by the serving engine",
    ("status",),
)

#: sequences preempted (blocks reclaimed, request requeued) because the
#: KV pool ran out of free blocks mid-decode.
SERVE_PREEMPTIONS = REGISTRY.counter(
    "tpx_serve_preemptions_total",
    "sequences preempted for KV-pool pressure and requeued",
)

#: time-to-first-token per request, seconds.
SERVE_TTFT_SECONDS = REGISTRY.histogram(
    "tpx_serve_ttft_seconds",
    "request time-to-first-token in seconds",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0),
)

#: per-token decode latency (time-per-output-token) per request, seconds.
SERVE_TPOT_SECONDS = REGISTRY.histogram(
    "tpx_serve_tpot_seconds",
    "request mean time-per-output-token in seconds",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 5.0),
)

#: serve-pool replica count, as last applied by the autoscaler.
SERVE_REPLICAS = REGISTRY.gauge(
    "tpx_serve_replicas",
    "generate_server replicas the serve pool is currently running",
)

#: serve-pool autoscaling decisions, by direction ("up"/"down").
SERVE_SCALE_EVENTS = REGISTRY.counter(
    "tpx_serve_scale_events_total",
    "serve-pool autoscale resizes applied",
    ("direction",),
)

#: prefix-cache lookups that matched at least one cached block.
SERVE_PREFIX_HITS = REGISTRY.counter(
    "tpx_serve_prefix_hits_total",
    "prefix-cache lookups that reused cached KV blocks",
)

#: prefix-cache lookups that matched nothing (cold prefix).
SERVE_PREFIX_MISSES = REGISTRY.counter(
    "tpx_serve_prefix_misses_total",
    "prefix-cache lookups with no cached prefix",
)

#: prompt tokens served from cached KV blocks instead of re-prefilling.
SERVE_PREFIX_HIT_TOKENS = REGISTRY.counter(
    "tpx_serve_prefix_hit_tokens_total",
    "prompt tokens whose KV came from the prefix cache",
)

#: KV blocks currently pinned by the prefix cache (refcount held).
SERVE_PREFIX_CACHED_BLOCKS = REGISTRY.gauge(
    "tpx_serve_prefix_cached_blocks",
    "paged KV blocks pinned by the prefix cache",
)

#: cache-only blocks evicted (LRU) under pool pressure or reserve cap.
SERVE_PREFIX_EVICTIONS = REGISTRY.counter(
    "tpx_serve_prefix_evictions_total",
    "prefix-cache blocks evicted back to the free list",
)

#: copy-on-write block copies (shared tail block about to be written).
SERVE_COW_COPIES = REGISTRY.counter(
    "tpx_serve_cow_copies_total",
    "shared KV blocks copied before an in-place append",
)

#: prefill->decode KV handoffs, by outcome ("ok"/"rejected"/"error") —
#: "rejected" is a draining decode target (the transfer is requeued).
SERVE_KV_TRANSFERS = REGISTRY.counter(
    "tpx_serve_kv_transfers_total",
    "KV block transfers between prefill and decode replicas",
    ("status",),
)

#: payload bytes moved prefill->decode (K+V blocks, serialized).
SERVE_KV_TRANSFER_BYTES = REGISTRY.counter(
    "tpx_serve_kv_transfer_bytes_total",
    "bytes of KV blocks streamed from prefill to decode replicas",
)

# -- fleet control plane (torchx_tpu/control/) ------------------------------

#: state-transition events emitted by scheduler watch streams, by source
#: ("sidecar"/"kubectl"/"poll") — the control plane's unit of work.
WATCH_EVENTS = REGISTRY.counter(
    "tpx_watch_events_total",
    "scheduler watch-stream state events observed",
    ("scheduler", "source"),
)

#: live watch streams, one per (scheduler, reconciler) pair.
WATCH_STREAMS = REGISTRY.gauge(
    "tpx_watch_streams",
    "watch streams currently owned by a reconciler",
    ("scheduler",),
)

#: Runner.wait waiters woken early by a reconciler event (instead of
#: sleeping out their full poll interval).
WAITER_WAKEUPS = REGISTRY.counter(
    "tpx_waiter_wakeups_total",
    "wait() waiters woken by a watch event before their poll interval",
    ("scheduler",),
)

#: control-daemon HTTP requests, by logical op and response code.
CONTROL_REQUESTS = REGISTRY.counter(
    "tpx_control_requests_total",
    "control daemon API requests served",
    ("op", "code"),
)

#: control-daemon request latency by logical op.
CONTROL_REQUEST_SECONDS = REGISTRY.histogram(
    "tpx_control_request_seconds",
    "control daemon API request latency in seconds",
    ("op",),
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 5.0),
)

#: active (non-terminal) jobs the daemon tracks per tenant — the value the
#: per-tenant 429 cap is enforced against.
CONTROL_ACTIVE_JOBS = REGISTRY.gauge(
    "tpx_control_active_jobs",
    "active jobs per control-daemon tenant",
    ("tenant",),
)

# -- fleet scheduler (torchx_tpu/fleet/) -------------------------------------

#: gangs waiting in the fleet queue, per priority class.
FLEET_QUEUE_DEPTH = REGISTRY.gauge(
    "tpx_fleet_queue_depth",
    "gangs queued in the fleet scheduler per priority class",
    ("klass",),
)

#: modeled fleet capacity in chips (series: state="total" / state="free").
FLEET_CHIPS = REGISTRY.gauge(
    "tpx_fleet_chips",
    "modeled fleet capacity in chips, total and currently free",
    ("state",),
)

#: chips currently placed per tenant (the quota accounting value).
FLEET_TENANT_CHIPS = REGISTRY.gauge(
    "tpx_fleet_tenant_chips",
    "chips currently placed per fleet tenant",
    ("tenant",),
)

#: gang placements executed, per priority class.
FLEET_PLACEMENTS = REGISTRY.counter(
    "tpx_fleet_placements_total",
    "gangs placed by the fleet scheduler",
    ("klass",),
)

#: market actions taken: kind="shrink" (elastic mesh-reshape, no kill) or
#: kind="requeue" (checkpoint-preempt of a non-elastic victim).
FLEET_PREEMPTIONS = REGISTRY.counter(
    "tpx_fleet_preemptions_total",
    "preemption-market actions executed, by kind",
    ("kind",),
)

#: shrink debts repaid — gangs grown back to their launch mesh.
FLEET_GROWBACKS = REGISTRY.counter(
    "tpx_fleet_growbacks_total",
    "shrunk gangs grown back to launch size",
)

#: queue wait from submit (or requeue) to placement, per priority class.
FLEET_GANG_WAIT_SECONDS = REGISTRY.histogram(
    "tpx_fleet_gang_wait_seconds",
    "gang wait time from enqueue to placement in seconds",
    ("klass",),
)


# -- pipelines (torchx_tpu/pipelines/) ------------------------------------

#: pipelines that reached a terminal state, by that state
#: (PROMOTED/SUCCEEDED/FAILED/ROLLED_BACK/CANCELLED).
PIPELINE_RUNS = REGISTRY.counter(
    "tpx_pipeline_runs_total",
    "pipelines finished, by terminal state",
    ("state",),
)

#: pipelines currently in a non-terminal state.
PIPELINE_ACTIVE = REGISTRY.gauge(
    "tpx_pipeline_active",
    "pipelines currently pending, running, or in canary",
)

#: stage transitions, by stage kind and the state entered.
PIPELINE_STAGES = REGISTRY.counter(
    "tpx_pipeline_stages_total",
    "pipeline stage transitions, by kind and state",
    ("kind", "state"),
)

#: eval-gate and canary-gate verdicts.
PIPELINE_GATES = REGISTRY.counter(
    "tpx_pipeline_gate_decisions_total",
    "pipeline gate decisions (eval threshold + canary gates)",
    ("decision",),
)

#: automatic canary rollbacks, by reason (eval_regression/slo_burn/
#: rollout_failed).
PIPELINE_ROLLBACKS = REGISTRY.counter(
    "tpx_pipeline_rollbacks_total",
    "canary rollbacks executed, by reason",
    ("reason",),
)

#: wall-clock from stage submit to terminal, per stage kind.
PIPELINE_STAGE_SECONDS = REGISTRY.histogram(
    "tpx_pipeline_stage_seconds",
    "pipeline stage duration from submit to terminal in seconds",
    ("kind",),
)


# -- virtual-time simulation (tpx sim) --------------------------------------

#: events processed by the sim harness's virtual-time loop, by kind
#: (arrival/gang_done/fault/tick/pipeline/wake).
SIM_EVENTS = REGISTRY.counter(
    "tpx_sim_events_total",
    "virtual-time events processed by the sim harness, by kind",
    ("kind",),
)

#: faults the harness injected, by kind.
SIM_FAULTS = REGISTRY.counter(
    "tpx_sim_faults_total",
    "faults injected into the simulated fleet, by kind",
    ("kind",),
)

#: virtual seconds covered by the last completed sim run.
SIM_VIRTUAL_SECONDS = REGISTRY.gauge(
    "tpx_sim_virtual_seconds",
    "virtual time span of the last completed sim run in seconds",
)

#: wall seconds the last completed sim run took to execute.
SIM_WALL_SECONDS = REGISTRY.gauge(
    "tpx_sim_wall_seconds",
    "wall-clock execution time of the last completed sim run in seconds",
)

#: virtual/wall speedup of the last completed sim run.
SIM_SPEEDUP = REGISTRY.gauge(
    "tpx_sim_speedup",
    "virtual-over-wall time ratio of the last completed sim run",
)


# -- federation (torchx_tpu/federation/) ------------------------------------

#: gauge encoding for a cell's lifecycle state (UNCORDONED is
#: transitional and reads back as HEALTHY).
CELL_STATE_VALUES = {"HEALTHY": 0, "DRAINING": 1, "DRAINED": 2}

#: one cell's lifecycle state, using :data:`CELL_STATE_VALUES`.
FED_CELL_STATE = REGISTRY.gauge(
    "tpx_federation_cell_state",
    "federation cell lifecycle (0=healthy, 1=draining, 2=drained)",
    ("cell",),
)

#: the long-window SLO burn the router last observed per cell.
FED_CELL_BURN = REGISTRY.gauge(
    "tpx_federation_cell_burn",
    "max long-window SLO burn rate the router last observed, per cell",
    ("cell",),
)

#: requests the federation router dispatched, by target cell + outcome
#: (ok/error/refused).
FED_REQUESTS = REGISTRY.counter(
    "tpx_federation_requests_total",
    "requests dispatched by the federation router, by cell and outcome",
    ("cell", "outcome"),
)

#: requests that landed on a cell other than the router's first choice
#: (burn over budget, breaker open, drain, or dial failure).
FED_SPILLOVERS = REGISTRY.counter(
    "tpx_federation_spillovers_total",
    "requests spilled past the first-choice cell, by reason",
    ("reason",),
)

#: per-cell circuit breaker state
#: (:data:`torchx_tpu.resilience.breaker.STATE_VALUES` encoding).
FED_BREAKER_STATE = REGISTRY.gauge(
    "tpx_federation_breaker_state",
    "per-cell dial circuit breaker state (0=closed, 1=half-open, 2=open)",
    ("cell",),
)
