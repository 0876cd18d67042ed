"""Observability for the launch path: traces, metrics, durable sinks.

The subsystem answers "where did my launch time go" end to end:

* :mod:`torchx_tpu.obs.trace` — the :class:`Span` model with contextvar
  propagation; every Runner API call, scheduler materialize/schedule,
  workspace build, and supervisor attempt nests under one trace, and the
  trace context rides into the job via ``$TPX_TRACE_ID`` /
  ``$TPX_PARENT_SPAN``;
* :mod:`torchx_tpu.obs.metrics` — a dependency-free metrics registry
  (counters / gauges / fixed-bucket histograms) with the launcher's
  standard instruments (API latency, wait polls, retries per failure
  class, backoff time, launch latency);
* :mod:`torchx_tpu.obs.sinks` — durable output under
  ``~/.torchx_tpu/obs/<session>/``: a JSONL trace/event sink and a
  Prometheus-textfile metrics exporter, shared with ``TpxEvent`` through
  the events-logger pipeline;
* :mod:`torchx_tpu.obs.timeline` — reads it all back for
  ``tpx trace <app-handle>``;
* :mod:`torchx_tpu.obs.telemetry` — the fleet telemetry plane: the
  control daemon's collector scrapes replica ``/metricz`` endpoints and
  tails textfile sessions into bounded ring-buffer series, served back
  as an aggregated fleet ``/metricz`` and a ``/v1/metrics/query`` JSON
  API (``tpx top`` renders it);
* :mod:`torchx_tpu.obs.slo` — declarative SLO specs evaluated as
  multi-window burn rates with journaled alert transitions; the serve
  autoscaler and the fleet market consume the burn signal;
* :mod:`torchx_tpu.obs.stitch` — cross-process trace stitching: the
  trace context crosses HTTP hops (``X-Tpx-Trace-Id``), KV-transfer
  payloads, and fleet gang env, and ``tpx trace --stitch`` reassembles
  the one timeline per request or fleet-job lifecycle;
* :mod:`torchx_tpu.obs.profile` — per-step phase attribution for the
  trainer (``data_wait`` / ``forward_backward`` / ``grad_sync`` per mesh
  axis / ``optimizer`` / ``checkpoint`` / ``host``): MFU/roofline
  accounting, measured collective overlap, fsync'd ``profile.jsonl``
  journals rendered by ``tpx profile``, and the measured-residual feed
  into the tune calibration table.

**Two planes.** Everything above is the *launcher plane*: spans on the wall
clock, a JSON record each, durable in ``trace.jsonl``; right for a launch, a
supervisor attempt or one request's route through the pool, wrong inside a
loop that turns every 40 ms. The *hot-path plane* is
:mod:`torchx_tpu.obs.hot` (import it by name; it needs jax and this package
stays jax-free): the serving engine's loop and the trainer's loop are
spanned with ``jax.profiler.TraceAnnotation`` and the compiled programs'
operations are named with ``jax.named_scope``. Those are recorded only while
a ``jax.profiler`` session runs, into the profiler's own ``.xplane.pb``, on
the clock of the device's ``XLA Ops`` line; with no session they cost under a
microsecond and write nothing. There is no switch of ours: the profiler's
session is the switch.

* host spans, engine thread, one tree per loop turn that did work:
  ``serve.admit`` over ``serve.admit.plan`` (admission is planning alone
  since prompts ride the decode steps: ``serve.admit.build``,
  ``serve.prefill.dispatch``, ``serve.prefill.fetch`` and
  ``serve.admit.commit`` are names of older traces, kept for their readers);
  ``serve.decode`` (``step``, ``active``, ``chunk_tokens``, ``chunk_width``,
  ``steps_overlapped``, ``tokens_discarded``) over
  ``serve.decode.prepare`` / ``.dispatch`` / ``.fetch`` / ``.commit``
  (``finished``); ``serve.idle``; ``serve.kv_import``; ``serve.cow_copy``
  inside ``.prepare`` where a shared tail block is copied. The engine keeps one
  decode step in flight, so one ``serve.decode`` turn spans two steps:
  ``.prepare`` and ``.dispatch`` build and enqueue step N+1 (``active`` is
  how many slots it steps; 0 when there is nothing left to enqueue), then
  ``.fetch`` and ``.commit`` wait for and hand out the tokens of step N,
  which ran on the device meanwhile. A turn with nothing in flight (the first
  step after an idle spell) has no ``.fetch`` / ``.commit``.
  **Which program a span enqueued is the runtime's to say**: it numbers its
  program runs (``run_id`` on the device's ``XLA Modules`` events and on the
  host's ``DoEnqueueProgram`` / ``CompleteCallbacks``) and records every
  compiled call on the thread that made it, inside the span that made it, so
  a reader ties each run to its turn and bounds the two clocks' offset from
  the trace alone; the spans carry nothing for that.
  **A prompt is counted where it is fed**: a ``serve.decode`` turn whose step
  carries a chunk of a prompt says so in ``chunk_tokens`` (its real tokens; 0
  on a pure decode step) beside ``chunk_width`` (the positions the program
  computes for them), and the call inside its ``.dispatch`` is
  ``_decode_chunk`` where a pure step's is ``_decode``. ``serve.admit`` carries
  ``admitted`` (requests given a slot), ``cached_tokens``, ``queue_depth``,
  ``kv_bytes_per_token`` and the blocks held after it.
  ``steps_overlapped`` (steps enqueued while the one before was unfetched)
  and ``tokens_discarded`` (slot-steps dropped at commit: the step after an
  EOS, a slot preempted with its step in flight) are the engine's running
  counts, also in ``ServeEngine.stats()``; ``stats()`` alone keeps the sums
  over all chunks (``chunk_steps``, ``prefill_tokens`` = the sum of
  ``chunk_tokens``, ``prefill_padded_tokens`` = ``chunk_steps`` x
  ``chunk_width``). They are per step, not per request: the per-request spans (``serve.generate``,
  ``serve.route``, ``serve.kv_transfer``) stay on the launcher plane;
* host spans, training: the profiler's step marker ``train`` around each
  iteration of ``train()``, ``train.log`` (holding ``train.fence``),
  ``train.checkpoint``; ``train.data_wait`` and ``train.h2d`` in the
  prefetcher;
* device scopes: ``embed``, ``layers`` (the scan over the layer stack: its
  own time is the slicing of each layer's weights; the paged pools ride its
  carry whole and are written and read where they lie), inside it
  ``norm``, ``attn`` (holding ``attn_kernel``, or
  ``append_kv`` and ``paged_attention`` with ``gather_kv`` / ``scores`` /
  ``values``), ``mlp`` or ``moe_router`` / ``moe_dispatch`` /
  ``moe_experts`` / ``moe_combine``, ``lm_head``, ``loss``, ``sample``,
  ``grad_clip``, ``optimizer``. The path is each operation's ``tf_op`` in
  the trace; autodiff wraps it (``transpose(jvp(attn))``) and
  ``rematted_computation`` marks recomputation.

To capture them: ``--profile-dir`` on the trainer; on ``generate_server``
``--profiler-port N`` (off by default) starts ``jax.profiler.start_server``,
and a live replica is then captured with the standard tools (TensorBoard's
profile tab, ``xprof``, or ``jax.profiler.ProfileOptions`` with
``python_tracer_level = 0`` to keep the Python tracer's tax off the loop
being measured); ``benchmark/run.py --trace 1`` reads them into per-layer
metrics (``benchmark/lib/host_spans.py``, ``benchmark/lib/scopes.py``).
"""

from torchx_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
)
from torchx_tpu.obs.sinks import (
    JsonlTraceHandler,
    flush_metrics,
    obs_root,
    session_dir,
    trace_path,
)
from torchx_tpu.obs.trace import (
    Span,
    current_span,
    current_trace_id,
    heartbeat,
    inject_env,
    span,
    tracing_enabled,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlTraceHandler",
    "MetricsRegistry",
    "REGISTRY",
    "Span",
    "current_span",
    "current_trace_id",
    "flush_metrics",
    "heartbeat",
    "inject_env",
    "obs_root",
    "session_dir",
    "span",
    "trace_path",
    "tracing_enabled",
]
