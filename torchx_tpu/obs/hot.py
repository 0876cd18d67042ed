"""Hot-path spans and device scopes: the profiler is the recorder.

The launcher's span model (:mod:`torchx_tpu.obs.trace`) times launches on the
wall clock and writes JSON; it is the wrong tool inside a loop that turns
every 40 ms. Spans of the serving engine's loop and of the trainer's loop are
``jax.profiler.TraceAnnotation`` instead: they cost under a microsecond when
no profiler session runs and record nothing, and while one runs (the
trainer's ``--profile-dir``, ``generate_server --profiler-port``, any
``jax.profiler.start_trace``) they land in the same ``.xplane.pb`` and on the
same clock as the device's ``XLA Modules`` and ``XLA Ops`` lines, so a device
idle gap can be named by the host phase that caused it. The runtime numbers
its program runs itself (``run_id``, on the device's ``XLA Modules`` events and
on the host's ``DoEnqueueProgram`` and ``CompleteCallbacks`` events) and records
every compiled call on the thread that made it, so a reader ties each run to
the span that holds its call and bounds the two clocks' offset from the trace
alone (``benchmark/lib/program_runs.py``): the spans carry nothing for that.
Operations inside a compiled program are named by ``jax.named_scope``: the
scope path is each operation's ``tf_op`` in the trace. There is no switch,
buffer or file here.

Readers and tests import the names below, not strings.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax

# -- host spans: serving engine loop (one line of /host:CPU) ----------------
#
# The engine keeps one decode step in flight, so one ``serve.decode`` turn
# spans two steps: ``.prepare`` and ``.dispatch`` build and enqueue step N+1,
# then ``.fetch`` and ``.commit`` wait for and hand out step N's tokens, which
# the device computed meanwhile. A turn with nothing in flight has no
# ``.fetch``/``.commit``; one with nothing left to enqueue no ``.dispatch``.
# Admission is planning alone (serve.admit over serve.admit.plan: prefix match, blocks, a slot); a prompt
# is then fed in chunks that ride the decode steps, so the step a turn enqueues may carry one, and the
# compiled call inside its .dispatch is then _decode_chunk where a pure step's is _decode.

# kv_blocks_full, kv_blocks_window: blocks the slots hold in the full and in the window pools (a decode
# turn: as its step is dispatched; an admission: after it); window_blocks_released: a running count.
# Where a slot's rows are not its tokens (EVA attention: serve/kv_pool.py::EvaTables; one pool, so
# kv_blocks_full is everything held) kv_blocks_window is the blocks of the slots' current windows and
# kv_blocks_pooled those of pooled rows, staging included; cache_rows_held the rows the slots hold in
# them (staged rows too) for cache_tokens_held tokens, cache_rows_read the rows the decode kernel of
# the step being dispatched reads (every stepping slot's cache coordinate + 1, a layer), and
# pooled_blocks_promoted a running count beside window_blocks_released
#
# serve.admit: admitted (requests given a slot), cached_tokens, queue_depth (after they left it),
# kv_bytes_per_token, kv_blocks_*. Until PR 40 an admission was a round with a program of its own, a
# serve.admit over all five children below with rows, rows_padded, width, tokens, slots_stalled, built:
# the names stay for the readers of such traces (benchmark/lib/host_spans.py, program_runs.py), the
# engine records serve.admit and serve.admit.plan alone
SERVE_ADMIT = "serve.admit"
SERVE_ADMIT_PLAN = "serve.admit.plan"
SERVE_ADMIT_BUILD = "serve.admit.build"
SERVE_PREFILL_DISPATCH = "serve.prefill.dispatch"
SERVE_PREFILL_FETCH = "serve.prefill.fetch"
SERVE_ADMIT_COMMIT = "serve.admit.commit"
# step, active (slots step N+1 has a token for), kv_blocks_*, chunk_tokens (real prompt tokens riding step N+1; 0 on a
# pure decode step) of chunk_width; running counts: steps_overlapped, tokens_discarded. ServeEngine.stats() keeps the
# sums over all chunks: chunk_steps, prefill_tokens (chunk_tokens), prefill_padded_tokens (chunk_steps x chunk_width)
SERVE_DECODE = "serve.decode"
SERVE_DECODE_PREPARE = "serve.decode.prepare"
SERVE_DECODE_DISPATCH = "serve.decode.dispatch"
SERVE_DECODE_FETCH = "serve.decode.fetch"
SERVE_DECODE_COMMIT = "serve.decode.commit"  # finished
SERVE_IDLE = "serve.idle"
SERVE_KV_IMPORT = "serve.kv_import"  # blocks, cache_len
# inside serve.decode.prepare, not one of its tiling children: a shared tail block copied before its slot
# writes to it (eager updates of the pool's leaves: several program runs, named by this span)
SERVE_COW_COPY = "serve.cow_copy"

#: parent -> the children that tile it, in order (serve.admit: in a trace from before PR 40; since then .plan alone)
SERVE_SPAN_TREE = {
    SERVE_ADMIT: (
        SERVE_ADMIT_PLAN,
        SERVE_ADMIT_BUILD,
        SERVE_PREFILL_DISPATCH,
        SERVE_PREFILL_FETCH,
        SERVE_ADMIT_COMMIT,
    ),
    SERVE_DECODE: (
        SERVE_DECODE_PREPARE,
        SERVE_DECODE_DISPATCH,
        SERVE_DECODE_FETCH,
        SERVE_DECODE_COMMIT,
    ),
}

# -- host spans: training ----------------------------------------------------

TRAIN_STEP = "train"  # StepTraceAnnotation, step_num
TRAIN_DATA_WAIT = "train.data_wait"
TRAIN_H2D = "train.h2d"
TRAIN_FENCE = "train.fence"
TRAIN_LOG = "train.log"
TRAIN_CHECKPOINT = "train.checkpoint"

# -- device scopes: ``with jax.named_scope(hot.ATTN): ...`` ---------------------

LAYERS = "layers"  # the scan over the layer stack; its own time is the slicing of each layer's weights
ATTN = "attn"  # projections, rope, the kernel call, output projection
ATTN_KERNEL = "attn_kernel"
ATTN_WINDOW = "attn_window"  # under attn, a stack of mixed kinds only: a sliding layer's attention
ATTN_FULL = "attn_full"  # ... and a full layer's, so that a kernel's time divides by kind
QK_NORM = "qk_norm"  # the per-head RMSNorm of q and k
MLP = "mlp"
NORM = "norm"
LM_HEAD = "lm_head"
LOSS = "loss"
EMBED = "embed"
MOE_ROUTER = "moe_router"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"
MOE_SORT = "moe_sort"  # under moe_dispatch, dropless path: sort by expert, count each group, gather rows
MOE_SHARED = "moe_shared"  # the shared expert beside the routed ones, its sigmoid gate included where it has one
MLA_LATENT = "mla_latent"  # down-projection, latent norm, rotary key
MLA_ABSORB = "mla_absorb"  # decode: W_kvb folded into the query and out of the result
MLA_Q_LATENT = "mla_q_latent"  # under attn: the query's own down-projection and its norm (q_lora_rank)
HC_PRE = "hc_pre"  # hyper-connections, a sublayer: the stream's norm, its projection, the gates, the read-in
HC_SINKHORN = "hc_sinkhorn"  # ... the write-back matrix made doubly stochastic
HC_POST = "hc_post"  # ... the write-back: the streams mixed, the sublayer's output added
HC_HEAD = "hc_head"  # the streams summed ahead of the final norm
SSM = "ssm"  # the whole state-space mixer of a layer, beside attn
SSM_PROJ = "ssm_proj"  # under ssm: its input and output projections
SSM_CONV = "ssm_conv"  # ... the depthwise causal convolution and its activation
SSM_STEP = "ssm_step"  # ... one position a row: the rows' state read, moved on, read out and written back
SSM_SCAN = "ssm_scan"  # ... many positions a row in chunks: the same for a chunk of a prompt or a whole sequence
SSM_GATE_NORM = "ssm_gate_norm"  # ... the gate and the grouped norm ahead of the output projection
GDN = "gdn"  # the whole Gated DeltaNet mixer of a linear layer, in attention's place
GDN_PROJ = "gdn_proj"  # under gdn: its two input projections and its output projection
GDN_CONV = "gdn_conv"  # ... the depthwise causal convolution and its activation
GDN_STEP = "gdn_step"  # ... one position a row: the rows' state decayed, corrected by the delta rule, read out, written back
GDN_CHUNK = "gdn_chunk"  # ... many positions a row: the same for a chunk of a prompt or a whole sequence, solved in sub-chunks
GDN_GATE_NORM = "gdn_gate_norm"  # ... the norm over each head's read-out and the gate behind it
ATTN_GATE = "attn_gate"  # under attn: the heads' output times the sigmoid of the gate wq made beside the query
APPEND_LATENT = "append_latent"
PAGED_ATTENTION = "paged_attention"
GATHER_KV = "gather_kv"
SCORES = "scores"
VALUES = "values"
APPEND_KV = "append_kv"
EVA_POOL = "eva_pool"  # under attn, beside paged_attention and append_kv: the blocks a step filled read, pooled, one row each written
SAMPLE = "sample"
GRAD_CLIP = "grad_clip"
OPTIMIZER = "optimizer"

DEVICE_SCOPES = (
    LAYERS, ATTN, ATTN_KERNEL, MLP, NORM, LM_HEAD, LOSS, EMBED, MOE_ROUTER, MOE_DISPATCH,
    MOE_EXPERTS, MOE_COMBINE, PAGED_ATTENTION, GATHER_KV, SCORES, VALUES,
    APPEND_KV, SAMPLE, GRAD_CLIP, OPTIMIZER, MOE_SORT, MOE_SHARED, MLA_LATENT, MLA_ABSORB,
    APPEND_LATENT, ATTN_WINDOW, ATTN_FULL, QK_NORM, MLA_Q_LATENT, HC_PRE, HC_SINKHORN, HC_POST, HC_HEAD,
    SSM, SSM_PROJ, SSM_CONV, SSM_STEP, SSM_SCAN, SSM_GATE_NORM, EVA_POOL,
    GDN, GDN_PROJ, GDN_CONV, GDN_STEP, GDN_CHUNK, GDN_GATE_NORM, ATTN_GATE,
)  # fmt: skip


def attn_kind_scope(cfg: Any, layer: dict):  # noqa: ANN201
    """The scope of one layer's attention by kind, where the stack mixes kinds
    (``cfg.layer_types``): ``attn_window`` or ``attn_full``. A stack of one
    kind gets no further level: its paths stay as they were."""
    if not getattr(cfg, "layer_types", ()):
        return contextlib.nullcontext()
    return jax.named_scope(ATTN_WINDOW if layer.get("attn_kind") == "window" else ATTN_FULL)


def span(name: str, **attrs: Any) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` on the calling thread's line of the
    profiler's trace; ``attrs`` become the event's stats. Attributes known
    only later go through the returned object's ``set_metadata(**attrs)``
    before the span closes."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def step_span(step_num: int) -> jax.profiler.StepTraceAnnotation:
    """One training step, as the profiler's step marker (its tools group
    device work by it)."""
    return jax.profiler.StepTraceAnnotation(TRAIN_STEP, step_num=step_num)

