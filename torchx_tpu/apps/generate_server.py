"""Generation server: HTTP inference over the KV-cache decode loop.

The serving half the reference delegates to TorchServe, rebuilt
TPU-native (JetStream-style, minimal): load a model family config (+
optional orbax checkpoint, optional int8 weight-only quantization), jit
the prefill+decode loop once per shape bucket, and serve token-in/
token-out generation over plain HTTP — no framework dependencies, so the
same binary runs under every scheduler backend.

    python -m torchx_tpu.apps.generate_server \
        --config llama_tiny [--ckpt-dir DIR] [--int8] [--port 8000]

API (JSON):
    GET  /healthz            -> {"status": "ok", "model": ..., "requests": N,
                                "platform": ..., "device_kind": ...,
                                "device_count": N, "attention": ...}
                                (503 {"status": "draining"} during SIGTERM
                                grace; 503 {"status": "failed"} once the
                                engine loop has died)
    GET  /metricz            -> tpx_* metrics, Prometheus text format
    POST /v1/generate        {"tokens": [[...]], "max_new_tokens": 16,
                              "temperature": 0.0}
                          or {"text": "...", ...} (byte-level codec, the
                              same tokenization datapreproc defaults to)
                          -> {"tokens": [[...]]} / {"text": [...]}
    POST /v1/kv              (decode role) serialized KvPayload handoff
                              from a prefill replica -> the decode
                              completion; 503 while draining so the
                              sender requeues elsewhere

Disaggregated serving (``--serve-role prefill|decode``): prefill
replicas take /v1/generate traffic, run the cache-aware chunked prefill
(shared prompt prefixes hit the radix prefix cache and skip
recomputation), then stream the computed KV blocks to a decode replica
over ``--kv-transfer`` and relay its completion. Decode replicas accept
handoffs on /v1/kv (or a file: spool) and batch pure decode steps.

Two serving engines, selected by ``--engine``:

* ``continuous`` (default): the :mod:`torchx_tpu.serve.engine`
  continuous-batching loop — a fixed ``--max-batch`` slot array decoding
  over a paged KV cache, with requests admitted into free slots between
  steps and completions returned the step they finish. Arbitrary prompt
  lengths, temperatures, and seeds share one device step.
* ``coalesce``: the legacy batch-to-completion batcher — compatible
  sequences (same prompt length / max_new / temperature) from concurrent
  clients merge into one device batch within a few-ms window, and each
  batch decodes to completion before the next dispatch. Kept as the
  serving-bench baseline and for bit-exact parity with
  :func:`torchx_tpu.models.generate.generate`.

On SIGTERM the server drains instead of dying mid-request: admission
stops, ``/healthz`` flips to 503 (so routers and the serve pool stop
sending traffic), in-flight slots decode to completion, then the process
exits 0. If the engine loop died (a device step raised), ``/healthz`` is
503 with the reason, requests are refused, and the process exits 1.
"""

from __future__ import annotations

import argparse
import codecs
import dataclasses
import json
import logging
import os
import queue
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)


class ServiceDraining(RuntimeError):
    """Raised for requests arriving during the SIGTERM drain window; the
    HTTP layer maps it to 503 so load balancers retry elsewhere."""


@dataclasses.dataclass
class _Pending:
    """One sequence awaiting decode, owned by a handler thread until the
    batcher thread fills ``result`` (or ``error``) and sets ``done``."""

    tokens: list[int]
    key: tuple  # (prompt_len, max_new_tokens, temperature) — seed is NOT
    # part of the key: rows carry their own seed and sample from their own
    # folded stream, so differently-seeded requests share a device batch
    seed: int = 0
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    result: Optional[list[int]] = None
    error: Optional[Exception] = None
    # serving-latency telemetry (bench_serving.py percentiles): when this
    # sequence entered the queue, when its device batch dispatched, and
    # when the batch finished — queue_ms = coalescing/backlog wait,
    # total_ms = request-observed latency
    t_enqueue: float = 0.0
    t_dispatch: float = 0.0
    t_done: float = 0.0


class GenerateService:
    """Model + serving engine, shared by all handler threads.

    ``engine="continuous"`` (default) runs the
    :class:`torchx_tpu.serve.engine.ServeEngine` continuous-batching loop:
    ``max_batch`` decode slots over a paged KV pool, admission/eviction
    every step, any mix of prompt lengths / temperatures / seeds in one
    compiled step.

    ``engine="coalesce"`` keeps the legacy batch-to-completion batcher:
    handler threads enqueue sequences and a single batcher thread drains
    the queue in a short window, merging compatible sequences (same prompt
    length / max_new / temperature) into ONE device batch that decodes to
    completion before the next dispatch.

    Seed semantics (both engines): every sequence samples from its own
    per-row PRNG stream derived from its request seed, so a (prompt, seed,
    temperature) triple reproduces the same tokens regardless of what
    other traffic it batched with. In coalesce mode a lone request is
    token-identical to :func:`torchx_tpu.models.generate.generate` at the
    same seed (per-row keys stack to exactly the single-key draw).
    """

    def __init__(
        self,
        config: str,
        ckpt_dir: Optional[str] = None,
        int8: bool = False,
        seed: int = 0,
        batch_window_ms: float = 3.0,
        max_batch: int = 16,
        engine: str = "continuous",
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        serve_role: str = "unified",
        kv_transfer: Optional[str] = None,
        enable_prefix_cache: bool = True,
        prefix_cache_reserve: float = 0.0,
    ) -> None:
        if engine not in ("continuous", "coalesce"):
            raise ValueError(
                f"unknown engine {engine!r}; have 'continuous', 'coalesce'"
            )
        if serve_role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"unknown serve role {serve_role!r}; have 'unified',"
                f" 'prefill', 'decode'"
            )
        if serve_role != "unified" and engine != "continuous":
            raise ValueError(
                f"serve role {serve_role!r} requires the continuous engine"
                f" (got {engine!r})"
            )
        if serve_role == "prefill" and not kv_transfer:
            raise ValueError(
                "prefill role needs a --kv-transfer spec (decode targets)"
            )
        from torchx_tpu.models import all_configs

        configs = all_configs()
        if config not in configs:
            raise ValueError(f"unknown config {config!r}; have {sorted(configs)}")
        self.cfg = configs[config]()
        self.name = config
        from torchx_tpu.models import llama

        if ckpt_dir:
            from torchx_tpu.parallel.checkpoint import Checkpointer

            abstract = llama.init_params(self.cfg, jax.random.PRNGKey(seed))
            ckpt = Checkpointer(ckpt_dir)
            step, params = ckpt.restore_latest(abstract)
            ckpt.close()
            if params is None:
                raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
            self.params = params
            self.ckpt_step = step
        else:
            self.params = llama.init_params(self.cfg, jax.random.PRNGKey(seed))
            self.ckpt_step = None
        if int8:
            from torchx_tpu.ops.quant import quantize_params

            self.params = quantize_params(self.params)
        self.int8 = int8
        from torchx_tpu.parallel.mesh import device_info
        from torchx_tpu.settings import ENV_TPU_VISIBLE_CHIPS

        # where this replica runs, as jax reports it (/healthz publishes
        # it); visible_chips tells co-located one-chip replicas apart —
        # each sees its own chip as device 0
        self.device = {
            **device_info(),
            "visible_chips": os.environ.get(ENV_TPU_VISIBLE_CHIPS),
        }
        self._cache_lock = threading.Lock()  # handlers run concurrently
        self._jit_cache: dict[tuple, Any] = {}
        self.requests = 0
        self.batches = 0  # device dispatches (< enqueued seqs when coalesced)
        self.batched_sequences = 0
        self.batch_window_s = batch_window_ms / 1000.0
        self.max_batch = max_batch
        self.engine_mode = engine
        self.serve_role = serve_role
        self.draining = False
        self._closed = False
        self._count_lock = threading.Lock()
        self._engine = None
        # prefill role: KV handoffs in flight to decode replicas — the
        # disaggregated twin of the engine's _admitting list; drain()
        # must wait these out or a mid-transfer SIGTERM drops the request
        self._transferring = 0
        self._transfer_done = threading.Condition()
        self._transfer = None
        self._spool_stop: Optional[threading.Event] = None
        self._spool_thread: Optional[threading.Thread] = None
        if engine == "continuous":
            from torchx_tpu.serve.engine import ServeEngine

            self._engine = ServeEngine(
                self.params,
                self.cfg,
                max_slots=max_batch,
                block_size=block_size,
                num_blocks=num_blocks,
                enable_prefix_cache=enable_prefix_cache,
                prefix_cache_reserve=prefix_cache_reserve,
            ).start()
            if serve_role == "prefill":
                from torchx_tpu.serve.kv_transfer import (
                    TransferConfig,
                    make_transfer,
                )

                self._transfer = make_transfer(
                    TransferConfig.from_spec(kv_transfer)
                )
            elif serve_role == "decode" and kv_transfer:
                # a decode role given a file: spec pumps the spool dir
                # itself (HTTP decode targets are served by /v1/kv)
                from torchx_tpu.serve import kv_transfer as kvt

                tcfg = kvt.TransferConfig.from_spec(kv_transfer)
                if tcfg.mode == "file":
                    self._spool_stop = threading.Event()
                    self._spool_thread = threading.Thread(
                        target=kvt.serve_spool,
                        args=(
                            tcfg.endpoints[0],
                            self.handle_kv_payload,
                            self._spool_stop,
                        ),
                        name="tpx-kv-spool",
                        daemon=True,
                    )
                    self._spool_thread.start()
            return
        self._submit_lock = threading.Lock()  # orders enqueue vs close
        self._queue: "queue.Queue[Optional[_Pending]]" = queue.Queue()
        self._batcher = threading.Thread(
            target=self._batch_loop, name="tpx-batcher", daemon=True
        )
        self._batcher.start()

    @property
    def failed(self) -> Optional[str]:
        """Why the engine loop died, else None."""
        return self._engine.failed if self._engine is not None else None

    def close(self) -> None:
        """Stop the serving engine (idempotent). Work enqueued before close
        drains to completion; work racing close fails fast — never hangs."""
        if self._engine is not None:
            self._closed = True
            if self._spool_stop is not None:
                self._spool_stop.set()
            self._engine.drain(timeout=60)
            self._wait_transfers(timeout=60)
            self._engine.stop()
            if self._spool_thread is not None:
                self._spool_thread.join(timeout=5)
            return
        with self._submit_lock:
            # under the same lock generate() enqueues with, so every put
            # either lands before the sentinel (drained by the batcher) or
            # observes _closed and raises
            self._closed = True
            self._queue.put(None)
        self._batcher.join(timeout=60)
        if self._batcher.is_alive():
            # a dispatch (e.g. cold compile) outlived the join budget; the
            # loop will finish it, drain its backlog, and exit on the
            # sentinel — nothing is stranded, we just stop waiting
            logger.warning("batcher still draining at close(); detaching")

    def drain(self, grace_s: float = 30.0) -> bool:
        """SIGTERM grace: stop admitting (:attr:`draining` flips healthz to
        503 and fails new requests fast), finish everything in flight.
        True when fully drained within ``grace_s``."""
        self.draining = True
        if self._engine is not None:
            if self._spool_stop is not None:
                self._spool_stop.set()
            t0 = time.monotonic()
            ok = self._engine.drain(timeout=grace_s)
            # prefill role: engine-drained handoffs may still be streaming
            # to decode replicas; they count as in-flight until the reply
            ok = (
                self._wait_transfers(
                    timeout=max(0.0, grace_s - (time.monotonic() - t0))
                )
                and ok
            )
            return ok
        deadline = time.monotonic() + grace_s
        with self._submit_lock:
            self._closed = True
            self._queue.put(None)
        self._batcher.join(timeout=max(0.0, deadline - time.monotonic()))
        return not self._batcher.is_alive()

    def _wait_transfers(self, timeout: float) -> bool:
        """Block until every in-flight KV handoff has its decode reply
        (prefill role; trivially True elsewhere)."""
        deadline = time.monotonic() + timeout
        with self._transfer_done:
            while self._transferring > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._transfer_done.wait(remaining)
        return True

    def handle_kv_payload(self, payload: Any) -> dict:
        """Decode role: admit one prefilled handoff and decode it out.

        Raises :class:`~torchx_tpu.serve.kv_transfer.TransferRejected`
        while draining so the prefill side requeues to another decode
        replica — the drain-race contract."""
        from torchx_tpu.serve.engine import serve_kv_payload
        from torchx_tpu.serve.kv_transfer import TransferRejected

        if self.serve_role != "decode":
            raise TransferRejected(
                f"replica role is {self.serve_role!r}, not decode"
            )
        if self.draining or self._closed:
            raise TransferRejected("decode replica draining; requeue")
        with self._count_lock:
            self.requests += 1
        # decode inside the handoff's originating trace, so the request's
        # stitched timeline covers router -> prefill -> transfer -> decode
        from torchx_tpu.serve.kv_transfer import payload_span

        with payload_span(payload, "serve.decode"):
            return serve_kv_payload(self._engine, payload)

    # -- batcher thread ----------------------------------------------------

    def _batch_loop(self) -> None:
        """Single dispatcher: groups compatible pendings, keeps a local
        backlog for incompatible ones so the OLDEST deferred key becomes
        the next group head (no starvation under a sustained stream of one
        key), and on shutdown drains queue + backlog before exiting."""
        from collections import deque

        backlog: "deque[_Pending]" = deque()
        shutdown = False
        while True:
            if backlog:
                item = backlog.popleft()
            elif shutdown:
                return
            else:
                item = self._queue.get()
                if item is None:
                    return
            group = [item]
            deadline = time.monotonic() + self.batch_window_s
            # adopt compatible backlog items first (they are oldest)
            for p in list(backlog):
                if len(group) >= self.max_batch:
                    break
                if p.key == item.key:
                    backlog.remove(p)
                    group.append(p)
            while not shutdown and len(group) < self.max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is None:
                    shutdown = True  # drain backlog, then exit above
                    break
                if nxt.key == item.key:
                    group.append(nxt)
                else:
                    backlog.append(nxt)
            self._dispatch(group)

    def _dispatch(self, group: list[_Pending]) -> None:
        _, max_new, temperature = group[0].key
        now = time.monotonic()
        for p in group:
            p.t_dispatch = now
        try:
            fn = self._decode_fn(max_new, temperature)
            rows = [p.tokens for p in group]
            # pad the group to a power-of-2 bucket (row 0 repeated): group
            # size depends on request-arrival jitter, and each distinct
            # batch shape is a fresh XLA compile — bucketing caps the jit
            # cache at log2(max_batch) shapes per (max_new, temperature)
            # instead of one per observed group size
            bucket = 1
            while bucket < len(rows):
                bucket *= 2
            # never exceed the operator's ceiling (max_batch bounds
            # KV-cache HBM): a non-power-of-2 max_batch clamps here
            bucket = min(bucket, self.max_batch)
            rows = rows + [rows[0]] * (bucket - len(rows))
            batch = jnp.asarray(rows, dtype=jnp.int32)
            if temperature <= 0:
                rng = jax.random.PRNGKey(0)  # greedy never reads it
            else:
                # one PRNG stream per row, from each request's own seed —
                # differently-seeded requests coalesce, and each row draws
                # exactly what a solo call with its seed would
                seeds = [p.seed for p in group]
                seeds += [group[0].seed] * (bucket - len(group))
                rng = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
            out = jax.device_get(fn(self.params, batch, rng))
            self.batches += 1
            self.batched_sequences += len(group)
            for row, p in enumerate(group):
                p.result = [int(x) for x in out[row]]
        except Exception as e:  # noqa: BLE001 - surfaced per-request
            for p in group:
                p.error = e
        finally:
            now = time.monotonic()
            for p in group:
                p.t_done = now
                p.done.set()

    _JIT_CACHE_MAX = 32

    def _decode_fn(self, max_new_tokens: int, temperature: float):
        """One jitted generate per (max_new, temperature); jax's own cache
        handles distinct (batch, prompt_len) shapes under each entry.

        Request-supplied floats key the cache, so temperature is rounded
        (1e-3 is far below sampling noise) and the cache is FIFO-bounded —
        adversarial parameter sweeps cannot grow compile state without
        bound."""
        from torchx_tpu.models import generate as gen

        key = (max_new_tokens, round(temperature, 3))
        with self._cache_lock:
            fn = self._jit_cache.get(key)
            if fn is None:
                if len(self._jit_cache) >= self._JIT_CACHE_MAX:
                    self._jit_cache.pop(next(iter(self._jit_cache)))
                fn = jax.jit(
                    lambda p, b, rng: gen.generate(
                        p,
                        b,
                        self.cfg,
                        max_new_tokens=max_new_tokens,
                        temperature=key[1],
                        rng=rng,
                    )
                )
                self._jit_cache[key] = fn
            return fn

    def generate(
        self,
        tokens: list[list[int]],
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
    ) -> list[list[int]]:
        return self.generate_timed(
            tokens, max_new_tokens, temperature=temperature, seed=seed,
            eos_id=eos_id,
        )[0]

    def generate_timed(
        self,
        tokens: list[list[int]],
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
    ) -> tuple[list[list[int]], dict]:
        """:meth:`generate` plus per-request latency telemetry:
        ``{"queue_ms", "total_ms", "ttft_ms"}`` — the admission/backlog
        wait, the end-to-end latency of the request's slowest sequence,
        and the time to its first decoded token. The HTTP layer attaches
        it to responses as ``timing`` so serving benchmarks can report
        percentiles without server-side scraping. ``eos_id`` stops a
        sequence early on that token (continuous engine only; the
        coalescing baseline always decodes the full budget)."""
        if not tokens or any(not t for t in tokens):
            raise ValueError("tokens must be non-empty sequences")
        longest = max(len(t) for t in tokens)
        if longest + max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt length {longest} + {max_new_tokens} new tokens"
                f" exceeds max_seq {self.cfg.max_seq}"
            )
        if self.draining:
            raise ServiceDraining("server is draining; retry elsewhere")
        if self._closed:
            raise RuntimeError("generate service is closed")
        with self._count_lock:
            self.requests += 1
        if self._engine is not None:
            return self._generate_engine(
                tokens, max_new_tokens, temperature, seed, eos_id
            )
        # one _Pending per sequence, keyed by EXACT length (padding would
        # pollute the causal context — correctness over cleverness; one
        # compile per distinct (length, max_new) pair, cached by jit). The
        # batcher thread merges compatible sequences ACROSS requests into
        # single device batches.
        t_enqueue = time.monotonic()
        pendings = [
            _Pending(
                tokens=list(t),
                key=(len(t), max_new_tokens, round(temperature, 3)),
                seed=seed,
                t_enqueue=t_enqueue,
            )
            for t in tokens
        ]
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("generate service is closed")
            for p in pendings:
                self._queue.put(p)
        for p in pendings:
            p.done.wait()
        errors = [p.error for p in pendings if p.error is not None]
        if errors:
            raise errors[0]
        # request-level timing: the slowest sequence bounds the response.
        # batch-to-completion delivers all tokens at once, so the first
        # token arrives when the batch does: ttft == total
        total_ms = round(
            max((p.t_done - p.t_enqueue) for p in pendings) * 1e3, 2
        )
        timing = {
            "queue_ms": round(
                max((p.t_dispatch - p.t_enqueue) for p in pendings) * 1e3, 2
            ),
            "total_ms": total_ms,
            "ttft_ms": total_ms,
        }
        return [p.result for p in pendings], timing

    def _generate_engine(
        self,
        tokens: list[list[int]],
        max_new_tokens: int,
        temperature: float,
        seed: int,
        eos_id: Optional[int],
    ) -> tuple[list[list[int]], dict]:
        from torchx_tpu.serve.engine import EngineStopped, ServeRequest

        if self.serve_role == "prefill":
            return self._generate_disagg(
                tokens, max_new_tokens, temperature, seed, eos_id
            )
        reqs = [
            ServeRequest(
                prompt=list(t),
                max_new_tokens=max_new_tokens,
                temperature=round(temperature, 3),
                seed=seed,
                eos_id=eos_id,
            )
            for t in tokens
        ]
        try:
            for r in reqs:
                self._engine.submit(r)
        except EngineStopped as e:
            raise ServiceDraining(str(e)) from e
        for r in reqs:
            r.wait()
        errors = [r.error for r in reqs if r.error is not None]
        if errors:
            raise RuntimeError(errors[0])
        with self._count_lock:
            self.batches = self._engine.steps
            self.batched_sequences += len(reqs)
        timing = {
            "queue_ms": round(max(r.ttft_s for r in reqs) * 1e3, 2),
            "total_ms": round(
                max(r.t_done - r.t_enqueue for r in reqs) * 1e3, 2
            ),
            "ttft_ms": round(max(r.ttft_s for r in reqs) * 1e3, 2),
        }
        return [r.tokens for r in reqs], timing

    def _generate_disagg(
        self,
        tokens: list[list[int]],
        max_new_tokens: int,
        temperature: float,
        seed: int,
        eos_id: Optional[int],
    ) -> tuple[list[list[int]], dict]:
        """Prefill role: run the cache-aware prefill locally, then stream
        each computed KV payload to a decode replica and relay its
        completion. TTFT is the locally-sampled first token; the decode
        gang owns the rest of the latency."""
        from torchx_tpu.serve.engine import EngineStopped, ServeRequest

        reqs = [
            ServeRequest(
                prompt=list(t),
                max_new_tokens=max_new_tokens,
                temperature=round(temperature, 3),
                seed=seed,
                eos_id=eos_id,
                prefill_only=True,
            )
            for t in tokens
        ]
        t0 = time.monotonic()
        # the handoff window counts as in-flight for drain(): a SIGTERM
        # between prefill completion and the decode reply must not drop
        # the request (the disaggregated twin of _admitting)
        with self._transfer_done:
            self._transferring += len(reqs)
        try:
            try:
                for r in reqs:
                    self._engine.submit(r)
            except EngineStopped as e:
                raise ServiceDraining(str(e)) from e
            outs: list[list[int]] = []
            ttft = 0.0
            for r in reqs:
                r.wait()
                if r.error is not None:
                    raise RuntimeError(r.error)
                ttft = max(ttft, r.ttft_s)
                if r.handoff is None:  # finished at the first token
                    outs.append(r.tokens)
                    continue
                result = self._transfer.send(r.handoff)
                # transfer replies carry generated tokens only; restore
                # the prompt+generated shape the unified path returns
                outs.append(list(r.prompt) + [int(x) for x in result["tokens"]])
        finally:
            with self._transfer_done:
                self._transferring -= len(reqs)
                self._transfer_done.notify_all()
        with self._count_lock:
            self.batches = self._engine.steps
            self.batched_sequences += len(reqs)
        total_ms = round((time.monotonic() - t0) * 1e3, 2)
        timing = {
            "queue_ms": round(ttft * 1e3, 2),
            "total_ms": total_ms,
            "ttft_ms": round(ttft * 1e3, 2),
        }
        return outs, timing

    def generate_stream(
        self,
        tokens: list[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        chunk: int = 8,
    ):
        """Yield lists of new token ids as they decode (single sequence).

        Streaming bypasses the batcher — a stream holds the device for its
        whole decode, so it trades coalescing for time-to-first-token;
        token-identical to the batch path at the same seed."""
        if self.draining:
            raise ServiceDraining("server is draining; retry elsewhere")
        if self._closed:
            raise RuntimeError("generate service is closed")
        if not tokens:
            raise ValueError("tokens must be a non-empty sequence")
        if len(tokens) + max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt length {len(tokens)} + {max_new_tokens} new tokens"
                f" exceeds max_seq {self.cfg.max_seq}"
            )
        from torchx_tpu.models import generate as gen

        with self._count_lock:
            self.requests += 1
        batch = jnp.asarray([tokens], dtype=jnp.int32)
        # gen.generate_stream ALSO validates eagerly (chunk/max_new/max_seq)
        # before returning its generator, so every argument error surfaces
        # here — before the caller commits an HTTP status line
        it = gen.generate_stream(
            self.params,
            batch,
            self.cfg,
            max_new_tokens=max_new_tokens,
            temperature=round(temperature, 3),
            rng=jax.random.PRNGKey(seed),
            chunk=chunk,
        )

        def rows():
            for piece in it:
                yield [int(x) for x in piece[0]]

        return rows()


def _make_handler(service: GenerateService):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt: str, *args: Any) -> None:  # quiet
            pass

        def _reply(self, code: int, payload: dict) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self) -> None:  # noqa: N802
            if self.path == "/healthz":
                from torchx_tpu.ops.attention import traced

                failed = service.failed
                body = {
                    "status": (
                        "failed"
                        if failed
                        else "draining" if service.draining else "ok"
                    ),
                    "model": service.name,
                    **service.device,
                    # what the compiled steps lowered to so far ("" until
                    # the first request traces one)
                    "attention": traced("attention"),
                    "kernels": service.cfg.kernels,
                    "engine": service.engine_mode,
                    "serve_role": service.serve_role,
                    "int8": service.int8,
                    "ckpt_step": service.ckpt_step,
                    "requests": service.requests,
                    "batches": service.batches,
                    "batched_sequences": service.batched_sequences,
                }
                if service._engine is not None:
                    body.update(service._engine.stats())
                    # cache-aware routing inputs: what this replica holds
                    body["block_size"] = service._engine.block_size
                    body["prefix_summary"] = service._engine.prefix_summary()
                # a draining replica — and one whose engine died — must
                # fail its health check so routers and the serve pool stop
                # sending it traffic
                self._reply(503 if failed or service.draining else 200, body)
            elif self.path == "/metricz":
                from torchx_tpu.obs.metrics import REGISTRY

                text = REGISTRY.render().encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(text)))
                self.end_headers()
                self.wfile.write(text)
            else:
                self._reply(404, {"error": f"unknown path {self.path}"})

        def _handle_kv(self) -> None:
            """Decode-role KV handoff intake (``HttpTransfer`` sender):
            octet-stream payload in, decode completion out; 503 while
            draining so the prefill side requeues elsewhere."""
            from torchx_tpu.serve.kv_transfer import KvPayload, TransferRejected

            try:
                n = int(self.headers.get("Content-Length", 0))
                payload = KvPayload.from_bytes(self.rfile.read(n))
                self._reply(200, service.handle_kv_payload(payload))
            except TransferRejected as e:
                self._reply(503, {"error": str(e)})
            except (KeyError, ValueError, TypeError) as e:
                self._reply(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 - surface, don't kill the server
                self._reply(500, {"error": f"{type(e).__name__}: {e}"})

        def _stream(self, tokens: list[int], req: dict, text_mode: bool) -> None:
            """JSONL streaming response (one line per decoded chunk,
            terminated by {\"done\": true}); connection closes at the end.

            The iterator is created BEFORE the 200 goes out — validation
            errors still surface as a clean 400. Once streaming has begun
            no status line may be written; mid-stream failures just end
            the stream (the missing done marker tells the client)."""
            it = service.generate_stream(
                tokens,
                max_new_tokens=int(req.get("max_new_tokens", 16)),
                temperature=float(req.get("temperature", 0.0)),
                seed=int(req.get("seed", 0)),
                # clamp: chunk < 1 would raise, huge chunks defeat streaming
                chunk=max(1, min(int(req.get("stream_chunk", 8)), 64)),
            )
            self._streamed = True  # no _reply may run after this point
            self.send_response(200)
            self.send_header("Content-Type", "application/jsonl")
            self.send_header("Connection", "close")
            self.end_headers()
            # multibyte UTF-8 sequences can split across chunk boundaries;
            # an incremental decoder carries the partial bytes over
            decoder = codecs.getincrementaldecoder("utf-8")("replace")
            try:
                for piece in it:
                    if text_mode:
                        payload = {
                            "text_delta": decoder.decode(
                                bytes(b for b in piece if 0 <= b < 256)
                            )
                        }
                    else:
                        payload = {"tokens": piece}
                    self.wfile.write(json.dumps(payload).encode() + b"\n")
                    self.wfile.flush()
                if text_mode:
                    tail = decoder.decode(b"", final=True)
                    if tail:
                        self.wfile.write(
                            json.dumps({"text_delta": tail}).encode() + b"\n"
                        )
                self.wfile.write(b'{"done": true}\n')
            except (BrokenPipeError, ConnectionResetError):
                pass  # client went away mid-stream; nothing to reply to

        def do_POST(self) -> None:  # noqa: N802
            if self.path == "/v1/kv":
                self._handle_kv()
                return
            if self.path != "/v1/generate":
                self._reply(404, {"error": f"unknown path {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                text_mode = "text" in req and "tokens" not in req
                if text_mode:
                    texts = req["text"]
                    if isinstance(texts, str):
                        texts = [texts]
                    tokens = [list(t.encode("utf-8")) for t in texts]
                else:
                    tokens = req["tokens"]
                if req.get("stream"):
                    if len(tokens) != 1:
                        self._reply(
                            400,
                            {"error": "stream mode takes exactly one sequence"},
                        )
                        return
                    self._stream(tokens[0], req, text_mode)
                    return
                eos = req.get("eos_id")
                # adopt the router's trace context (HTTP headers): the
                # replica's span — and, on a prefill role, the KV
                # transfer it triggers — join the request's one trace
                from torchx_tpu.obs import trace as obs_trace

                tid, sid = obs_trace.extract_headers(self.headers)
                with obs_trace.trace_context(tid, sid):
                    with obs_trace.span(
                        f"serve.{service.serve_role}", sequences=len(tokens)
                    ):
                        out, timing = service.generate_timed(
                            tokens,
                            max_new_tokens=int(req.get("max_new_tokens", 16)),
                            temperature=float(req.get("temperature", 0.0)),
                            seed=int(req.get("seed", 0)),
                            eos_id=None if eos is None else int(eos),
                        )
                if text_mode:
                    self._reply(
                        200,
                        {
                            "text": [
                                bytes(
                                    b for b in seq if 0 <= b < 256
                                ).decode("utf-8", errors="replace")
                                for seq in out
                            ],
                            "timing": timing,
                        },
                    )
                else:
                    self._reply(200, {"tokens": out, "timing": timing})
            except ServiceDraining as e:
                self._reply(503, {"error": str(e)})
            except (KeyError, ValueError, TypeError) as e:
                if getattr(self, "_streamed", False):
                    logger.warning("stream aborted mid-flight: %s", e)
                else:
                    self._reply(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 - surface, don't kill the server
                if getattr(self, "_streamed", False):
                    logger.error(
                        "stream aborted mid-flight: %s: %s", type(e).__name__, e
                    )
                else:
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})

    return Handler


def serve(
    config: str,
    port: int = 8000,
    ckpt_dir: Optional[str] = None,
    int8: bool = False,
    ready_event: Optional[threading.Event] = None,
    batch_window_ms: float = 3.0,
    max_batch: int = 16,
    engine: str = "continuous",
    block_size: int = 16,
    num_blocks: Optional[int] = None,
    serve_role: str = "unified",
    kv_transfer: Optional[str] = None,
    enable_prefix_cache: bool = True,
    prefix_cache_reserve: float = 0.0,
) -> ThreadingHTTPServer:
    service = GenerateService(
        config,
        ckpt_dir=ckpt_dir,
        int8=int8,
        batch_window_ms=batch_window_ms,
        max_batch=max_batch,
        engine=engine,
        block_size=block_size,
        num_blocks=num_blocks,
        serve_role=serve_role,
        kv_transfer=kv_transfer,
        enable_prefix_cache=enable_prefix_cache,
        prefix_cache_reserve=prefix_cache_reserve,
    )
    server = ThreadingHTTPServer(("", port), _make_handler(service))
    server.service = service  # for tests / shutdown hooks
    if ready_event is not None:
        ready_event.set()
    return server


def make_drain(
    server: ThreadingHTTPServer,
    service: GenerateService,
    grace_s: float = 30.0,
) -> Any:
    """The SIGTERM drain sequence, as a callable (testable without
    signals): stop admission + fail ``/healthz``, let in-flight slots
    decode out, then shut the HTTP loop down so :func:`main` returns and
    the process exits 0 inside the preemption notice window."""

    def _drain() -> None:
        logger.warning("SIGTERM: draining (grace %.0fs)", grace_s)
        ok = service.drain(grace_s)
        if not ok:
            logger.warning("drain grace expired with requests in flight")
        server.shutdown()

    return _drain


def _install_drain_handler(
    server: ThreadingHTTPServer,
    service: GenerateService,
    grace_s: float = 30.0,
) -> bool:
    """Arm SIGTERM -> graceful drain (mirrors the trainer's preemption
    handler in ``train/run.py``: main thread only, previous handler semantics preserved by
    process exit). The handler thread exists because ``server.shutdown``
    must not run on the thread ``serve_forever`` occupies."""
    import signal

    if threading.current_thread() is not threading.main_thread():
        return False
    drain = make_drain(server, service, grace_s)

    def _on_sigterm(signum, frame):  # noqa: ANN001
        threading.Thread(target=drain, name="tpx-drain", daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # no signal support here
        return False
    return True


def main(argv: Optional[list[str]] = None) -> None:
    parser = argparse.ArgumentParser(prog="generate_server", description=__doc__)
    parser.add_argument("--config", required=True, help="model config name")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument("--int8", action="store_true", help="int8 weight-only")
    parser.add_argument(
        "--batch-window-ms",
        type=float,
        default=3.0,
        help="how long the coalescing batcher waits for concurrent requests",
    )
    parser.add_argument(
        "--max-batch",
        type=int,
        default=16,
        help="decode slots (continuous) / max sequences per batch (coalesce)",
    )
    parser.add_argument(
        "--engine",
        choices=("continuous", "coalesce"),
        default="continuous",
        help="continuous batching over paged KV (default), or the legacy"
        " batch-to-completion coalescer",
    )
    parser.add_argument(
        "--block-size", type=int, default=16, help="paged KV-cache block size"
    )
    parser.add_argument(
        "--num-blocks",
        type=int,
        default=None,
        help="paged KV pool size in blocks (default: sized from max-batch)",
    )
    parser.add_argument(
        "--serve-role",
        choices=("unified", "prefill", "decode"),
        default="unified",
        help="disaggregated serving role: 'prefill' computes prompt KV and"
        " streams it out over --kv-transfer, 'decode' accepts handoffs on"
        " /v1/kv; 'unified' (default) does both in one replica",
    )
    parser.add_argument(
        "--kv-transfer",
        default=None,
        help="KV transfer spec: local | file:<dir> |"
        " http:<url>[,<url>...] (decode replica base URLs)",
    )
    parser.add_argument(
        "--no-prefix-cache",
        action="store_true",
        help="disable the radix prefix cache (every prompt prefills cold)",
    )
    parser.add_argument(
        "--prefix-cache-reserve",
        type=float,
        default=0.0,
        help="cap cached prefix blocks at this fraction of the KV pool"
        " (0 = share the whole pool, evicting under pressure)",
    )
    parser.add_argument(
        "--drain-grace-s",
        type=float,
        default=30.0,
        help="SIGTERM drain budget before shutdown proceeds anyway",
    )
    parser.add_argument(
        "--port-stride",
        type=int,
        default=0,
        help="listen on port + stride * TPX_REPLICA_ID, so a serve pool's"
        " replicas co-located by the local scheduler get distinct ports",
    )
    parser.add_argument(
        "--profiler-port",
        type=int,
        default=0,
        help="start jax.profiler's server on this port (0 = off; strides"
        " like --port), so the standard tools can capture the live replica:"
        " device operations by scope and the engine loop's serve.* spans on"
        " one clock",
    )
    args = parser.parse_args(argv)
    if args.port_stride and (args.port or args.profiler_port):
        from torchx_tpu.settings import ENV_TPX_REPLICA_ID

        replica_id = int(os.environ.get(ENV_TPX_REPLICA_ID, "0") or "0")
        if args.port:
            args.port += args.port_stride * replica_id
        if args.profiler_port:
            args.profiler_port += args.port_stride * replica_id
    if args.profiler_port:
        import jax

        jax.profiler.start_server(args.profiler_port)
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    # the decode step and every prefill bucket relaunch from the cache
    setup_compilation_cache()
    t0 = time.monotonic()
    server = serve(
        args.config,
        args.port,
        args.ckpt_dir,
        args.int8,
        batch_window_ms=args.batch_window_ms,
        max_batch=args.max_batch,
        engine=args.engine,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        serve_role=args.serve_role,
        kv_transfer=args.kv_transfer,
        enable_prefix_cache=not args.no_prefix_cache,
        prefix_cache_reserve=args.prefix_cache_reserve,
    )
    _install_drain_handler(server, server.service, args.drain_grace_s)
    # report the BOUND port: with --port 0 the OS picks one, and whatever
    # launched us (serve pool, smoke test) reads it from this line
    port = server.server_address[1]
    device = server.service.device
    print(
        f"generate_server: {args.config} [{args.engine}] on :{port}"
        f" (loaded in {time.monotonic() - t0:.1f}s)"
        f" platform={device['platform']} device_kind={device['device_kind']!r}"
        f" device_count={device['device_count']}",
        flush=True,
    )
    server.serve_forever()
    server.server_close()
    if server.service.failed:
        print(f"generate_server: {server.service.failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
