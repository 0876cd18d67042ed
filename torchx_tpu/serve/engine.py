"""Continuous-batching decode engine over a paged KV cache.

The batch-to-completion server (`apps/generate_server.py`'s coalescing
batcher) decodes every admitted batch to its full ``max_new_tokens``
before the next batch starts: a request arriving mid-decode waits out the
whole window, and slots whose sequences finish early idle until the
stragglers do. Decode on TPU is HBM-bandwidth-bound, so throughput is
(occupied slots) x (step rate) — idle slots are thrown-away bandwidth.

This engine keeps a **fixed slot array** decoding continuously:

* **two compiled programs** per engine, whatever the traffic: the decode
  step (:func:`torchx_tpu.models.generate.paged_decode_step`, static
  ``[max_slots]`` shapes) and the same step carrying one chunk of a prompt
  (:func:`~torchx_tpu.models.generate.paged_decode_chunk_step`, ``[max_slots]``
  + ``[chunk_width]``);
* **admission is no program**: a waiting request gets a free slot at once,
  with its KV blocks allocated from the shared paged pool
  (:mod:`torchx_tpu.serve.kv_pool`), and its prompt is then **fed in chunks
  of** ``chunk_width`` **tokens that ride the decode steps**: each step takes,
  beside every decoding slot's row, the next chunk of the oldest request that
  is mid-prompt, through the layer stack in the same pass, so a prompt reads
  no weight that the slots are not already paying for and stalls nobody. A
  slot that holds an unfinished prompt decodes nothing; chunks need no result
  of the device, so chunk k+1 is enqueued with chunk k's step still in
  flight; the step that carries a prompt's last chunk samples its first
  token into that slot's place on the device, where the next step reads it;
* **one decode step always in flight**: a step's sampled tokens stay on the
  device as the next step's input, and the loop enqueues step N+1 *before*
  it fetches and commits step N, so the host's work for a step runs while
  the device runs the step before and decode program follows decode program
  on the chip. Everything else step N+1 needs is known ahead: positions,
  block tables, seeds, and a finish by token budget (such a slot is not
  stepped again). A finish by EOS is learnt one step late: the slot has
  then been stepped once too often, and that token is dropped at commit
  (:meth:`ServeEngine._commit_step`);
* **eviction** per step: a slot that hits EOS or its token budget
  completes as soon as the host holds that token — its caller unblocks, its
  blocks return to the pool, and the slot is free for the next admission;
* **preemption** under pool pressure: if a mid-decode slot can't get its
  next block, the youngest slot is evicted back to the wait queue (its
  finished tokens kept; decode resumes exactly — sampling keys are a pure
  function of (seed, position));
* **prefix reuse**: admission consults the refcounted radix
  :class:`~torchx_tpu.serve.prefix_cache.PrefixCache` and feeds only the
  *uncached suffix* of each prompt (its chunks start at the cached length);
* **what a slot holds is one object** (:mod:`torchx_tpu.serve.slot_cache`,
  picked once from the configuration): one paged pool; a full pool beside a
  ring where sliding and full attention layers mix; a paged pool beside a
  mixer's recurrent state a slot; a window's rows and the pooled rows behind
  it under one table (EVA attention). The loop asks it for a request's blocks,
  a slot made writable at a position, the tables a step takes and what the
  spans report, and knows no kind by name; a kind that is not cached or handed
  off yet says so (``stats()["prefix_cache_off"]``, a refusal);
* **disaggregation seams**: a request marked ``prefill_only`` completes
  with its first token, its KV blocks exported as a
  :class:`~torchx_tpu.serve.kv_transfer.KvPayload` (the prefill-replica
  role), and :meth:`ServeEngine.submit_prefilled` admits a transferred
  payload straight into a decode slot — scattering the received blocks
  into the pool with no prefill pass (the decode-replica role). A
  draining engine rejects handoffs with :class:`EngineStopped` so the
  sender requeues to another decode target.

Requests carry per-sequence temperature, seed, and EOS, so unrelated
requests share every device step. The engine publishes ``tpx_serve_*``
metrics through the obs registry, and its loop is spanned per step (not per
request) with the ``serve.*`` names of :mod:`torchx_tpu.obs.hot`: those are
recorded only while a ``jax.profiler`` session runs, on the device trace's
clock. With no session the loop thread writes nothing to disk.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import math
import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama
from torchx_tpu.obs import hot
from torchx_tpu.obs import metrics as obs_metrics
from torchx_tpu.serve.kv_pool import PoolPlan
from torchx_tpu.serve.kv_transfer import KvPayload, new_request_id
from torchx_tpu.serve.slot_cache import Plan, slot_cache

logger = logging.getLogger(__name__)

__all__ = [
    "ServeRequest",
    "ServeEngine",
    "EngineStopped",
    "serve_kv_payload",
]


class EngineStopped(RuntimeError):
    """Raised by :meth:`ServeEngine.submit` once the engine is draining or
    stopped — the SIGTERM drain path returns 503s off this."""


@dataclasses.dataclass
class ServeRequest:
    """One generation request moving through the engine.

    Callers fill the first block and :meth:`wait`; the engine appends to
    ``generated`` as tokens decode and sets ``done`` at completion.
    Timing: ``ttft_s`` is enqueue -> first token, ``tpot_s`` the mean gap
    between subsequent tokens — the two serving-latency axes.
    """

    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    eos_id: Optional[int] = None
    #: disaggregated mode: complete at prefill and export the computed
    #: KV blocks as ``handoff`` instead of occupying a decode slot.
    prefill_only: bool = False
    handoff: Optional[KvPayload] = None

    generated: list[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    t_enqueue: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request completes (True) or ``timeout`` (False)."""
        return self.done.wait(timeout)

    @property
    def tokens(self) -> list[int]:
        """prompt + generated, the full sequence."""
        return list(self.prompt) + self.generated

    @property
    def ttft_s(self) -> float:
        """Seconds from enqueue to first generated token."""
        return max(0.0, self.t_first - self.t_enqueue)

    @property
    def tpot_s(self) -> float:
        """Mean seconds per generated token after the first."""
        n = len(self.generated)
        if n <= 1:
            return 0.0
        return max(0.0, self.t_done - self.t_first) / (n - 1)


@dataclasses.dataclass
class _SlotState:
    req: ServeRequest
    cache_len: int  # tokens currently in the KV cache for this sequence
    last_tok: int  # most recent sampled token the host holds
    admit_seq: int  # admission order; highest = youngest = preemption victim
    #: steps enqueued for this slot whose token is not committed yet: 1 while
    #: a step is in flight, 2 between a dispatch and the commit that follows it
    unfetched: int = 0
    #: while the slot holds an unfinished prompt: prompt + tokens generated
    #: before a preemption, of which ``cache_len`` are fed; None once all are
    feeding: Optional[list[int]] = None

    @property
    def more_to_decode(self) -> bool:
        """False while the prompt is being fed, for a request that ends with
        its first token, and once the tokens held plus those in flight reach
        the budget: a finish by count is known a step ahead, so the slot is
        not stepped again."""
        if self.feeding is not None or self.req.prefill_only:
            return False
        return len(self.req.generated) + self.unfetched < self.req.max_new_tokens


@dataclasses.dataclass
class _InFlight:
    """A decode step enqueued and not yet fetched."""

    nxt: jax.Array  # [max_slots] sampled tokens, still on the device
    #: the slots it has a token for, as they were then: those it stepped, and
    #: the one whose prompt its chunk ended
    stepping: list[tuple[int, _SlotState]]
    first_of: Optional[int] = None  # the slot whose token is its request's first


@dataclasses.dataclass
class _Admit:
    """One request through admission: its cached prefix + fresh blocks."""

    req: ServeRequest
    toks: list[int]  # prompt + already-generated (resume) tokens
    plan: Plan


@dataclasses.dataclass
class _Handoff:
    """A transferred prefill (KV blocks + continuation state) waiting for
    a decode slot."""

    req: ServeRequest
    k: np.ndarray  # [L, n_blocks, bs, kvh, hd]; latent pools: [L, n_blocks, bs, cache_width]
    v: np.ndarray  # the same; latent pools: no width (generate.export_blocks)
    cache_len: int
    last_tok: int


#: in the host's token vector: "take the token the step before left on the device"
_FROM_DEVICE = -1


def _seed32(req: ServeRequest) -> np.int32:
    """A request's seed as the programs take it: its low 32 bits."""
    return np.int32(np.uint32(req.seed & 0xFFFFFFFF))


def _fold_keys(seeds: jnp.ndarray, sample_pos: jnp.ndarray) -> jnp.ndarray:
    # per-row sampling key = f(seed, position of the last token read):
    # pure, so decode resumed after preemption draws the same tokens
    base = jax.vmap(jax.random.PRNGKey)(seeds)
    return jax.vmap(jax.random.fold_in)(base, sample_pos)


class ServeEngine:
    """The continuous-batching serving engine (see module docstring).

    ``max_slots``/``block_size``/``num_blocks`` fix the compiled geometry;
    pass a :class:`~torchx_tpu.serve.kv_pool.PoolPlan` (from
    :func:`~torchx_tpu.serve.kv_pool.plan_pool`) via :meth:`from_plan` to
    size them against real HBM. The default ``num_blocks`` gives every
    slot a half-``max_seq`` budget — mild oversubscription; the preemption
    path covers the tail.
    """

    def __init__(
        self,
        params: llama.Params,
        cfg: llama.LlamaConfig,
        *,
        max_slots: int = 8,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        num_window_blocks: Optional[int] = None,
        max_prefill_batch: int = 4,
        chunk_width: int = 256,
        enable_prefix_cache: bool = True,
        prefix_cache_reserve: float = 0.0,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ) -> None:
        if block_size & (block_size - 1):
            raise ValueError(f"block_size must be a power of 2, got {block_size}")
        self._params = params
        self._cfg = cfg
        self.max_slots = max_slots
        self.block_size = block_size
        #: requests that may be mid-prompt at once, and with them the window
        #: blocks staged for prompts
        self.max_prefill_batch = max(1, max_prefill_batch)
        if chunk_width < block_size or chunk_width % block_size:
            raise ValueError(f"chunk_width must be a positive multiple of block_size={block_size}, got {chunk_width}")
        #: what the slots hold, by the kind of cache the model keeps: pools, tables, allocators, prefix cache
        self.cache = slot_cache(cfg, max_slots=max_slots, block_size=block_size, num_blocks=num_blocks, num_window_blocks=num_window_blocks,
                                max_prefill_batch=self.max_prefill_batch, prefix_cache=enable_prefix_cache, prefix_cache_reserve=prefix_cache_reserve)  # fmt: skip
        obs_metrics.SERVE_STATE_BYTES.set(self.cache.state_bytes)
        #: prompt tokens a step can carry: compiled geometry, like block_size
        #: (256 is the one width measured and checked on the chip; the tests
        #: pass a small one). No prompt is longer than a slot's blocks
        self.chunk_width = min(chunk_width, self.cache.blocks_per_slot * block_size)
        self._clock = clock
        self._sleep = sleep
        self._slots: list[Optional[_SlotState]] = [None] * max_slots
        self._admit_counter = itertools.count()

        self._lock = threading.Lock()
        self._waiting: deque[ServeRequest] = deque()
        self._handoffs: deque[_Handoff] = deque()
        #: popped from _waiting/_handoffs, not yet slotted or done: drain()
        #: waits for them, and a step that raises mid-admission must fail
        #: them too, or their callers wait for ever
        self._admitting: list[ServeRequest] = []
        self._work = threading.Event()
        self._stop = threading.Event()
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self.requests_done = 0
        self.tokens_out = 0
        self.steps = 0
        self.preemptions = 0  # slots evicted back to the queue under pool pressure
        self.steps_overlapped = 0  # steps enqueued while the step before was unfetched
        #: slot-steps whose token was dropped at commit: the step after an EOS,
        #: a slot preempted with its step in flight
        self.tokens_discarded = 0
        self.chunk_steps = 0  # steps that carried a chunk of a prompt
        self.prefill_tokens = 0  # prompt tokens fed: the chunks' real tokens
        self.prefill_padded_tokens = 0  # positions computed for them: chunk_steps x chunk_width
        self._in_flight: Optional[_InFlight] = None
        #: why the loop died (a step raised), else None; a dead engine
        #: refuses work and fails the replica's health check
        self.failed: Optional[str] = None

        # two compiled programs for the engine's lifetime. The weights
        # are an ARGUMENT of every jitted function: closed over, they lower
        # to constants — at 1B parameters 2.5 GB of literals in each
        # program's HLO and a private device copy in each executable.
        # Donation lets XLA update the pools in place (no-op on CPU, where
        # jax warns — so only donate off-CPU)
        donate = (5,) if jax.default_backend() != "cpu" else ()
        cfg_c = self._cfg

        def _decode(params, tokens, prev, positions, tables, pools, seeds, temps):  # noqa: ANN001
            # a slot's input is the token the host holds for it (just admitted,
            # handed off) or, where the host says _FROM_DEVICE, the one the step
            # before sampled for that slot, which never left the device
            tokens = jnp.where(tokens == _FROM_DEVICE, prev, tokens)
            keys = _fold_keys(seeds, positions)
            return gen.paged_decode_step(
                params, tokens, positions, tables, pools, cfg_c, keys, temps
            )

        def _decode_chunk(params, tokens, prev, positions, tables, pools, seeds, temps, chunk, at, chunk_tables):  # noqa: ANN001
            # the same step with ``chunk`` riding it: the next tokens of one
            # prompt from position ``at[0]`` on, ``at[1]`` of them real. ``seeds``
            # and ``temps`` end with that request's. Where the chunk ends its
            # prompt, ``at[2]`` is the request's slot and the token sampled
            # behind the chunk, the request's first, takes that slot's place in
            # what the step leaves on the device; else ``at[2]`` is no slot
            tokens = jnp.where(tokens == _FROM_DEVICE, prev, tokens)
            # the key is a function of the *absolute* position of the last
            # prompt token, so a prompt fed behind a cached head, or in other
            # chunks after a preemption, draws the same first token
            keys = _fold_keys(seeds, jnp.append(positions, at[0] + at[1] - 1))
            sampled, pools = gen.paged_decode_chunk_step(
                params, tokens, positions, tables, chunk, at[0], at[1], chunk_tables, pools, cfg_c, keys, temps
            )
            return jnp.where(jnp.arange(max_slots) == at[2], sampled[-1], sampled[:-1]), pools

        self._decode = jax.jit(_decode, donate_argnums=donate)
        self._decode_chunk = jax.jit(_decode_chunk, donate_argnums=donate)

    @classmethod
    def from_plan(
        cls,
        params: llama.Params,
        cfg: llama.LlamaConfig,
        plan: PoolPlan,
        **kwargs,
    ) -> "ServeEngine":
        """Build an engine with the geometry a :func:`plan_pool` sizing
        chose for the HBM budget."""
        return cls(
            params,
            cfg,
            max_slots=plan.max_slots,
            block_size=plan.block_size,
            num_blocks=plan.num_blocks,
            num_window_blocks=plan.num_window_blocks or None,
            **kwargs,
        )

    @property
    def pools(self):  # noqa: ANN201
        """The pools on the device, as the last step enqueued leaves them."""
        return self.cache.pools

    # -- public API --------------------------------------------------------

    def start(self) -> "ServeEngine":
        """Spawn the engine loop thread (idempotent)."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="serve-engine", daemon=True
            )
            self._thread.start()
        return self

    def submit(self, req: ServeRequest) -> ServeRequest:
        """Enqueue a request for admission; raises :class:`EngineStopped`
        when draining/stopped, ValueError when it can never fit."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self._cfg.max_seq:
            raise ValueError(
                f"prompt + new tokens ({total}) exceeds max_seq"
                f" {self._cfg.max_seq}"
            )
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.prefill_only:
            self._refuse_handoff()
        with self._lock:
            if self.failed is not None:
                raise EngineStopped(self.failed)
            if self._draining or self._stop.is_set():
                raise EngineStopped("engine is draining; not admitting requests")
            req.t_enqueue = self._clock()
            self._waiting.append(req)
            obs_metrics.SERVE_QUEUE_DEPTH.set(len(self._waiting))
        self._work.set()
        return req

    def submit_prefilled(
        self,
        req: ServeRequest,
        k: np.ndarray,
        v: np.ndarray,
        cache_len: int,
        last_tok: int,
    ) -> ServeRequest:
        """Admit a sequence whose KV was prefilled on another replica.

        ``k``/``v`` are block-granular ``[L, n, bs, kvh, hd]`` arrays (a
        latent pool's ``[L, n, bs, cache_width]`` as ``k``, beside a ``v``
        of no width: :func:`~torchx_tpu.models.generate.export_blocks`)
        covering ``cache_len`` tokens; decode continues from ``last_tok``
        with no prefill pass. Raises :class:`EngineStopped` while
        draining — the transfer sender requeues to another decode
        target (the disaggregated drain-race contract)."""
        self._refuse_handoff()
        n_need = math.ceil(cache_len / self.block_size)
        if k.shape[1] != n_need or v.shape[1] != n_need:
            raise ValueError(
                f"payload has {k.shape[1]} blocks; cache_len={cache_len} "
                f"needs {n_need} at block_size={self.block_size}"
            )
        remaining = req.max_new_tokens - len(req.generated)
        if cache_len + remaining > self._cfg.max_seq:
            raise ValueError(
                f"cached tokens + remaining new tokens "
                f"({cache_len}+{remaining}) exceeds max_seq {self._cfg.max_seq}"
            )
        with self._lock:
            if self.failed is not None:
                raise EngineStopped(self.failed)
            if self._draining or self._stop.is_set():
                raise EngineStopped("engine is draining; not accepting handoffs")
            if req.t_enqueue == 0.0:
                req.t_enqueue = self._clock()
            self._handoffs.append(_Handoff(req, k, v, cache_len, last_tok))
        self._work.set()
        return req

    def _refuse_handoff(self) -> None:
        """A hand-off carries K/V blocks by token: a kind that holds more says no."""
        if self.cache.no_handoff:
            raise NotImplementedError(self.cache.no_handoff)

    def _admit_handoffs(self) -> bool:
        """Place transferred prefills into free slots: scatter the
        received blocks into the pool, no device prefill needed."""
        worked = False
        while True:
            free = [i for i, s in enumerate(self._slots) if s is None]
            with self._lock:
                if not self._handoffs or not free:
                    return worked
                h = self._handoffs[0]
                plan = self.cache.plan_handoff(h.cache_len)
                if plan is None:
                    return worked  # pool pressure; retry next loop pass
                self._handoffs.popleft()
                self._admitting = [h.req]  # visible to drain() until slotted
            with hot.span(hot.SERVE_KV_IMPORT, blocks=len(plan.blocks), cache_len=h.cache_len):
                self.cache.import_blocks(plan, h.k, h.v)
            slot = free[0]
            self.cache.place(slot, plan)
            self.cache.fed(slot, list(h.req.prompt) + h.req.generated, h.cache_len, last=True)
            self._slots[slot] = _SlotState(
                req=h.req,
                cache_len=h.cache_len,
                last_tok=h.last_tok,
                admit_seq=next(self._admit_counter),
            )
            with self._lock:
                self._admitting = []
            self._update_gauges()
            worked = True

    def generate(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        timeout: Optional[float] = None,
    ) -> ServeRequest:
        """Submit and block until done — the one-call convenience path."""
        req = ServeRequest(
            prompt=list(prompt),
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            seed=seed,
            eos_id=eos_id,
        )
        self.submit(req)
        if not req.wait(timeout):
            raise TimeoutError(f"generation did not finish in {timeout}s")
        if req.error:
            raise RuntimeError(req.error)
        return req

    def stats(self) -> dict:
        """Engine occupancy/queue snapshot (feeds ``/healthz`` and the
        serve pool's load probe)."""
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
            out = {
                "active_slots": active,
                "max_slots": self.max_slots,
                "occupancy": active / self.max_slots,
                "queue_depth": len(self._waiting),
                "handoffs_pending": len(self._handoffs),
                "kv_blocks_used": self.cache.alloc.used_blocks,
                "kv_blocks_free": self.cache.alloc.free_blocks,
                "requests_done": self.requests_done,
                "tokens_out": self.tokens_out,
                "steps": self.steps,
                "preemptions": self.preemptions,
                "steps_overlapped": self.steps_overlapped,
                "tokens_discarded": self.tokens_discarded,
                "chunk_steps": self.chunk_steps,
                "chunk_width": self.chunk_width,
                "prefill_tokens": self.prefill_tokens,
                "prefill_padded_tokens": self.prefill_padded_tokens,
                **self.cache.stats(self._held()),
                "draining": self._draining,
                "failed": self.failed,
            }
        if self.cache.prefix_cache is not None:
            out["prefix_cache"] = self.cache.prefix_cache.stats()
        elif self.cache.prefix_cache_off:
            out["prefix_cache_off"] = self.cache.prefix_cache_off
        return out

    def prefix_summary(self, max_entries: int = 128) -> list[str]:
        """Digests of this engine's hottest cached prefixes — published
        on ``/healthz`` for the cache-aware router."""
        if self.cache.prefix_cache is None:
            return []
        return self.cache.prefix_cache.summary(max_entries)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (the autoscaler's primary signal)."""
        with self._lock:
            return len(self._waiting)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting, finish everything in flight, return True when
        empty (False on timeout). The SIGTERM grace path."""
        with self._lock:
            self._draining = True
        deadline = None if timeout is None else self._clock() + timeout
        while True:
            with self._lock:
                empty = (
                    not self._waiting
                    and not self._handoffs
                    and not self._admitting
                    and all(s is None for s in self._slots)
                    # the step after an EOS: stepped nobody that is left, still to be fetched
                    and self._in_flight is None
                )
            if empty:
                return True
            if deadline is not None and self._clock() > deadline:
                return False
            self._sleep(0.005)

    def stop(self, timeout: float = 5.0) -> None:
        """Kill the loop thread; in-flight requests get ``error`` set."""
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout)
        self._fail_all("engine stopped")

    # -- engine loop -------------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                worked = self._admit_handoffs()
                worked = self._admit() or worked
                worked = self._decode_once() or worked
            except Exception as e:  # noqa: BLE001 — a step bug must not hang callers
                logger.exception("serve engine step failed")
                msg = f"engine step failed: {type(e).__name__}: {e}"
                with self._lock:
                    self.failed = msg  # before failing waiters: no re-queue race
                self._fail_all(msg)
                return
            if not worked:
                with hot.span(hot.SERVE_IDLE):
                    self._work.wait(0.002)
                self._work.clear()

    def _fail_all(self, msg: str) -> None:
        with self._lock:
            pending = list(self._waiting)
            pending.extend(h.req for h in self._handoffs)
            pending.extend(self._admitting)
            self._waiting.clear()
            self._handoffs.clear()
            self._admitting = []
        self._in_flight = None
        for i, st in enumerate(self._slots):
            if st is not None:
                self._release_slot(i)
                pending.append(st.req)
        for req in pending:
            if not req.done.is_set():
                req.error = msg
                req.t_done = self._clock()
                req.done.set()
                obs_metrics.SERVE_REQUESTS.inc(status="error")

    # -- admission -----------------------------------------------------------

    def _held(self):  # noqa: ANN202
        """The tokens each occupied slot holds, written or in flight (iterated by a kind that reports in rows alone)."""
        return (st.cache_len + st.unfetched for st in self._slots if st is not None)

    def _release_slot(self, slot: int) -> _SlotState:
        """Empty ``slot``: what it holds in the cache goes back. -> the state it held."""
        st = self._slots[slot]
        self._slots[slot] = None
        self.cache.release(slot)
        return st

    def _admit(self) -> bool:
        """Give waiting requests the free slots, as many as may be mid-prompt
        at once: planning alone (prefix match, blocks), no program. The decode
        steps that follow feed each prompt (:meth:`_decode_once`)."""
        free_slots = [i for i, s in enumerate(self._slots) if s is None]
        # an unlocked peek: most loop turns find nothing waiting, and those
        # turns open no span
        if not free_slots or not self._waiting:
            return False
        feeding = sum(1 for s in self._slots if s is not None and s.feeding is not None)
        room = min(len(free_slots), self.max_prefill_batch - feeding)
        if room <= 0:
            return False
        with hot.span(hot.SERVE_ADMIT) as admit_span:
            with hot.span(hot.SERVE_ADMIT_PLAN):
                admitted = self._plan_admission(room)
            if not admitted:
                return False
            for a, slot in zip(admitted, free_slots):
                self.cache.place(slot, a.plan)
                self._slots[slot] = _SlotState(
                    req=a.req,
                    cache_len=a.plan.cached_tokens,
                    last_tok=0,  # never read: the slot's first step takes its token from the device
                    admit_seq=next(self._admit_counter),
                    feeding=a.toks,
                )
            with self._lock:
                self._admitting = []
            self._update_gauges()
            admit_span.set_metadata(
                admitted=len(admitted),
                cached_tokens=sum(a.plan.cached_tokens for a in admitted),
                queue_depth=len(self._waiting),
                kv_bytes_per_token=self.cache.kv_bytes_per_token,
                **self.cache.span_attrs(self._held()),
            )
        return True

    def _plan_admission(self, limit: int) -> list[_Admit]:
        """Under the lock: match prefixes, allocate blocks and take up to
        ``limit`` requests off the head of the queue. -> what to feed."""
        admitted: list[_Admit] = []
        with self._lock:
            for req in list(self._waiting)[:limit]:
                toks = list(req.prompt) + req.generated
                plan = self.cache.plan(toks)
                if plan is None:
                    break  # pool pressure: admit what fits, retry later
                admitted.append(_Admit(req, toks, plan))
            for a in admitted:
                self._waiting.remove(a.req)
            # visible to drain(): popped but not yet in a slot
            self._admitting = [a.req for a in admitted]
            obs_metrics.SERVE_QUEUE_DEPTH.set(len(self._waiting))
        return admitted

    def _export_handoff(self, req: ServeRequest, toks: list[int], slot: int) -> KvPayload:
        """Snapshot ``slot``'s prefilled K/V blocks for transfer to a decode
        replica (the ``prefill_only`` completion path, which :meth:`submit`
        lets only a kind that is handed off take)."""
        k, v = self.cache.export(slot)
        return KvPayload(
            request_id=new_request_id(),
            tokens=list(toks),
            generated=list(req.generated),
            cache_len=len(toks),
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature,
            seed=req.seed,
            eos_id=req.eos_id,
            block_size=self.block_size,
            k=k,
            v=v,
        )

    # -- decode ------------------------------------------------------------

    def _finished(self, req: ServeRequest, tok: int) -> bool:
        return len(req.generated) >= req.max_new_tokens or (
            req.eos_id is not None and tok == req.eos_id
        )

    def _complete(self, req: ServeRequest, now: float) -> None:
        req.t_done = now
        req.done.set()
        self.requests_done += 1
        obs_metrics.SERVE_REQUESTS.inc(status="ok")
        if len(req.generated) > 1:
            obs_metrics.SERVE_TPOT_SECONDS.observe(req.tpot_s)

    def _preempt_youngest(self) -> bool:
        victims = [
            (st.admit_seq, i) for i, st in enumerate(self._slots) if st is not None
        ]
        if not victims:
            return False
        _, slot = max(victims)
        st = self._release_slot(slot)
        with self._lock:
            self._waiting.appendleft(st.req)  # resumes by being fed again, its generated tokens behind its prompt
            obs_metrics.SERVE_QUEUE_DEPTH.set(len(self._waiting))
        obs_metrics.SERVE_PREEMPTIONS.inc()
        self.preemptions += 1
        return True

    def _make_writable(self, slot: int, write_pos: int) -> bool:
        """Make ``slot`` writable at ``write_pos``, preempting the youngest slot for as long as
        a pool is short. False if ``slot`` itself was preempted away: nothing else to evict."""
        while not self.cache.grow(slot, write_pos):
            self._preempt_youngest()
            if self._slots[slot] is None:
                return False
        return True

    def _next_chunk(self) -> Optional[tuple[int, _SlotState, int]]:
        """-> (slot, its state, real tokens) of the chunk the next step
        carries: the next ``chunk_width`` tokens, or what is left, of the
        oldest request that is mid-prompt. None with no prompt pending."""
        feeding = [(st.admit_seq, slot) for slot, st in enumerate(self._slots) if st is not None and st.feeding is not None]
        if not feeding:
            return None
        slot = min(feeding)[1]
        st = self._slots[slot]
        return slot, st, self.cache.chunk_tokens(st.cache_len, min(self.chunk_width, len(st.feeding) - st.cache_len))

    def _chunk_enqueued(self, slot: int, st: _SlotState, n: int) -> bool:
        """The step just enqueued carries the next ``n`` tokens of ``slot``'s
        prompt: the cache is told (it indexes the full blocks, and a kind moves
        on what it staged), and behind the prompt's last chunk the slot decodes
        from the next step on. -> whether that chunk was the last."""
        st.cache_len += n
        self.chunk_steps += 1
        self.prefill_tokens += n
        self.prefill_padded_tokens += self.chunk_width
        last = st.cache_len == len(st.feeding)
        self.cache.fed(slot, st.feeding, st.cache_len, last)
        if last:
            st.feeding = None
            # as a decode step leaves it: the step that writes the sequence's
            # last row is in flight, and its token is the next step's input
            st.cache_len -= 1
            st.unfetched = 1
        return last

    def _decode_once(self) -> bool:
        """One turn of the decode pipeline: prepare and enqueue the next step,
        then fetch and commit the one in flight. The step enqueued reads, on
        the device, the tokens the one in flight will have sampled, and carries
        the next chunk of a prompt where one is pending (a chunk needs no
        result of the device, so it follows the chunk before it with that
        step still in flight); with nothing left to step, the turn still
        fetches what is in flight, so the loop never idles on an unfetched
        token."""
        before = self._in_flight
        if before is None and all(s is None for s in self._slots):
            return False
        with hot.span(hot.SERVE_DECODE, step=self.steps) as step_span:
            with hot.span(hot.SERVE_DECODE_PREPARE):
                for slot, st in enumerate(self._slots):
                    # None by now: preempted by an earlier slot's capacity grab
                    if st is not None and st.more_to_decode:
                        self._make_writable(slot, st.cache_len + st.unfetched)
                # a kind may give a prompt its blocks as its chunks reach them: now, ahead of the
                # tables' copy below (a request preempted away for them leaves the next to be fed)
                chunk = self._next_chunk()
                while chunk is not None and not self._make_writable(chunk[0], chunk[1].cache_len + chunk[2] - 1):
                    chunk = self._next_chunk()

                tokens = np.zeros((self.max_slots,), np.int32)
                positions = np.zeros((self.max_slots,), np.int32)
                seeds = np.zeros((self.max_slots,), np.int32)
                temps = np.zeros((self.max_slots,), np.float32)
                stepping: list[tuple[int, _SlotState]] = []
                parked: list[int] = []  # its prompt is still being fed, or its last token is in flight
                for slot, st in enumerate(self._slots):
                    if st is None:
                        continue
                    if not st.more_to_decode:
                        parked.append(slot)
                        continue
                    tokens[slot] = _FROM_DEVICE if st.unfetched else st.last_tok
                    positions[slot] = st.cache_len + st.unfetched
                    seeds[slot] = _seed32(st.req)
                    temps[slot] = st.req.temperature
                    stepping.append((slot, st))
                tables = self.cache.step_tables([slot for slot, _ in stepping], parked)

                if chunk is not None:
                    c_slot, c_st, n = chunk
                    start = c_st.cache_len
                    chunk_tokens = np.zeros((self.chunk_width,), np.int32)
                    chunk_tokens[:n] = c_st.feeding[start : start + n]
                    ends = start + n == len(c_st.feeding)
                    at = np.asarray([start, n, c_slot if ends else -1], np.int32)
                    seeds = np.append(seeds, _seed32(c_st.req))
                    temps = np.append(temps, np.float32(c_st.req.temperature))
                    # the request's own (the step's copy sends its slot's decode row to the trash block)
                    chunk_tables = self.cache.chunk_tables(c_slot)

            enqueued = None
            reading = (st.cache_len + st.unfetched for _, st in stepping)
            step_span.set_metadata(**self.cache.span_attrs(self._held(), reading))  # as the step is dispatched
            if stepping or chunk is not None:
                with hot.span(hot.SERVE_DECODE_DISPATCH):
                    host_tokens = jnp.asarray(tokens)
                    args = (
                        self._params,
                        host_tokens,
                        # with no step in flight every slot reads the host's token
                        host_tokens if before is None else before.nxt,
                        jnp.asarray(positions),
                        jax.tree.map(jnp.asarray, tables),
                        self.cache.pools,
                        jnp.asarray(seeds),
                        jnp.asarray(temps),
                    )
                    first_of = None
                    if chunk is None:
                        nxt, self.cache.pools = self._decode(*args)
                    else:
                        nxt, self.cache.pools = self._decode_chunk(
                            *args,
                            jnp.asarray(chunk_tokens),
                            jnp.asarray(at),
                            jax.tree.map(jnp.asarray, chunk_tables),
                        )
                    for _, st in stepping:
                        st.unfetched += 1
                    if chunk is not None and self._chunk_enqueued(c_slot, c_st, n):
                        stepping.append((c_slot, c_st))
                        first_of = c_slot
                enqueued = _InFlight(nxt, stepping, first_of)
                self.steps_overlapped += before is not None

            if before is not None:
                with hot.span(hot.SERVE_DECODE_FETCH):
                    sampled = np.asarray(before.nxt)
                self.steps += 1
                with hot.span(hot.SERVE_DECODE_COMMIT) as commit_span:
                    commit_span.set_metadata(finished=self._commit_step(before, sampled))
            # only now: drain() must not see "nothing in flight" between the two
            self._in_flight = enqueued
            step_span.set_metadata(
                active=len(stepping),
                chunk_tokens=n if chunk is not None else 0,
                chunk_width=self.chunk_width,
                steps_overlapped=self.steps_overlapped,
                tokens_discarded=self.tokens_discarded,
            )
        return enqueued is not None or before is not None

    def _commit_step(self, step: _InFlight, sampled: np.ndarray) -> int:
        """Hand each slot a fetched step has a token for its token; -> how
        many finished. A slot that no longer holds the state it was stepped
        with finished by EOS or was preempted while the step was in flight: its
        token is dropped (a preempted request draws it again when it is fed
        again: the key is seed and position). The row that step wrote
        for it lies in a block the slot owned unshared at dispatch, past every
        token the prefix cache indexes, and whatever reuses the block is
        enqueued after the step. The token behind a prompt's last chunk
        (``step.first_of``) is its request's first: it stamps the time to first
        token, and ends a ``prefill_only`` request, whose blocks are exported
        as they then lie."""
        finished = kept = 0
        now = self._clock()
        for slot, st in step.stepping:
            if self._slots[slot] is not st:
                self.tokens_discarded += 1
                continue
            st.unfetched -= 1
            st.cache_len += 1
            tok = int(sampled[slot])
            st.last_tok = tok
            req = st.req
            if slot == step.first_of:
                if not req.generated:  # else preempted earlier: its first token came then
                    req.t_first = now
                    obs_metrics.SERVE_TTFT_SECONDS.observe(req.ttft_s)
                obs_metrics.SERVE_TOKENS.inc(phase="prefill")
            else:
                kept += 1
            req.generated.append(tok)
            self.tokens_out += 1
            done = self._finished(req, tok)
            if done or req.prefill_only:
                finished += 1
                seq = list(req.prompt) + req.generated
                if not done:
                    # a request its first token already finishes never needs
                    # the decode side: no handoff, the caller reads .tokens
                    req.handoff = self._export_handoff(req, seq[: st.cache_len], slot)
                # index the completed sequence's full blocks (cache_len tokens: all
                # but the final sampled token) before dropping the slot's refs
                self.cache.fed(slot, seq, st.cache_len, last=True)
                self._release_slot(slot)
                self._complete(req, now)
        if kept:
            obs_metrics.SERVE_TOKENS.inc(kept, phase="decode")
        self._update_gauges()
        return finished

    def _update_gauges(self) -> None:
        active = sum(1 for s in self._slots if s is not None)
        obs_metrics.SERVE_SLOTS_ACTIVE.set(active)
        obs_metrics.SERVE_OCCUPANCY.set(active / self.max_slots)
        self.cache.update_gauges(self._held())


def serve_kv_payload(
    engine: ServeEngine,
    payload: KvPayload,
    timeout: Optional[float] = None,
) -> dict:
    """Decode-replica handler for one transferred prefill: admit the
    payload via :meth:`ServeEngine.submit_prefilled`, wait for
    completion, and return the transport reply. The ``/v1/kv`` endpoint
    and the file-spool pump both route here; :class:`EngineStopped`
    (draining) propagates as
    :class:`~torchx_tpu.serve.kv_transfer.TransferRejected` so the
    prefill side requeues."""
    from torchx_tpu.serve.kv_transfer import TransferRejected

    if payload.block_size != engine.block_size:
        raise ValueError(
            f"payload block_size {payload.block_size} != engine "
            f"block_size {engine.block_size}"
        )
    req = ServeRequest(
        prompt=list(payload.tokens),
        max_new_tokens=payload.max_new_tokens,
        temperature=payload.temperature,
        seed=payload.seed,
        eos_id=payload.eos_id,
        generated=list(payload.generated),
    )
    try:
        engine.submit_prefilled(
            req,
            payload.k,
            payload.v,
            payload.cache_len,
            last_tok=payload.generated[-1],
        )
    except EngineStopped as e:
        raise TransferRejected(str(e)) from e
    if not req.wait(timeout):
        raise TimeoutError(
            f"transferred request {payload.request_id} did not finish"
        )
    if req.error:
        raise RuntimeError(req.error)
    return {"request_id": payload.request_id, "tokens": req.generated}
