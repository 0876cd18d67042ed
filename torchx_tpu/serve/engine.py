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
  :class:`~torchx_tpu.serve.prefix_cache.PrefixCache` and prefills only
  the *uncached suffix* of each prompt (its chunks start at the cached
  length); newly computed full blocks are inserted back as soon as the chunk
  that fills them is enqueued (device order makes them valid for every later
  program) and on completion.
  Cached blocks are shared by refcount — a shared tail block about to be
  written is copy-on-write copied first, and under pool pressure the
  engine evicts cache-only blocks before preempting live slots;
* **two kinds of cache in one manager**: a model that mixes sliding and full
  attention layers (``cfg.layer_types``) keeps a pool a kind. The full layers'
  blocks are paged by a slot's table as above; in the sliding layers' pools a
  slot holds a ring of :func:`~torchx_tpu.serve.kv_pool.window_ring` blocks,
  and the oldest goes back to that pool's allocator as soon as every
  position in it is below every future query's window (while decoding, and
  between two chunks of a prompt, whose blocks are staged block ``b`` at entry
  ``b`` until its last chunk hands those still in reach to the ring).
  Preemption frees both; a prefix-cache node holds a block of each;
* **recurrent state beside the paged K/V**: where the layers have a state-space
  mixer (``cfg.ssm_heads``), slot ``i`` owns row ``i + 1`` of the mixer's store
  (:func:`torchx_tpu.models.ssm.init_store`; row 0 is the trash row, as block 0
  is the trash block), addressed by slot and not by position. **A row has one
  writer a step**: a step's decode part addresses the trash row for every slot
  that is not decoding (empty, or mid-prompt), so a slot whose prompt is being
  fed is written by its chunk alone. A chunk that starts at position 0 starts
  from zeros inside the program, which is the whole of a reset: for a new
  tenant, and for a preempted request, which is fed again from position 0. The
  step in flight behind an EOS moves a row on that nobody reads again. State is
  not yet cached or handed off: such an engine has no prefix cache (a hit would
  bring K/V without the state that goes with it; ``stats()`` says so) and
  refuses ``prefill_only`` requests and :meth:`ServeEngine.submit_prefilled`;
* **a cache whose rows are not its tokens**: under EVA attention
  (``cfg.eva_window``, :mod:`torchx_tpu.models.eva`) a slot holds the blocks of
  its current window, the pooled rows of every window behind it (one row for
  every ``cfg.eva_chunk`` positions) and staging blocks in which the current
  window's pooled rows are being written, all in the one pool and, but for the
  staging, under the one table the programs read
  (:class:`~torchx_tpu.serve.kv_pool.EvaTables`). The programs take a slot's
  position (roped, and what the sampling key is folded from) and work out its
  cache coordinate themselves. When a write starts a new window the host moves
  the staging blocks into the table, gives the ended window's blocks back all at
  once and allocates anew, with the step that wrote the window's last row still
  in flight (device order keeps its blocks its own until it has run). A chunk of
  a prompt stops where a window ends, and a prompt is given the blocks of its
  first window at admission and the rest as its chunks reach them. Admission,
  pressure and the spans reckon in the rows held. Such an engine has no prefix
  cache and refuses hand-offs, as with recurrent state: neither indexes a cache
  by anything but tokens yet;
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
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.serve.kv_pool import BlockAllocator, EvaTables, PoolPlan, SlotTables, WindowTables, window_ring
from torchx_tpu.serve.kv_transfer import KvPayload, new_request_id
from torchx_tpu.serve.prefix_cache import PrefixCache

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
    #: the sliding layers' blocks staged for the prompt, where the model has
    #: any: block of the sequence -> block; the ring's after the last chunk
    staged: dict[int, int] = dataclasses.field(default_factory=dict)

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
    cached_blocks: list[int]  # retained from the prefix cache
    cached_tokens: int  # block-aligned prefix length served from cache
    new_blocks: list[int]  # freshly allocated for the suffix
    #: the sliding layers' pool, where the model has one: block of the sequence
    #: -> block, for the cached prefix's last blocks and for every new block
    window_blocks: dict[int, int] = dataclasses.field(default_factory=dict)


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
        #: EVA attention: the slots' tables, whose rows are not their tokens; None for every other model
        self.eva = EvaTables(max_slots, cfg.max_seq, cfg.eva_window, cfg.eva_chunk, block_size) if cfg.eva_window else None
        self.blocks_per_slot = self.eva.blocks_per_slot if self.eva else math.ceil(cfg.max_seq / block_size)
        #: requests that may be mid-prompt at once, and with them the window
        #: blocks staged for prompts
        self.max_prefill_batch = max(1, max_prefill_batch)
        if chunk_width < block_size or chunk_width % block_size:
            raise ValueError(f"chunk_width must be a positive multiple of block_size={block_size}, got {chunk_width}")
        #: prompt tokens a step can carry: compiled geometry, like block_size
        #: (256 is the one width measured and checked on the chip; the tests
        #: pass a small one). No prompt is longer than a slot's blocks
        self.chunk_width = min(chunk_width, self.blocks_per_slot * block_size)
        #: the most blocks one sequence holds at once
        most = self.eva.most_blocks if self.eva else self.blocks_per_slot
        if num_blocks is None:
            # half a table a slot; where rows are not tokens, beside every block of pooled rows a slot
            # can come to hold (they stay for as long as the sequence does, the window's come back)
            pooled = self.eva.pooled_blocks * self.eva.windows if self.eva else 0
            num_blocks = 1 + max_slots * (pooled + max(1, self.blocks_per_slot // 2))
        if num_blocks < most + 1:
            raise ValueError(
                f"num_blocks={num_blocks} cannot hold one max_seq sequence"
                f" ({most} blocks + trash)"
            )
        self.num_blocks = num_blocks
        self._clock = clock
        self._sleep = sleep

        #: the sliding layers' window (0: the model has none) and the entries of a
        #: slot's ring table in their pools
        self.window = cfg.sliding_window if cfg.layers_of("window") else 0
        self.window_ring = window_ring(self.window, block_size) if self.window else 0
        if self.window and num_window_blocks is None:
            # every slot's ring, and the prompts being fed staged whole
            num_window_blocks = 1 + max_slots * self.window_ring + self.max_prefill_batch * self.blocks_per_slot
        self.num_window_blocks = num_window_blocks if self.window else 0
        self.pools = gen.init_kv_pools(cfg, num_blocks, block_size, self.num_window_blocks, max_slots)
        #: recurrent state a slot holds whatever its length (a mixer's store), and over all rows
        store = jax.tree.leaves(self.pools.get("ssm", ()))
        self.state_bytes_per_slot = sum(p.nbytes // p.shape[1] for p in store)
        self.state_bytes = sum(p.nbytes for p in store)
        obs_metrics.SERVE_STATE_BYTES.set(self.state_bytes)
        row_bytes = lambda pools: sum(  # noqa: E731 - a token's bytes over the layers of a pool tree
            p.shape[0] * math.prod(p.shape[3:]) * p.dtype.itemsize for p in jax.tree.leaves(pools)
        )
        #: bytes a further token of context holds, as the pools are laid out:
        #: every layer's, but for the sliding layers, whose cost a slot is constant
        self.kv_bytes_per_token = row_bytes(self._pools_of("full"))
        if self.eva:  # a row's bytes while the token is in its window; for ever after, its share of a pooled row
            self.kv_bytes_per_token //= cfg.eva_chunk
        self.kv_bytes_per_slot_window = (
            self.window_ring * block_size * row_bytes(self.pools["window"]) if self.window else 0
        )
        self.alloc = BlockAllocator(num_blocks)
        self.tables = self.eva or SlotTables(max_slots, self.blocks_per_slot)
        self.window_alloc = BlockAllocator(self.num_window_blocks) if self.window else None
        self.window_tables = WindowTables(max_slots, self.window_ring) if self.window else None
        self.window_blocks_released = 0  # window blocks slots gave back as their windows moved on (EVA: ended)
        self.pooled_blocks_promoted = 0  # EVA: staging blocks moved into a table as their window ended
        self._slots: list[Optional[_SlotState]] = [None] * max_slots
        self._admit_counter = itertools.count()
        self.prefix_cache: Optional[PrefixCache] = None
        #: why there is no prefix cache though one was asked for, else None
        self.prefix_cache_off: Optional[str] = None
        if enable_prefix_cache and self.state_bytes:
            self.prefix_cache_off = (
                "recurrent state: the cache indexes K/V blocks alone, and a hit would hand a request K/V without"
                " the state that goes with it"
            )
        elif enable_prefix_cache and self.eva:
            self.prefix_cache_off = (
                "a cache whose rows are not its tokens: the prefix cache indexes a block by the tokens it holds, and a"
                " window's blocks are given back and its pooled rows laid out anew as the sequence grows"
            )
        elif enable_prefix_cache:
            cap = (
                max(1, int(prefix_cache_reserve * num_blocks))
                if prefix_cache_reserve > 0
                else None
            )
            self.prefix_cache = PrefixCache(
                self.alloc,
                block_size,
                max_blocks=cap,
                window_alloc=self.window_alloc,
                # the blocks ahead of a suffix that its first query's window reaches into
                window_back=math.ceil((self.window - 1) / block_size) if self.window else 0,
            )

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

    def _window_first_block(self, query_pos: int) -> int:
        """The lowest block of a sequence that the window of a query at
        ``query_pos``, and so of every later one, still touches."""
        return max(0, query_pos - self.window + 1) // self.block_size

    def _pools_of(self, kind: str):  # noqa: ANN202
        """The paged pools of one cache kind: the whole tree where the model has
        one kind, a mixer's store (addressed by slot, not by block) left out."""
        if self.window:
            return self.pools[kind]
        return {name: pool for name, pool in self.pools.items() if name != "ssm"}

    def _tables_arg(self, full, window, state_rows=None):  # noqa: ANN001, ANN202
        """What the programs take as ``tables``: one array, or one a cache kind;
        with a mixer the rows' state rows beside the one table."""
        if self.state_bytes:
            return {"full": jnp.asarray(full), "state": jnp.asarray(state_rows, jnp.int32)}
        if self.eva:  # ``window`` is then the rows' staging blocks
            return {"full": jnp.asarray(full), "stage": jnp.asarray(window)}
        return {"full": jnp.asarray(full), "window": jnp.asarray(window)} if self.window else jnp.asarray(full)

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
        """A hand-off carries K/V blocks by token, and no recurrent state."""
        if self.state_bytes:
            raise NotImplementedError(
                "a model with state-space layers is not handed off: a KvPayload carries K/V blocks and not the"
                " recurrent state that goes with them"
            )
        if self.eva:
            raise NotImplementedError(
                "a cache whose rows are not its tokens is not handed off: a KvPayload carries a block for every"
                " block_size tokens, not a window's rows and the pooled rows behind it"
            )

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
                n = math.ceil(h.cache_len / self.block_size)
                blocks = self._alloc_pressure(n)
                # of a sliding layer's blocks, those the next query's window still touches
                keep_from = self._window_first_block(h.cache_len) if self.window else n
                kept = self._alloc_pressure(n - keep_from, "window") if self.window and blocks is not None else []
                if blocks is None or kept is None:
                    if blocks:
                        self.alloc.release(blocks)
                    return worked  # pool pressure; retry next loop pass
                window_blocks = dict(zip(range(keep_from, n), kept))
                self._handoffs.popleft()
                self._admitting = [h.req]  # visible to drain() until slotted
            with hot.span(
                hot.SERVE_KV_IMPORT, blocks=len(blocks), cache_len=h.cache_len
            ):
                idx = jnp.asarray(np.asarray(blocks, np.int32))
                self.pools = gen.import_blocks(
                    self.pools, idx, h.k, h.v, self._cfg.layer_types and self._cfg.cache_kinds,
                    self._window_ids(window_blocks, n),
                )  # fmt: skip
            seq = list(h.req.prompt) + h.req.generated
            if self.prefix_cache is not None:
                self.prefix_cache.insert(seq[: h.cache_len], blocks, window_blocks)
            slot = free[0]
            self.tables.assign(slot, blocks)
            self.tables.lengths[slot] = h.cache_len
            for b, block in window_blocks.items():
                self.window_tables.assign(slot, b, block)
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
                "kv_blocks_used": self.alloc.used_blocks,
                "kv_blocks_free": self.alloc.free_blocks,
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
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "kv_bytes_per_slot_window": self.kv_bytes_per_slot_window,
                "kv_blocks_window_used": self.window_alloc.used_blocks if self.window else 0,
                "state_bytes_per_slot": self.state_bytes_per_slot,
                "state_bytes": self.state_bytes,
                **self._kv_blocks(),
                "draining": self._draining,
                "failed": self.failed,
            }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        elif self.prefix_cache_off:
            out["prefix_cache_off"] = self.prefix_cache_off
        return out

    def prefix_summary(self, max_entries: int = 128) -> list[str]:
        """Digests of this engine's hottest cached prefixes — published
        on ``/healthz`` for the cache-aware router."""
        if self.prefix_cache is None:
            return []
        return self.prefix_cache.summary(max_entries)

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

    def _alloc_pressure(self, n: int, kind: str = "full") -> Optional[list[int]]:
        """:meth:`BlockAllocator.alloc` from the pool of ``kind`` that spills
        cache-only blocks first: under pool pressure, LRU prefix-cache entries
        are cheaper to reclaim than preempting a live slot."""
        alloc = self.window_alloc if kind == "window" else self.alloc
        blocks = alloc.alloc(n)
        if blocks is None and self.prefix_cache is not None:
            evict = self.prefix_cache.evict_window if kind == "window" else self.prefix_cache.evict
            evict(n - alloc.free_blocks)
            blocks = alloc.alloc(n)
        return blocks

    def _kv_blocks(self) -> dict[str, int]:
        """Blocks the slots hold in each kind of pool (not what the prefix
        cache keeps beside them; those staged for a prompt being fed among
        them), and window blocks given back so far: what the ``serve.decode``
        and ``serve.admit`` spans carry."""
        if self.eva:
            tokens, rows = self._rows_held()
            return {
                "kv_blocks_full": self.eva.held_blocks,
                "kv_blocks_window": self.eva.held_window,
                "kv_blocks_pooled": self.eva.held_pooled,
                "window_blocks_released": self.window_blocks_released,
                "pooled_blocks_promoted": self.pooled_blocks_promoted,
                "cache_tokens_held": tokens,
                "cache_rows_held": rows,
            }
        staged = sum(len(st.staged) for st in self._slots if st is not None)
        return {
            "kv_blocks_full": self.tables.held_blocks,
            "kv_blocks_window": self.window_tables.held_blocks + staged if self.window else 0,
            "window_blocks_released": self.window_blocks_released,
            # what a slot holds beside its blocks whatever its length; not there without a mixer
            **({"state_bytes_per_slot": self.state_bytes_per_slot} if self.state_bytes else {}),
        }

    def _rows_held(self) -> tuple[int, int]:
        """Where rows are not tokens: (the tokens the slots hold, written or in
        flight; the cache rows they hold for them, staged ones among them)."""
        held = [st.cache_len + st.unfetched for st in self._slots if st is not None]
        return sum(held), sum(self.eva.rows(n) for n in held)

    def _release_slot(self, slot: int) -> _SlotState:
        """Empty ``slot``: a reference to each block it holds goes back to the
        block's allocator, those staged for an unfinished prompt too. -> the
        state it held."""
        st = self._slots[slot]
        self._slots[slot] = None
        self.alloc.release(self.tables.release(slot))
        if self.window:
            self.window_alloc.release(self.window_tables.release(slot) + list(st.staged.values()))
            st.staged = {}
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
                self.tables.assign(slot, a.cached_blocks + a.new_blocks)
                self.tables.lengths[slot] = a.cached_tokens
                self._slots[slot] = _SlotState(
                    req=a.req,
                    cache_len=a.cached_tokens,
                    last_tok=0,  # never read: the slot's first step takes its token from the device
                    admit_seq=next(self._admit_counter),
                    feeding=a.toks,
                    staged=a.window_blocks,
                )
            with self._lock:
                self._admitting = []
            self._update_gauges()
            admit_span.set_metadata(
                admitted=len(admitted),
                cached_tokens=sum(a.cached_tokens for a in admitted),
                queue_depth=len(self._waiting),
                kv_bytes_per_token=self.kv_bytes_per_token,
                **self._kv_blocks(),
            )
        return True

    def _plan_admission(self, limit: int) -> list[_Admit]:
        """Under the lock: match prefixes, allocate blocks and take up to
        ``limit`` requests off the head of the queue. -> what to feed."""
        admitted: list[_Admit] = []
        with self._lock:
            for req in list(self._waiting)[:limit]:
                toks = list(req.prompt) + req.generated
                cached_blocks: list[int] = []
                cached_window: dict[int, int] = {}
                cached_tokens = 0
                if self.prefix_cache is not None:
                    # retains the matched blocks on our behalf; never
                    # covers the last token, so a token is left to feed
                    cached_blocks, cached_window, cached_tokens = self.prefix_cache.match_kinds(toks)
                need = math.ceil(len(toks) / self.block_size) - len(cached_blocks)
                if self.eva:  # its staging and its first window's blocks; _ensure_rows brings the rest
                    need = self.eva.pooled_blocks + math.ceil(min(len(toks), self.eva.window) / self.block_size)
                new_blocks = self._alloc_pressure(need)
                # a window block is staged for every new block of the prompt;
                # _chunk_enqueued hands back those below the next chunk's window
                new_window = self._alloc_pressure(need, "window") if self.window and new_blocks is not None else []
                if new_blocks is None or new_window is None:
                    if new_blocks:
                        self.alloc.release(new_blocks)
                    if cached_blocks:
                        self.alloc.release(cached_blocks)
                    if cached_window:
                        self.window_alloc.release(list(cached_window.values()))
                    break  # pool pressure: admit what fits, retry later
                window_blocks = dict(cached_window)
                window_blocks.update({len(cached_blocks) + j: block for j, block in enumerate(new_window)})
                admitted.append(_Admit(req, toks, cached_blocks, cached_tokens, new_blocks, window_blocks))
            for a in admitted:
                self._waiting.remove(a.req)
            # visible to drain(): popped but not yet in a slot
            self._admitting = [a.req for a in admitted]
            obs_metrics.SERVE_QUEUE_DEPTH.set(len(self._waiting))
        return admitted

    def _window_ids(self, window_blocks: dict[int, int], n: int) -> Optional[np.ndarray]:
        """A sequence's ``n`` blocks in the window pools as an array: the trash
        block where none is held. None for a model of one cache kind."""
        if not self.window:
            return None
        ids = np.full((n,), TRASH_BLOCK, np.int32)
        for b, block in window_blocks.items():
            ids[b] = block
        return ids

    def _export_handoff(
        self, req: ServeRequest, toks: list[int], blocks: list[int], window_blocks: dict[int, int]
    ) -> KvPayload:
        """Snapshot the prefilled K/V blocks for transfer to a decode
        replica (the ``prefill_only`` completion path)."""
        self._refuse_handoff()
        k, v = gen.export_blocks(
            self.pools, np.asarray(blocks, np.int32), self._cfg.layer_types and self._cfg.cache_kinds,
            self._window_ids(window_blocks, len(blocks)),
        )  # fmt: skip
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
            k=np.asarray(k),
            v=np.asarray(v),
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

    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side copy of one physical block across all layers (of the
        full kind: a block being written in a window pool is never a cached one,
        the cache adopts whole blocks only)."""
        with hot.span(hot.SERVE_COW_COPY):
            copied = jax.tree.map(lambda p: p.at[:, dst].set(p[:, src]), self._pools_of("full"))
            self.pools = {**self.pools, **({"full": copied} if self.window else copied)}

    def _ensure_capacity(self, slot: int, write_pos: int) -> bool:
        """Make sure ``slot`` holds a *writable* block for ``write_pos``:
        grows the table lazily, copy-on-writes a shared tail block
        (another holder — cache or sibling slot — still reads it), and
        preempts the youngest slot under pool pressure. False if ``slot``
        itself was preempted away."""
        if self.eva:
            return self._ensure_rows(slot, write_pos)
        idx = write_pos // self.block_size
        while True:
            have = len(self.tables.blocks_of(slot))
            if have >= idx + 1:
                tail = self.tables.blocks_of(slot)[idx]
                if not self.alloc.is_shared(tail):
                    return self._ensure_window(slot, write_pos) if self.window else True
                fresh = self._alloc_pressure(1)
                if fresh is not None:
                    self._copy_block(tail, fresh[0])
                    self.tables.replace_block(slot, idx, fresh[0])
                    self.alloc.release([tail])
                    obs_metrics.SERVE_COW_COPIES.inc()
                    continue  # re-check the (fresh, unshared) tail
            else:
                blocks = self._alloc_pressure(idx + 1 - have)
                if blocks is not None:
                    self.tables.assign(slot, blocks)
                    continue  # re-check the (fresh, unshared) tail
            self._preempt_youngest()
            if self._slots[slot] is None:
                return False  # preempted ourselves: nothing else to evict

    def _ensure_window(self, slot: int, write_pos: int) -> bool:
        """The sliding layers' side of :meth:`_ensure_capacity`: hand back the
        blocks of ``slot`` whose every position is below the window of the
        query at ``write_pos`` (every later query's window lies higher), then
        make sure the ring holds a block for ``write_pos``. False if ``slot``
        itself was preempted away for it."""
        tables = self.window_tables
        below = tables.release_below(slot, self._window_first_block(write_pos))
        self.window_alloc.release(below)
        self.window_blocks_released += len(below)
        idx = write_pos // self.block_size
        while not tables.has(slot, idx):
            block = self._alloc_pressure(1, "window")
            if block is not None:
                tables.assign(slot, idx, block[0])
                break
            self._preempt_youngest()
            if self._slots[slot] is None:
                return False
        return True

    def _ensure_rows(self, slot: int, write_pos: int) -> bool:
        """:meth:`_ensure_capacity` where rows are not tokens (EVA attention):
        make ``slot`` writable up to ``write_pos``, which lies in the window its
        table is laid out for or starts the next. Then the window before has
        ended, whatever step wrote its last row still in flight: its staged rows
        go into the table, its blocks go back to the pool, all of them, and the
        new window is given staging and a first block. False if ``slot`` itself
        was preempted away for them."""
        tables = self.eva
        if write_pos // tables.window > tables.window_of(slot):
            released = tables.turn(slot)
            self.alloc.release(released)
            self.window_blocks_released += len(released)
            self.pooled_blocks_promoted += tables.pooled_blocks
        while short := tables.short(slot, write_pos):
            blocks = self._alloc_pressure(short)
            if blocks is not None:
                tables.assign(slot, blocks)
                break
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
        n = min(self.chunk_width, len(st.feeding) - st.cache_len)
        if self.eva:  # a chunk stops where its window ends: the table is laid anew there
            n = min(n, self.eva.window - st.cache_len % self.eva.window)
        return slot, st, n

    def _chunk_enqueued(self, slot: int, st: _SlotState, n: int) -> bool:
        """The step just enqueued carries the next ``n`` tokens of ``slot``'s
        prompt. Their blocks are valid for every later program (device order),
        so the full ones are indexed now; of the sliding layers' staged blocks,
        those below the window of the next chunk's first query go back. Behind
        the prompt's last chunk the ring takes what is still in reach and the
        slot decodes from the next step on. -> whether that chunk was the last."""
        st.cache_len += n
        self.chunk_steps += 1
        self.prefill_tokens += n
        self.prefill_padded_tokens += self.chunk_width
        if self.prefix_cache is not None:
            self.prefix_cache.insert(st.feeding[: st.cache_len], self.tables.blocks_of(slot), st.staged)
        if self.window:
            keep_from = self._window_first_block(st.cache_len)
            below = [st.staged.pop(b) for b in sorted(st.staged) if b < keep_from]
            self.window_alloc.release(below)
            self.window_blocks_released += len(below)
        last = st.cache_len == len(st.feeding)
        if last:
            for b, block in st.staged.items():
                self.window_tables.assign(slot, b, block)
            st.feeding, st.staged = None, {}
            # as a decode step leaves it: the step that writes the sequence's
            # last row is in flight, and its token is the next step's input
            st.cache_len -= 1
            st.unfetched = 1
        self.tables.lengths[slot] = st.cache_len
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
                        self._ensure_capacity(slot, st.cache_len + st.unfetched)
                # where rows are not tokens a prompt's blocks come as its chunks reach them: now, ahead
                # of the tables' copy below (a request preempted away for them leaves the next to be fed)
                while self.eva and (chunk := self._next_chunk()) is not None:
                    if self._ensure_rows(chunk[0], chunk[1].cache_len + chunk[2] - 1):
                        break

                tokens = np.zeros((self.max_slots,), np.int32)
                positions = np.zeros((self.max_slots,), np.int32)
                seeds = np.zeros((self.max_slots,), np.int32)
                temps = np.zeros((self.max_slots,), np.float32)
                # a copy: the loop goes on to change the table while the step
                # is in flight, and the CPU backend reads a numpy array where
                # it lies
                tables = self.tables.tables.copy()
                window_tables = self.window_tables.tables.copy() if self.window else None
                if self.eva:  # in the window tables' place: a slot's staging blocks, where the programs pool what a step fills
                    window_tables = self.eva.stage.copy()
                # a mixer's state row a slot: its own (slot + 1) while it decodes, else the
                # trash row, so that a slot being fed is written by its chunk alone
                state_rows = np.zeros((self.max_slots,), np.int32)
                stepping: list[tuple[int, _SlotState]] = []
                rows_read = 0  # EVA: cache rows this step's decode attention reads, a layer
                for slot, st in enumerate(self._slots):
                    if st is None:
                        continue
                    if not st.more_to_decode:
                        # its prompt is still being fed, or its last token is
                        # in flight. The program writes a row for every slot:
                        # this one's goes where an empty slot's does
                        tables[slot] = TRASH_BLOCK
                        if window_tables is not None:
                            window_tables[slot] = TRASH_BLOCK
                        continue
                    tokens[slot] = _FROM_DEVICE if st.unfetched else st.last_tok
                    positions[slot] = st.cache_len + st.unfetched
                    seeds[slot] = _seed32(st.req)
                    temps[slot] = st.req.temperature
                    state_rows[slot] = slot + 1
                    stepping.append((slot, st))
                    if self.eva:
                        rows_read += self.eva.coord(int(positions[slot])) + 1

                chunk = self._next_chunk()
                if chunk is not None:
                    c_slot, c_st, n = chunk
                    start = c_st.cache_len
                    chunk_tokens = np.zeros((self.chunk_width,), np.int32)
                    chunk_tokens[:n] = c_st.feeding[start : start + n]
                    ends = start + n == len(c_st.feeding)
                    at = np.asarray([start, n, c_slot if ends else -1], np.int32)
                    seeds = np.append(seeds, _seed32(c_st.req))
                    temps = np.append(temps, np.float32(c_st.req.temperature))
                    # the request's own table (the copy above sends its slot's decode row to the
                    # trash block); its staged window blocks lie as the full ones do, block b at entry b
                    chunk_full = self.tables.tables[c_slot : c_slot + 1].copy()
                    chunk_window = self.eva.stage[c_slot].copy() if self.eva else self._window_ids(c_st.staged, self.blocks_per_slot)

            enqueued = None
            step_span.set_metadata(**self._kv_blocks())  # as the step is dispatched
            if self.eva:
                step_span.set_metadata(cache_rows_read=rows_read)
            if stepping or chunk is not None:
                with hot.span(hot.SERVE_DECODE_DISPATCH):
                    host_tokens = jnp.asarray(tokens)
                    args = (
                        self._params,
                        host_tokens,
                        # with no step in flight every slot reads the host's token
                        host_tokens if before is None else before.nxt,
                        jnp.asarray(positions),
                        self._tables_arg(tables, window_tables, state_rows),
                        self.pools,
                        jnp.asarray(seeds),
                        jnp.asarray(temps),
                    )
                    first_of = None
                    if chunk is None:
                        nxt, self.pools = self._decode(*args)
                    else:
                        nxt, self.pools = self._decode_chunk(
                            *args,
                            jnp.asarray(chunk_tokens),
                            jnp.asarray(at),
                            self._tables_arg(chunk_full, None if chunk_window is None else chunk_window[None], [c_slot + 1]),
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
            self.tables.lengths[slot] = st.cache_len
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
                blocks = self.tables.blocks_of(slot)
                window_blocks = self.window_tables.blocks_of(slot) if self.window else {}
                seq = list(req.prompt) + req.generated
                if not done:
                    # a request its first token already finishes never needs
                    # the decode side: no handoff, the caller reads .tokens
                    req.handoff = self._export_handoff(req, seq[: st.cache_len], blocks, window_blocks)
                if self.prefix_cache is not None:
                    # index the completed sequence's full blocks (cache
                    # holds cache_len tokens: everything but the final
                    # sampled token) before dropping the slot's refs
                    self.prefix_cache.insert(seq[: st.cache_len], blocks, window_blocks)
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
        obs_metrics.SERVE_KV_BLOCKS_USED.set(self.alloc.used_blocks)
        if self.eva:
            obs_metrics.SERVE_CACHE_ROWS_HELD.set(self._rows_held()[1])


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
