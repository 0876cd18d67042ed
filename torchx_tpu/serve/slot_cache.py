"""What a slot holds in the cache, behind one object a kind of cache.

:class:`~torchx_tpu.serve.engine.ServeEngine` keeps the queue, the slots' request state and the pipeline of steps. What
depends on how a slot's cache looks it asks of the object :func:`slot_cache` picks from the configuration, once: the
pools on the device with the tables and allocators over them, a request's blocks at admission and at a hand-off, a slot
made writable at a position (one attempt: the engine preempts and asks again where a pool is short), the tables a step's
programs take (:func:`torchx_tpu.models.generate._table_of` has the format), what the spans and ``stats()`` report, why
a kind has no prefix reuse or no hand-off. The next kind is a class here and a line of :func:`slot_cache`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama
from torchx_tpu.obs import hot
from torchx_tpu.obs import metrics as obs_metrics
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.serve.kv_pool import BlockAllocator, EvaTables, SlotTables, WindowTables, window_ring
from torchx_tpu.serve.prefix_cache import PrefixCache


@dataclasses.dataclass
class Plan:
    """A sequence's blocks before it has a slot: its cached prefix's, retained on its behalf, and fresh ones behind."""

    blocks: list[int]
    window: dict[int, int] = dataclasses.field(default_factory=dict)  # in the sliding layers' pools, where there are any: block of the sequence -> block
    cached_tokens: int = 0  # block-aligned prefix length served from the prefix cache


def _row_bytes(pools) -> int:  # noqa: ANN001
    """A token's bytes over the layers of a pool tree."""
    return sum(p.shape[0] * math.prod(p.shape[3:]) * p.dtype.itemsize for p in jax.tree.leaves(pools))


def _trashed(tables: np.ndarray, rows: list[int]) -> np.ndarray:
    """``tables`` with ``rows`` sent to the trash block. A copy: the loop goes on to change the table while the step
    is in flight, and the CPU backend reads a numpy array where it lies."""
    out = tables.copy()
    for row in rows:
        out[row] = TRASH_BLOCK
    return out


class PagedCache:
    """One paged pool under one block table a slot: dense, MoE and latent models, and what every kind shares. A slot
    holds the blocks its tokens occupy, grown lazily; cached blocks are shared by refcount (a shared tail block about
    to be written is copy-on-write copied first), and under pool pressure cache-only blocks are evicted before the
    engine preempts a live slot."""

    no_prefix_cache: Optional[str] = None  # why this kind reuses no prefix; None where it does
    no_handoff: Optional[str] = None  # why it is not handed off; None where it is
    beside: tuple[str, ...] = ()  # the pools that lie beside those the slots' block table pages
    num_window_blocks = kv_bytes_per_slot_window = 0
    _prefix_pools: dict = {}  # what the prefix cache is told of the pools beside the table's
    state_bytes_per_slot = state_bytes = 0  # recurrent state a slot holds whatever its length, and over all rows

    def __init__(self, cfg: llama.LlamaConfig, tables=None, *, max_slots: int, block_size: int,
                 num_blocks: Optional[int], prefix_cache: bool, prefix_cache_reserve: float) -> None:  # noqa: ANN001  # fmt: skip
        self.max_slots, self.block_size = max_slots, block_size
        self.tables = SlotTables(max_slots, math.ceil(cfg.max_seq / block_size)) if tables is None else tables
        self.blocks_per_slot = self.tables.blocks_per_slot
        if num_blocks is None:  # half a table a slot, beside what a slot keeps for as long as its sequence lasts
            num_blocks = 1 + max_slots * (self.tables.kept_blocks + max(1, self.blocks_per_slot // 2))
        if num_blocks < self.tables.most_blocks + 1:
            raise ValueError(f"num_blocks={num_blocks} cannot hold one max_seq sequence ({self.tables.most_blocks} blocks + trash)")
        self.num_blocks = num_blocks
        self.pools = gen.init_kv_pools(cfg, num_blocks, block_size, self.num_window_blocks, max_slots)
        self._layer_kinds = cfg.layer_types and cfg.cache_kinds  # what tells a hand-off's layers apart
        self.alloc = BlockAllocator(num_blocks)
        #: bytes a further token of context holds: every layer's, but for the sliding layers, whose cost a slot is constant
        self.kv_bytes_per_token = _row_bytes(self._paged())
        self.window_blocks_released = 0  # window blocks slots gave back as their windows moved on (rows: ended)
        self.prefix_cache: Optional[PrefixCache] = None
        self.prefix_cache_off = self.no_prefix_cache if prefix_cache else None  # why there is none though one was asked for
        if prefix_cache and not self.no_prefix_cache:
            cap = max(1, int(prefix_cache_reserve * num_blocks)) if prefix_cache_reserve > 0 else None
            self.prefix_cache = PrefixCache(self.alloc, block_size, max_blocks=cap, **self._prefix_pools)

    def _window_of(self, slot: int) -> Optional[dict[int, int]]:
        return None  # what ``slot`` holds in pools of sliding layers, block of the sequence -> block: there are no such pools

    def _window_ids(self, window: Optional[dict[int, int]], n: int) -> Optional[np.ndarray]:
        return None  # such blocks as the array of ``n`` ids a hand-off takes

    def _give_back(self, plan: Plan) -> None:
        self.alloc.release(plan.blocks)

    def _paged(self) -> dict:
        """The pools the slots' table pages, as the part of the tree that merges back into it."""
        return {name: pool for name, pool in self.pools.items() if name not in self.beside}

    def _take(self, n: int) -> Optional[list[int]]:
        return self.alloc.alloc(n, self.prefix_cache and self.prefix_cache.evict)

    def export(self, slot: int) -> tuple[np.ndarray, np.ndarray]:
        """``slot``'s blocks as the ``(k, v)`` a hand-off carries (:func:`generate.export_blocks`)."""
        blocks = self.tables.blocks_of(slot)
        window_ids = self._window_ids(self._window_of(slot), len(blocks))
        k, v = gen.export_blocks(self.pools, np.asarray(blocks, np.int32), self._layer_kinds, window_ids)
        return np.asarray(k), np.asarray(v)

    def import_blocks(self, plan: Plan, k: np.ndarray, v: np.ndarray) -> None:
        """Scatter a hand-off's ``(k, v)`` into the blocks planned for it."""
        idx, window_ids = jnp.asarray(np.asarray(plan.blocks, np.int32)), self._window_ids(plan.window, len(plan.blocks))
        self.pools = gen.import_blocks(self.pools, idx, k, v, self._layer_kinds, window_ids)

    def plan(self, toks: Sequence[int]) -> Optional[Plan]:
        """A waiting request's blocks: its longest cached prefix's (retained on its behalf; never covers the last
        token, so a token is left to feed) and fresh ones for the rest. None, and nothing held, where a pool is short."""
        plan = Plan(*self.prefix_cache.match_kinds(toks)) if self.prefix_cache is not None else Plan([])
        return self._allocate(plan, math.ceil(len(toks) / self.block_size) - len(plan.blocks), plan.cached_tokens)

    def plan_handoff(self, cache_len: int) -> Optional[Plan]:
        """Blocks for a transferred prefill of ``cache_len`` tokens, or None."""
        return self._allocate(Plan([]), math.ceil(cache_len / self.block_size), cache_len)

    def _allocate(self, plan: Plan, n: int, next_pos: int) -> Optional[Plan]:
        """``n`` fresh blocks behind ``plan``'s, for a sequence whose next query stands at ``next_pos``; all or nothing."""
        fresh = self._take(n)
        if fresh is None:
            self._give_back(plan)
            return None
        plan.blocks.extend(fresh)
        return plan

    def place(self, slot: int, plan: Plan) -> None:
        """Give ``slot`` a plan's blocks: a prompt's, fed from ``plan.cached_tokens`` on, or a hand-off's (:meth:`fed` next)."""
        self.tables.assign(slot, plan.blocks)

    def chunk_tokens(self, fed: int, n: int) -> int:
        """How many of the ``n`` tokens that follow the ``fed`` ones a chunk may carry."""
        return n

    def fed(self, slot: int, toks: Sequence[int], n: int, last: bool) -> None:
        """``slot``'s cache holds the first ``n`` of ``toks``, or will when what is enqueued has run (``last``: and is
        fed no more: a prompt's last chunk, a hand-off, a finished sequence). Their blocks are valid for every later
        program (device order), so the full ones are offered to the prefix cache now."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(toks[:n], self.tables.blocks_of(slot), self._window_of(slot))

    def release(self, slot: int) -> None:
        """Empty ``slot``: a reference to each block it holds goes back to the block's allocator."""
        self.alloc.release(self.tables.release(slot))

    def grow(self, slot: int, write_pos: int) -> bool:
        """Make sure ``slot`` holds a *writable* block for ``write_pos``: grows the table lazily and copy-on-writes a
        shared tail block (another holder, cache or sibling slot, still reads it). False where a pool is short: the same
        call does the rest once there is room. Asked for a prompt's next chunk too: it holds its blocks from admission on."""
        idx = write_pos // self.block_size
        held = self.tables.blocks_of(slot)
        if len(held) <= idx:
            blocks = self._take(idx + 1 - len(held))
            if blocks is None:
                return False
            self.tables.assign(slot, blocks)
        elif self.alloc.is_shared(held[idx]):
            fresh = self._take(1)
            if fresh is None:
                return False
            # on the device, across all layers (of the table's pools: a block being written in a window pool is never a
            # cached one, the cache adopts whole blocks only)
            with hot.span(hot.SERVE_COW_COPY):
                copied = jax.tree.map(lambda p: p.at[:, fresh[0]].set(p[:, held[idx]]), self._paged())
                self.pools = {**self.pools, **copied}
            self.tables.replace_block(slot, idx, fresh[0])
            self.alloc.release([held[idx]])
            obs_metrics.SERVE_COW_COPIES.inc()
        return True

    def step_tables(self, stepping: list[int], parked: list[int]):  # noqa: ANN201
        """What a step's decode part takes as ``tables``, in numpy: the slots in ``stepping`` decode; those in
        ``parked`` hold a prompt still being fed or a last token in flight, and the program, which writes a row
        for every slot, writes theirs where an empty slot's goes."""
        return _trashed(self.tables.tables, parked)

    def chunk_tables(self, slot: int):  # noqa: ANN201
        """What a step's chunk takes: the request's own table (:meth:`step_tables` sends its slot's row to the trash)."""
        return self.tables.tables[slot : slot + 1].copy()

    def span_attrs(self, held: Iterable[int], reading: Optional[Iterable[int]] = None) -> dict[str, int]:
        """What the ``serve.decode`` and ``serve.admit`` spans carry: blocks the slots hold in each kind of pool (those
        staged for a prompt among them; not what the prefix cache keeps beside them), window blocks given back so far.
        ``held``: the tokens each occupied slot holds, written or in flight; ``reading``: where a step's decode rows read."""
        return {"kv_blocks_full": self.tables.held_blocks, "kv_blocks_window": 0, "window_blocks_released": self.window_blocks_released}

    def stats(self, held: Iterable[int]) -> dict:
        """The cache's part of ``ServeEngine.stats()``."""
        sizes = {"kv_bytes_per_token": self.kv_bytes_per_token, "kv_bytes_per_slot_window": self.kv_bytes_per_slot_window,
                 "kv_blocks_window_used": 0, "state_bytes_per_slot": self.state_bytes_per_slot, "state_bytes": self.state_bytes}  # fmt: skip
        return {**sizes, **self.span_attrs(held)}

    def update_gauges(self, held: Iterable[int]) -> None:
        """Set the ``tpx_serve_*`` gauges that are the cache's."""
        obs_metrics.SERVE_KV_BLOCKS_USED.set(self.alloc.used_blocks)


class RingCache(PagedCache):
    """A full pool beside a ring: a model that mixes sliding and full attention layers (``cfg.layer_types``) keeps a
    pool a kind. The full layers' blocks are paged by a slot's table; in the sliding layers' pools a slot holds a ring
    (:class:`~torchx_tpu.serve.kv_pool.WindowTables`), and the oldest block goes back to that pool's allocator as soon
    as every position in it is below every future query's window: while decoding, and between two chunks of a prompt,
    whose blocks are staged block ``b`` at entry ``b`` until its last chunk hands those still in reach to the ring."""

    beside = ("window",)

    def __init__(self, cfg: llama.LlamaConfig, *, num_window_blocks: Optional[int], max_prefill_batch: int, **shared) -> None:  # noqa: ANN003
        max_slots, block_size = shared["max_slots"], shared["block_size"]
        self.window = cfg.sliding_window
        self.window_ring = window_ring(self.window, block_size)  # the entries of a slot's ring table
        if num_window_blocks is None:  # every slot's ring, and the prompts being fed staged whole
            num_window_blocks = 1 + max_slots * self.window_ring + max_prefill_batch * math.ceil(cfg.max_seq / block_size)
        self.num_window_blocks = num_window_blocks
        self.window_alloc = BlockAllocator(num_window_blocks)
        self.window_tables = WindowTables(max_slots, self.window_ring)
        # window_back: the blocks ahead of a suffix that its first query's window reaches into
        self._prefix_pools = dict(window_alloc=self.window_alloc, window_back=math.ceil((self.window - 1) / block_size))
        # the blocks staged for the prompt a slot is fed: block of the sequence -> block; the ring's after the last chunk
        self._staged: list[dict[int, int]] = [{} for _ in range(max_slots)]
        super().__init__(cfg, **shared)
        self.kv_bytes_per_slot_window = self.window_ring * block_size * _row_bytes(self.pools["window"])

    def _first_block(self, query_pos: int) -> int:
        """The lowest block of a sequence that the window of a query at ``query_pos``, and so of every later one, still touches."""
        return max(0, query_pos - self.window + 1) // self.block_size

    def _take_window(self, n: int) -> Optional[list[int]]:
        return self.window_alloc.alloc(n, self.prefix_cache and self.prefix_cache.evict_window)

    def _window_of(self, slot: int) -> dict[int, int]:
        return self._staged[slot] or self.window_tables.blocks_of(slot)

    def _window_ids(self, window: dict[int, int], n: int) -> np.ndarray:
        """A sequence's ``n`` blocks in the window pools as an array: the trash block where none is held."""
        ids = np.full((n,), TRASH_BLOCK, np.int32)
        for b, block in window.items():
            ids[b] = block
        return ids

    def _give_back(self, plan: Plan) -> None:
        super()._give_back(plan)
        self.window_alloc.release(list(plan.window.values()))

    def _allocate(self, plan: Plan, n: int, next_pos: int) -> Optional[Plan]:
        # a window block for every fresh block that the next query's window still touches: all of a prompt's (each is
        # staged; fed() hands back those below the next chunk's window), of a hand-off's those still in reach
        first = max(len(plan.blocks), self._first_block(next_pos))
        if super()._allocate(plan, n, next_pos) is None:
            return None
        fresh = self._take_window(len(plan.blocks) - first)
        if fresh is None:
            self._give_back(plan)
            return None
        plan.window.update(zip(range(first, len(plan.blocks)), fresh))
        return plan

    def place(self, slot: int, plan: Plan) -> None:
        super().place(slot, plan)
        self._staged[slot] = plan.window

    def fed(self, slot: int, toks: Sequence[int], n: int, last: bool) -> None:
        """Staged blocks below the window of the next chunk's first query go back; behind the last the ring takes the rest."""
        super().fed(slot, toks, n, last)
        staged = self._staged[slot]
        below = [staged.pop(b) for b in sorted(staged) if b < self._first_block(n)]
        self.window_alloc.release(below)
        self.window_blocks_released += len(below)
        if last:
            for b, block in staged.items():
                self.window_tables.assign(slot, b, block)
            self._staged[slot] = {}

    def release(self, slot: int) -> None:
        """Both pools' blocks go back, those staged for an unfinished prompt too."""
        super().release(slot)
        self.window_alloc.release(self.window_tables.release(slot) + list(self._staged[slot].values()))
        self._staged[slot] = {}

    def grow(self, slot: int, write_pos: int) -> bool:
        """And the sliding layers' side: hand back the blocks of ``slot`` whose every position is below the window of the
        query at ``write_pos`` (every later query's window lies higher), then have the ring hold a block for it."""
        if not super().grow(slot, write_pos):
            return False
        if self._staged[slot]:  # mid-prompt: its blocks here are the staged ones, and fed() moves them on
            return True
        below = self.window_tables.release_below(slot, self._first_block(write_pos))
        self.window_alloc.release(below)
        self.window_blocks_released += len(below)
        idx = write_pos // self.block_size
        if not self.window_tables.has(slot, idx):
            block = self._take_window(1)
            if block is None:
                return False
            self.window_tables.assign(slot, idx, block[0])
        return True

    def step_tables(self, stepping: list[int], parked: list[int]):  # noqa: ANN201
        return {"full": super().step_tables(stepping, parked), "window": _trashed(self.window_tables.tables, parked)}

    def chunk_tables(self, slot: int):  # noqa: ANN201
        # the staged window blocks lie as the full ones do, block b at entry b
        return {"full": super().chunk_tables(slot), "window": self._window_ids(self._staged[slot], self.blocks_per_slot)[None]}

    def span_attrs(self, held: Iterable[int], reading: Optional[Iterable[int]] = None) -> dict[str, int]:
        staged = sum(len(s) for s in self._staged)
        return {**super().span_attrs(held), "kv_blocks_window": self.window_tables.held_blocks + staged}

    def stats(self, held: Iterable[int]) -> dict:
        return {**super().stats(held), "kv_blocks_window_used": self.window_alloc.used_blocks}


class StateCache(PagedCache):
    """A paged pool beside a store of rows a slot: where the layers have a state-space mixer (``cfg.ssm_heads``), slot
    ``i`` owns row ``i + 1`` of the mixer's store (:func:`torchx_tpu.models.ssm.init_store`; row 0 is the trash row, as
    block 0 is the trash block), addressed by slot and not by position. **A row has one writer a step**: a step's
    decode part addresses the trash row for every slot that is not decoding (empty, or mid-prompt), so a slot whose
    prompt is being fed is written by its chunk alone. A chunk that starts at position 0 starts from zeros inside the
    program, which is the whole of a reset: for a new tenant, and for a preempted request, fed again from position 0.
    The step in flight behind an EOS moves a row on that nobody reads again. State is not yet cached or handed off."""

    store = "ssm"  # where the rows lie in the pools
    beside = (store,)
    no_prefix_cache = "recurrent state: the cache indexes K/V blocks alone, and a hit would hand a request K/V without the state that goes with it"
    no_handoff = "a model with state-space layers is not handed off: a KvPayload carries K/V blocks and not the recurrent state that goes with them"

    def __init__(self, cfg: llama.LlamaConfig, **shared) -> None:  # noqa: ANN003
        super().__init__(cfg, **shared)
        store = jax.tree.leaves(self.pools[self.store])
        self.state_bytes_per_slot = sum(p.nbytes // p.shape[1] for p in store)
        self.state_bytes = sum(p.nbytes for p in store)

    def step_tables(self, stepping: list[int], parked: list[int]):  # noqa: ANN201
        rows = np.zeros((self.max_slots,), np.int32)  # a slot's own row (slot + 1) while it decodes, else the trash row
        for slot in stepping:
            rows[slot] = slot + 1
        return {"full": super().step_tables(stepping, parked), "state": rows}

    def chunk_tables(self, slot: int):  # noqa: ANN201
        return {"full": super().chunk_tables(slot), "state": np.asarray([slot + 1], np.int32)}

    def span_attrs(self, held: Iterable[int], reading: Optional[Iterable[int]] = None) -> dict[str, int]:
        # what a slot holds beside its blocks whatever its length; not there without a mixer
        return {**super().span_attrs(held), "state_bytes_per_slot": self.state_bytes_per_slot}


class LinearStateCache(StateCache):
    """The same where some layers are linear (``"linear"`` in ``cfg.layer_types``: a Gated DeltaNet mixer,
    :mod:`torchx_tpu.models.gdn`, in attention's place): those layers own a row of the store a slot and **no** blocks,
    so the pool under the slots' one table is the attending layers' alone, and a token's bytes count those. A prefix
    hit would bring K/V for the attending layers and no state for the linear ones."""

    store = "gdn"
    beside = (store,)


class RowsCache(PagedCache):
    """A cache whose rows are not its tokens: under EVA attention (:mod:`torchx_tpu.models.eva`) a slot holds the
    blocks of its current window, the pooled rows of every window behind it and staging blocks for the current window's,
    all in the one pool (:class:`~torchx_tpu.serve.kv_pool.EvaTables` has the layout). The programs take a slot's
    position (roped, and what the sampling key is folded from) and work out its cache coordinate themselves. When a
    write starts a new window the host turns the table with the step that wrote the window's last row still in flight
    (device order keeps its blocks its own until it has run). A chunk of a prompt stops where a window ends, and a prompt
    is given the blocks of its first window at admission and the rest as its chunks reach them. Admission, pressure and
    the spans reckon in the rows held. Neither the prefix cache nor a hand-off indexes a cache by anything but tokens yet."""

    no_prefix_cache = ("a cache whose rows are not its tokens: the prefix cache indexes a block by the tokens it holds, and a"
                       " window's blocks are given back and its pooled rows laid out anew as the sequence grows")  # fmt: skip
    no_handoff = ("a cache whose rows are not its tokens is not handed off: a KvPayload carries a block for every block_size"
                  " tokens, not a window's rows and the pooled rows behind it")  # fmt: skip

    def __init__(self, cfg: llama.LlamaConfig, tables: EvaTables, **shared) -> None:  # noqa: ANN003
        super().__init__(cfg, tables, **shared)
        # a row's bytes while the token is in its window; for ever after, its share of a pooled row
        self.kv_bytes_per_token //= tables.chunk
        self.pooled_blocks_promoted = 0  # staging blocks moved into a table as their window ended

    def plan(self, toks: Sequence[int]) -> Optional[Plan]:
        # a prompt's staging and its first window's blocks; grow() brings the rest as its chunks reach them
        return self._allocate(Plan([]), self.tables.pooled_blocks + math.ceil(min(len(toks), self.tables.window) / self.block_size), 0)

    def chunk_tokens(self, fed: int, n: int) -> int:
        return min(n, self.tables.window - fed % self.tables.window)  # a chunk stops where its window ends: the table is laid anew there

    def grow(self, slot: int, write_pos: int) -> bool:
        """Make ``slot`` writable up to ``write_pos``, which lies in the window its table is laid out for or starts the
        next. Then the window before has ended: its staged rows go into the table, its blocks go back to the pool, all
        of them, and the new window is given staging and a first block."""
        tables = self.tables
        if write_pos // tables.window > tables.window_of(slot):
            released = tables.turn(slot)
            self.alloc.release(released)
            self.window_blocks_released += len(released)
            self.pooled_blocks_promoted += tables.pooled_blocks
        if short := tables.short(slot, write_pos):
            blocks = self._take(short)
            if blocks is None:
                return False
            tables.assign(slot, blocks)
        return True

    def step_tables(self, stepping: list[int], parked: list[int]):  # noqa: ANN201
        # beside the table a slot's staging blocks, where the programs pool what a step fills
        return {"full": super().step_tables(stepping, parked), "stage": _trashed(self.tables.stage, parked)}

    def chunk_tables(self, slot: int):  # noqa: ANN201
        return {"full": super().chunk_tables(slot), "stage": self.tables.stage[slot : slot + 1].copy()}

    def span_attrs(self, held: Iterable[int], reading: Optional[Iterable[int]] = None) -> dict[str, int]:
        held = list(held)  # the tokens the slots hold; below, the cache rows they hold for them, staged ones among them
        out = {
            "kv_blocks_full": self.tables.held_blocks,
            "kv_blocks_window": self.tables.held_window,
            "kv_blocks_pooled": self.tables.held_pooled,
            "window_blocks_released": self.window_blocks_released,
            "pooled_blocks_promoted": self.pooled_blocks_promoted,
            "cache_tokens_held": sum(held),
            "cache_rows_held": sum(self.tables.rows(n) for n in held),
        }
        if reading is not None:  # cache rows this step's decode attention reads, a layer
            out["cache_rows_read"] = sum(self.tables.coord(position) + 1 for position in reading)
        return out

    def update_gauges(self, held: Iterable[int]) -> None:
        super().update_gauges(held)
        obs_metrics.SERVE_CACHE_ROWS_HELD.set(sum(self.tables.rows(n) for n in held))


def slot_cache(cfg: llama.LlamaConfig, *, num_window_blocks: Optional[int], max_prefill_batch: int, **shared) -> PagedCache:  # noqa: ANN003
    """The cache of the kind ``cfg`` keeps: the one place in ``serve/`` that asks a configuration which that is.
    ``shared``: what every kind takes (:class:`PagedCache`'s keywords)."""
    if cfg.eva_window:
        tables = EvaTables(shared["max_slots"], cfg.max_seq, cfg.eva_window, cfg.eva_chunk, shared["block_size"])
        return RowsCache(cfg, tables, **shared)
    if cfg.layers_of("window"):
        return RingCache(cfg, num_window_blocks=num_window_blocks, max_prefill_batch=max_prefill_batch, **shared)
    if cfg.ssm_heads or cfg.layers_of("state"):
        return (StateCache if cfg.ssm_heads else LinearStateCache)(cfg, **shared)
    return PagedCache(cfg, **shared)
