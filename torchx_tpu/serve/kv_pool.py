"""Paged KV-cache planning and block allocation (host side).

The dense decode path costs ``L * 2 * max_seq * kvh * hd`` bytes per
sequence regardless of how many tokens the request actually produces, so
concurrency is bounded by worst-case ``max_seq``. Here KV memory is one
fixed pool of ``num_blocks`` blocks of ``block_size`` tokens shared by
every active slot; a slot holds only the blocks its tokens occupy, so the
same HBM budget admits far more concurrent sequences (vLLM's central
observation, applied to the TPU serving path).

Nothing here runs on device: :func:`plan_pool` does the analytic HBM
sizing — same style as ``parallel/aot_fit.model_state_bytes_per_device``,
whose budget constants it reuses — and :class:`BlockAllocator` +
:class:`SlotTables` manage physical blocks and per-slot block tables as
plain numpy, feeding the jitted step functions in
:mod:`torchx_tpu.serve.engine` as ordinary array arguments.

Block 0 is the *trash block* (``ops.paged_attention.TRASH_BLOCK``): never
allocated, the target of every unassigned table entry, so inactive slots
in the fixed-shape step harmlessly read/write it under the length mask.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque

import numpy as np

from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.parallel.aot_fit import DEFAULT_HEADROOM, GIB, V5P_HBM_BYTES

__all__ = [
    "PoolPlan",
    "plan_pool",
    "BlockAllocator",
    "SlotTables",
    "WindowTables",
    "EvaTables",
    "window_ring",
]


@dataclasses.dataclass(frozen=True)
class PoolPlan:
    """Resolved geometry of a paged KV pool for one model config.

    ``kv_budget_bytes`` is HBM after headroom and parameters;
    ``dense_slots`` is how many sequences the *dense* ``[max_seq]`` cache
    would fit in the same budget — the bench's occupancy comparison.
    """

    num_blocks: int
    block_size: int
    blocks_per_slot: int
    max_slots: int
    kv_bytes: int
    kv_budget_bytes: int
    dense_slots: int
    #: a stack with sliding layers (``cfg.layer_types``): the blocks of the
    #: window pools, and the entries of a slot's ring table there
    num_window_blocks: int = 0
    window_ring: int = 0

    @property
    def pool_tokens(self) -> int:
        """Total KV token capacity (excluding the trash block)."""
        return (self.num_blocks - 1) * self.block_size

    def occupancy_report(self) -> dict:
        """Paged-vs-dense concurrency at the same HBM budget, as a dict
        (serialised into the serving bench's JSON output).

        ``kv_bytes_gib`` is the actual pool footprint
        (``num_blocks * block_bytes``); the block grid rarely tiles the
        budget exactly, so the unusable remainder is reported separately
        as ``kv_slack_gib`` rather than rounded into equality with
        ``kv_budget_gib``.
        """
        slack = self.kv_budget_bytes - self.kv_bytes
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "blocks_per_slot": self.blocks_per_slot,
            "paged_slots": self.max_slots,
            "dense_slots": self.dense_slots,
            "kv_budget_gib": round(self.kv_budget_bytes / GIB, 3),
            "kv_bytes_gib": round(self.kv_bytes / GIB, 6),
            "kv_slack_gib": round(slack / GIB, 6),
            "pool_tokens": self.pool_tokens,
        }


def _kv_itemsize(cfg) -> int:
    # serving caches are stored in the model compute dtype; np.dtype
    # resolves jnp dtypes too (ml_dtypes registers bfloat16)
    return np.dtype(cfg.dtype).itemsize


def window_ring(window: int, block_size: int) -> int:
    """Entries of a slot's ring table in a sliding layer's pool: the blocks a
    window of ``window`` positions can touch (``ceil(window / block_size) + 1``
    when it starts inside a block) and one more, for the block a step in
    flight writes into before the oldest falls out."""
    return math.ceil(window / block_size) + 2


def plan_pool(
    cfg,
    *,
    hbm_bytes: int = V5P_HBM_BYTES,
    headroom: float = DEFAULT_HEADROOM,
    block_size: int = 16,
    max_slots: int | None = None,
    mean_tokens_per_seq: int | None = None,
    max_prefill_batch: int = 4,
) -> PoolPlan:
    """Size a paged KV pool against an HBM budget for ``cfg``.

    Budget = ``hbm_bytes * headroom`` minus parameter bytes (serving holds
    no optimizer state, so params are ``param_count * itemsize`` — compare
    ``aot_fit.model_state_bytes_per_device`` which charges 3x for Adam).
    ``num_blocks`` fills the remainder; ``max_slots`` (the engine's fixed
    slot-array size) defaults to oversubscribing the pool assuming
    sequences average ``mean_tokens_per_seq`` tokens (default
    ``max_seq / 4`` — serving traffic rarely decodes to the cap), capped
    so a single full-length sequence always fits.

    A sliding layer (``cfg.layer_types``) is charged its window, not
    ``max_seq``: a slot holds there a ring of :func:`window_ring` blocks
    whatever its context, and a prefill round stages its rows' blocks
    (``max_prefill_batch`` rows of a whole sequence). That constant comes off
    the budget first; ``num_blocks`` fills the rest with blocks of the full
    layers alone.
    """
    itemsize = _kv_itemsize(cfg)
    param_bytes = cfg.param_count() * itemsize
    budget = int(hbm_bytes * headroom) - param_bytes
    if budget <= 0:
        raise ValueError(
            f"params ({param_bytes / GIB:.1f} GiB) exceed HBM budget "
            f"({hbm_bytes * headroom / GIB:.1f} GiB); no room for KV pool"
        )
    # one block of one layer: K and V of every cache head, or a latent row a token
    layer_block_bytes = block_size * cfg.cache_width * itemsize
    n_window = cfg.layers_of("window")
    block_bytes = (cfg.n_layers - n_window) * layer_block_bytes  # a further block of context: the full layers'
    if not block_bytes:
        raise ValueError("a stack of sliding layers alone has no pool to plan")
    blocks_per_slot = math.ceil(cfg.max_seq / block_size)
    ring = window_ring(cfg.sliding_window, block_size) if n_window else 0
    slot_bytes = n_window * ring * layer_block_bytes  # what a slot holds in the window pools, whatever its context
    staged_bytes = n_window * (1 + max_prefill_batch * blocks_per_slot) * layer_block_bytes if n_window else 0
    mean_blocks = math.ceil((mean_tokens_per_seq or max(block_size, cfg.max_seq // 4)) / block_size)
    if n_window and max_slots is None:
        max_slots = max(1, (budget - staged_bytes) // (mean_blocks * block_bytes + slot_bytes))
    window_bytes = staged_bytes + (max_slots or 0) * slot_bytes
    num_blocks = (budget - window_bytes) // block_bytes
    if num_blocks < blocks_per_slot + 1:  # +1: trash block
        raise ValueError(
            f"KV budget ({budget / GIB:.2f} GiB) fits only {num_blocks} "
            f"blocks; one {cfg.max_seq}-token sequence needs "
            f"{blocks_per_slot}"
        )
    dense_seq_bytes = cfg.n_layers * cfg.max_seq * cfg.cache_width * itemsize
    dense_slots = budget // dense_seq_bytes
    if max_slots is None:
        max_slots = max(1, (num_blocks - 1) // mean_blocks)
    kv_bytes = num_blocks * block_bytes + window_bytes
    return PoolPlan(
        num_blocks=int(num_blocks),
        block_size=block_size,
        blocks_per_slot=blocks_per_slot,
        max_slots=int(max_slots),
        kv_bytes=int(kv_bytes),
        kv_budget_bytes=int(budget),
        dense_slots=int(dense_slots),
        num_window_blocks=int(1 + max_prefill_batch * blocks_per_slot + max_slots * ring) if n_window else 0,
        window_ring=ring,
    )


class BlockAllocator:
    """Refcounting free-list allocator over the physical blocks of one
    KV pool.

    Allocation is all-or-nothing: :meth:`alloc` returns ``None`` rather
    than a partial grant, so the engine can atomically decide to admit,
    wait, or preempt. Block ``TRASH_BLOCK`` is never handed out.

    Every allocated block carries a reference count (1 on :meth:`alloc`):
    the prefix cache and any slot sharing a cached prefix each hold one
    reference via :meth:`retain`, and :meth:`release` returns the block to
    the free list only when the count reaches zero. A shared block
    (refcount > 1) must never be written in place — the engine
    copy-on-writes the partial tail block through :meth:`is_shared` first.

    Freeing a block that is already free (double-free) or freeing the
    trash block raises ``ValueError`` instead of silently corrupting the
    free list.
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 is trash), got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: deque[int] = deque(
            b for b in range(num_blocks) if b != TRASH_BLOCK
        )
        # refcount per physical block; 0 == free (trash stays pinned at 0
        # and is rejected everywhere by the explicit guards)
        self._refs = np.zeros((num_blocks,), np.int32)

    @property
    def free_blocks(self) -> int:
        """Blocks currently available to allocate."""
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Blocks currently held by slots (excludes the trash block)."""
        return self.num_blocks - 1 - len(self._free)

    def _check(self, b: int) -> None:
        if b == TRASH_BLOCK:
            raise ValueError("trash block is never allocated/retained/freed")
        if not 0 < b < self.num_blocks:
            raise ValueError(f"block {b} outside pool of {self.num_blocks}")

    def alloc(self, n: int, evict=None) -> list[int] | None:  # noqa: ANN001
        """Take ``n`` blocks (each with refcount 1), or ``None`` (and take
        nothing) if fewer are free. ``evict(k)`` (a prefix cache's) is first
        asked for the ``k`` that are missing: under pool pressure its LRU
        entries are cheaper to reclaim than preempting a live slot."""
        if n < 0:
            raise ValueError(f"negative allocation: {n}")
        if n > len(self._free) and evict is not None:
            evict(n - len(self._free))
        if n > len(self._free):
            return None
        out = [self._free.popleft() for _ in range(n)]
        self._refs[out] += 1
        return out

    def refcount(self, block: int) -> int:
        """Current reference count of ``block`` (0 == free)."""
        self._check(block)
        return int(self._refs[block])

    def is_shared(self, block: int) -> bool:
        """True when more than one holder references ``block`` — writing
        it in place would corrupt another holder's prefix (COW trigger)."""
        return self.refcount(block) > 1

    def retain(self, blocks: list[int]) -> None:
        """Add one reference to each allocated block (prefix sharing)."""
        for b in blocks:
            self._check(b)
            if self._refs[b] == 0:
                raise ValueError(f"retaining free block {b}")
        for b in blocks:
            self._refs[b] += 1

    def release(self, blocks: list[int]) -> list[int]:
        """Drop one reference per block; blocks reaching refcount 0 go
        back to the free list. Returns the blocks actually freed.

        Raises ``ValueError`` on the trash block or a block that is
        already free (double-free) — validated for the whole batch before
        any count moves, so a raise leaves the allocator unchanged.
        """
        for b in blocks:
            self._check(b)
        counts: dict[int, int] = {}
        for b in blocks:
            counts[b] = counts.get(b, 0) + 1
            if counts[b] > self._refs[b]:
                raise ValueError(
                    f"double-free of block {b} "
                    f"(refcount {int(self._refs[b])})"
                )
        freed: list[int] = []
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)
                freed.append(b)
        return freed


class SlotTables:
    """Per-slot block tables, host side (numpy).

    The engine passes a copy of :attr:`tables` into the jitted decode step
    every iteration; unassigned entries stay ``TRASH_BLOCK`` so inactive
    slots are inert under the mask. One instance is shared by
    all layers — every layer of a sequence uses the same physical block
    ids into its own layer-indexed pool.
    """

    def __init__(self, max_slots: int, blocks_per_slot: int) -> None:
        self.max_slots = max_slots
        self.blocks_per_slot = self.most_blocks = blocks_per_slot  # a table's entries, and the most blocks a slot holds at once
        self.kept_blocks = 0  # of them, those a slot keeps whatever its tokens
        self.tables = np.full((max_slots, blocks_per_slot), TRASH_BLOCK, np.int32)
        self._blocks: list[list[int]] = [[] for _ in range(max_slots)]

    def assign(self, slot: int, blocks: list[int]) -> None:
        """Append physical ``blocks`` to ``slot``'s table."""
        held = self._blocks[slot]
        if len(held) + len(blocks) > self.blocks_per_slot:
            raise ValueError(
                f"slot {slot}: {len(held)}+{len(blocks)} blocks exceeds "
                f"blocks_per_slot={self.blocks_per_slot}"
            )
        self.tables[slot, len(held) : len(held) + len(blocks)] = blocks
        held.extend(blocks)

    def blocks_of(self, slot: int) -> list[int]:
        """Physical blocks currently held by ``slot``."""
        return list(self._blocks[slot])

    @property
    def held_blocks(self) -> int:
        """Blocks all slots hold together (a block two slots share counts twice)."""
        return sum(len(b) for b in self._blocks)

    def replace_block(self, slot: int, index: int, block: int) -> None:
        """Swap the physical block at table ``index`` — the engine's
        copy-on-write path after duplicating a shared tail block."""
        if index >= len(self._blocks[slot]):
            raise ValueError(
                f"slot {slot} holds {len(self._blocks[slot])} blocks; "
                f"cannot replace index {index}"
            )
        self._blocks[slot][index] = block
        self.tables[slot, index] = block

    def release(self, slot: int) -> list[int]:
        """Clear ``slot`` back to trash and return its blocks for
        :meth:`BlockAllocator.release`."""
        blocks = self._blocks[slot]
        self._blocks[slot] = []
        self.tables[slot, :] = TRASH_BLOCK
        return blocks


class WindowTables:
    """Per-slot ring tables of a sliding layer's pool, host side (numpy).

    A slot holds there only the blocks its window still touches: block ``b``
    of its sequence sits at entry ``b % ring`` of its row, the engine assigns
    a block as the sequence grows into it and takes back, oldest first, every
    block whose positions are all below every future query's window. The
    decode programs read the row through
    :func:`torchx_tpu.ops.paged_attention.ring_positions`'s rule; entries of
    blocks not held are the trash block and are never read.
    """

    def __init__(self, max_slots: int, ring: int) -> None:
        self.max_slots = max_slots
        self.ring = ring
        self.tables = np.full((max_slots, ring), TRASH_BLOCK, np.int32)
        self._held: list[dict[int, int]] = [{} for _ in range(max_slots)]  # block of the sequence -> physical block

    def assign(self, slot: int, logical: int, block: int) -> None:
        """Block ``logical`` of ``slot``'s sequence now lives in ``block``."""
        held = self._held[slot]
        if any(b % self.ring == logical % self.ring for b in held):
            raise ValueError(f"slot {slot}: block {logical} meets a held block in a ring of {self.ring}")
        held[logical] = block
        self.tables[slot, logical % self.ring] = block

    def has(self, slot: int, logical: int) -> bool:
        """Whether ``slot`` holds block ``logical`` of its sequence."""
        return logical in self._held[slot]

    def blocks_of(self, slot: int) -> dict[int, int]:
        """Block of the sequence -> physical block, for what ``slot`` holds."""
        return dict(self._held[slot])

    @property
    def held_blocks(self) -> int:
        """Blocks all slots hold together."""
        return sum(len(h) for h in self._held)

    def release_below(self, slot: int, logical: int) -> list[int]:
        """Take back every block of ``slot`` below block ``logical`` of its
        sequence, for :meth:`BlockAllocator.release`."""
        held = self._held[slot]
        out = []
        for b in sorted(b for b in held if b < logical):
            out.append(held.pop(b))
            self.tables[slot, b % self.ring] = TRASH_BLOCK
        return out

    def release(self, slot: int) -> list[int]:
        """Clear ``slot`` back to trash and return its blocks."""
        blocks = list(self._held[slot].values())
        self._held[slot] = {}
        self.tables[slot, :] = TRASH_BLOCK
        return blocks


class EvaTables:
    """Per-slot tables of a cache whose rows are not its tokens, host side (numpy).

    EVA attention (:mod:`torchx_tpu.models.eva`) reads the exact rows of a
    position's own window of ``window`` positions and one pooled row of every
    ``chunk`` positions of every window before it. Both kinds of row lie in the
    one pool under the one table the decode programs take, a slot's row of
    :attr:`tables` being ``[pooled_blocks blocks a finished window ... | the
    current window's blocks]``: position ``t`` is written at cache coordinate
    :meth:`coord` and reads the ``coord(t) + 1`` rows in front of it. Beside
    them a slot holds ``pooled_blocks`` **staging** blocks (its row of
    :attr:`stage`), into which the programs pool the current window's chunks as
    they fill and which nothing reads until the window ends. Then :meth:`turn`
    moves them into the table behind the pooled blocks already there, starts the
    next window at the entry behind them, and hands back the window's blocks
    **all at once** (a ring gives back one at a time). A block is a chunk
    (``block_size == chunk``), and a window's pooled rows are whole blocks.
    """

    def __init__(self, max_slots: int, max_seq: int, window: int, chunk: int, block_size: int) -> None:
        if block_size != chunk or window % chunk or (window // chunk) % block_size:
            raise ValueError(
                f"a block must be one chunk and a window's pooled rows whole blocks: block_size={block_size},"
                f" chunk={chunk}, window={window}"
            )
        self.max_slots, self.window, self.chunk, self.block_size = max_slots, window, chunk, block_size
        self.window_blocks = min(window, max_seq + block_size - 1) // block_size  # a whole window, or all a sequence can have
        self.pooled_blocks = window // chunk // block_size  # what a finished window leaves behind, and a slot's staging
        self.windows = math.ceil(max_seq / window)
        #: entries of a slot's table: the finished windows of the longest sequence, and its last window whole
        self.blocks_per_slot = self.pooled_blocks * (self.windows - 1) + self.window_blocks
        #: the most blocks a slot holds at once: those, and its staging
        self.most_blocks = self.blocks_per_slot + self.pooled_blocks
        self.kept_blocks = self.pooled_blocks * self.windows  # they stay for as long as the sequence does, the window's come back
        self.tables = np.full((max_slots, self.blocks_per_slot), TRASH_BLOCK, np.int32)
        self.stage = np.full((max_slots, self.pooled_blocks), TRASH_BLOCK, np.int32)
        self._pooled: list[list[int]] = [[] for _ in range(max_slots)]
        self._stage: list[list[int]] = [[] for _ in range(max_slots)]
        self._window: list[list[int]] = [[] for _ in range(max_slots)]

    def coord(self, position: int) -> int:
        """The row of its slot's cache that ``position`` is written to
        (:func:`torchx_tpu.models.eva.cache_coord`)."""
        return (self.window // self.chunk) * (position // self.window) + position % self.window

    def rows(self, tokens: int) -> int:
        """Rows a sequence of ``tokens`` holds: the pooled rows of its finished
        windows, its last window's own, and those staged of that window."""
        if not tokens:
            return 0
        return self.coord(tokens - 1) + 1 + ((tokens - 1) % self.window + 1) // self.chunk

    def window_of(self, slot: int) -> int:
        """The window ``slot``'s table is laid out for: how many it has finished."""
        return len(self._pooled[slot]) // self.pooled_blocks

    def short(self, slot: int, position: int) -> int:
        """Blocks ``slot`` lacks to be written up to ``position`` of its current
        window: its staging where it has none yet, and the window's blocks."""
        in_window = (position % self.window) // self.block_size + 1
        return self.pooled_blocks - len(self._stage[slot]) + max(0, in_window - len(self._window[slot]))

    def assign(self, slot: int, blocks: list[int]) -> None:
        """Give ``slot`` ``blocks``: its staging first where that is short, the
        rest behind its window's last block."""
        n = self.pooled_blocks - len(self._stage[slot])
        stage, window = self._stage[slot] + blocks[:n], self._window[slot] + blocks[n:]
        if len(window) > self.window_blocks:
            raise ValueError(f"slot {slot}: {len(window)} blocks exceed a window's {self.window_blocks}")
        self._stage[slot], self._window[slot] = stage, window
        self._lay(slot)

    def turn(self, slot: int) -> list[int]:
        """``slot``'s window has ended: its staged rows become readable (the
        staging blocks go into the table behind the pooled blocks), the next
        window starts at the entry behind them with no block yet and no staging,
        and the ended window's blocks come back for :meth:`BlockAllocator.release`."""
        if len(self._window[slot]) != self.window_blocks or len(self._stage[slot]) != self.pooled_blocks:
            raise ValueError(f"slot {slot}: a window that is not whole cannot end")
        if self.window_of(slot) + 1 >= self.windows:
            raise ValueError(f"slot {slot}: no window behind the {self.windows} of max_seq")
        released, self._window[slot] = self._window[slot], []
        self._pooled[slot] = self._pooled[slot] + self._stage[slot]
        self._stage[slot] = []
        self._lay(slot)
        return released

    def _lay(self, slot: int) -> None:
        held = self._pooled[slot] + self._window[slot]
        self.tables[slot, : len(held)] = held
        self.tables[slot, len(held) :] = TRASH_BLOCK
        self.stage[slot, : len(self._stage[slot])] = self._stage[slot]
        self.stage[slot, len(self._stage[slot]) :] = TRASH_BLOCK

    def blocks_of(self, slot: int) -> list[int]:
        """Every block ``slot`` holds: pooled, staging, its window's."""
        return self._pooled[slot] + self._stage[slot] + self._window[slot]

    @property
    def held_blocks(self) -> int:
        """Blocks all slots hold together."""
        return self.held_window + self.held_pooled

    @property
    def held_window(self) -> int:
        """Blocks of the slots' current windows."""
        return sum(len(b) for b in self._window)

    @property
    def held_pooled(self) -> int:
        """Blocks of pooled rows: the finished windows' and the staging."""
        return sum(len(b) for b in self._pooled) + sum(len(b) for b in self._stage)

    def release(self, slot: int) -> list[int]:
        """Clear ``slot`` back to trash and return its blocks."""
        blocks = self.blocks_of(slot)
        self._pooled[slot], self._stage[slot], self._window[slot] = [], [], []
        self._lay(slot)
        return blocks
