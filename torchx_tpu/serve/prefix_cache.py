"""Refcounted radix prefix cache over the paged KV pool.

Serving traffic is dominated by shared prefixes — the same system prompt
in front of every request, few-shot preambles, multi-turn histories. The
paged pool already stores KV block-wise, so a prefix that two sequences
share can be *one* set of physical blocks with two references instead of
being recomputed per request (SGLang's RadixAttention observation).

:class:`PrefixCache` is the host-side index: a radix tree keyed on token
ids at **block granularity** — each node owns exactly one physical block
holding ``block_size`` tokens, and a root-to-node path spells out a
block-aligned prefix. The cache holds its own reference on every adopted
block through :class:`~torchx_tpu.serve.kv_pool.BlockAllocator`, so
blocks survive the completing slot and are shared into later slots via
:meth:`match` (which retains them for the new holder).

Only *full* blocks are ever cached, and :meth:`match` never covers the
final prompt token (the engine must compute at least one position to
produce logits), so a matched block is never written by its sharers —
the engine's copy-on-write tail guard is the backstop, not the hot path.

Eviction is LRU over nodes whose block has refcount 1 (cache-only, no
live slot): :meth:`evict` frees the least-recently-touched such leaves
under pool pressure, and an optional ``max_blocks`` cap bounds how much
of the pool the cache may pin (the ``--prefix-cache-reserve`` fraction
the cost model accounts for).

A model that mixes sliding and full layers keeps two pools, and a node then
holds a block of each (``window_alloc``): the full layers' block as above, and
the sliding layers' block of the same tokens for as long as it lasts. Window
blocks are the scarcer (their pool is a few blocks a slot) and are given up
first, least recently used first, the node staying (:meth:`evict_window`). A
suffix's first query reads the window ahead of it, so a match is cut back to
the longest cached prefix whose last ``window_back`` blocks all still have
their window block (:meth:`match_kinds`).

Hit/miss accounting feeds ``tpx_serve_prefix_*`` metrics and the serving
bench's prefix-hit-rate scorecard. Routers use :func:`prefix_chain` /
:meth:`summary` — positionally-chained digests of block keys — to score
replicas by longest cached prefix without shipping token ids around.
"""

from __future__ import annotations

import hashlib
import heapq
import itertools
import threading
from typing import TYPE_CHECKING, Optional, Sequence

from torchx_tpu.obs import metrics as obs_metrics

if TYPE_CHECKING:  # annotation-only: kv_pool pulls the jax-backed op stack
    from torchx_tpu.serve.kv_pool import BlockAllocator

__all__ = ["PrefixCache", "prefix_chain"]


def _chain_digest(parent: bytes, chunk: tuple[int, ...]) -> bytes:
    h = hashlib.blake2b(digest_size=8)
    h.update(parent)
    h.update(b"|".join(str(t).encode() for t in chunk))
    return h.digest()


def prefix_chain(
    tokens: Sequence[int], block_size: int, max_blocks: int = 64
) -> list[str]:
    """Chained per-block digests of ``tokens``: entry ``i`` identifies the
    whole prefix ``tokens[: (i+1) * block_size]``. Routers compare these
    against replica summaries to find the longest cached prefix without
    exchanging raw token ids."""
    out: list[str] = []
    parent = b""
    n_full = min(len(tokens) // block_size, max_blocks)
    for i in range(n_full):
        chunk = tuple(tokens[i * block_size : (i + 1) * block_size])
        parent = _chain_digest(parent, chunk)
        out.append(parent.hex())
    return out


class _Node:
    __slots__ = ("chunk", "block", "window", "children", "parent", "last_used", "digest", "live")

    def __init__(
        self,
        chunk: tuple[int, ...],
        block: int,
        parent: Optional["_Node"],
        stamp: int,
    ) -> None:
        self.chunk = chunk
        self.block = block
        self.window: Optional[int] = None  # the sliding layers' block of the same tokens, while it lasts
        self.parent = parent
        self.children: dict[tuple[int, ...], _Node] = {}
        self.last_used = stamp
        self.live = True  # False once evicted: a heap entry may outlive its node
        self.digest = _chain_digest(
            parent.digest if parent is not None else b"", chunk
        )


class PrefixCache:
    """Radix tree of cached full KV blocks (see module docstring).

    Thread-safe: the engine loop matches/inserts/evicts while HTTP
    threads read :meth:`stats` and :meth:`summary`.
    """

    def __init__(
        self,
        alloc: BlockAllocator,
        block_size: int,
        *,
        max_blocks: Optional[int] = None,
        window_alloc: Optional[BlockAllocator] = None,
        window_back: int = 0,
    ) -> None:
        self.alloc = alloc
        #: the sliding layers' allocator, and how many blocks ahead of a
        #: suffix its first query's window reaches into
        self.window_alloc = window_alloc
        self.window_back = window_back
        #: (last_used, tie, node) for nodes given a window block: the order
        #: :meth:`evict_window` gives them up in, stale entries skipped
        self._windows: list[tuple[int, int, _Node]] = []
        self.window_evictions = 0
        self.block_size = block_size
        self.max_blocks = max_blocks  # None: bounded only by pool pressure
        self._root: dict[tuple[int, ...], _Node] = {}
        self._nodes = 0
        self._stamp = itertools.count()
        #: (last_used, tie, node) for every node that became, or was touched
        #: as, a leaf: the eviction order without a walk of the tree. Entries
        #: are not removed when they go stale (the node was touched again,
        #: got a child or was evicted); eviction skips those
        self._leaves: list[tuple[int, int, _Node]] = []
        self._tie = itertools.count()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.evictions = 0

    @property
    def cached_blocks(self) -> int:
        """Blocks currently pinned by the cache."""
        return self._nodes

    # -- lookup ------------------------------------------------------------

    def match(self, tokens: Sequence[int]) -> tuple[list[int], int]:
        """:meth:`match_kinds` for a cache of one pool: ``(blocks, n_tokens)``."""
        blocks, _, matched = self.match_kinds(tokens)
        return blocks, matched

    def match_kinds(self, tokens: Sequence[int]) -> tuple[list[int], dict[int, int], int]:
        """Longest cached block-aligned prefix of ``tokens``.

        Returns ``(blocks, window_blocks, n_tokens)`` with one reference
        **retained per returned block on behalf of the caller** (release them
        through the normal slot-release path). ``window_blocks`` maps a block
        of the sequence to the sliding layers' block of it, for the last
        ``window_back`` blocks of the prefix (empty without a window pool); the
        prefix is cut back until all of those are still cached. Never covers
        the final token: the engine always has at least one position left to
        prefill, so the sampled "first" token has logits to come from.
        """
        bs = self.block_size
        # at least one token must remain uncached
        limit = max(0, (len(tokens) - 1) // bs)
        with self._lock:
            stamp = next(self._stamp)
            path: list[_Node] = []
            children = self._root
            for i in range(limit):
                chunk = tuple(tokens[i * bs : (i + 1) * bs])
                child = children.get(chunk)
                if child is None:
                    break
                path.append(child)
                children = child.children
            window: dict[int, int] = {}
            if self.window_alloc is not None:
                n = len(path)
                while n and any(node.window is None for node in path[max(0, n - self.window_back) : n]):
                    n -= 1
                del path[n:]
                window = {i: path[i].window for i in range(max(0, n - self.window_back), n)}
            blocks = [node.block for node in path]
            self._note_leaf(path[-1] if path else None, stamp)
            # touch the whole path so LRU evicts leaves before their parents
            for node in path:
                node.last_used = stamp
            if blocks:
                self.alloc.retain(blocks)
                if window:
                    self.window_alloc.retain(list(window.values()))
                self.hits += 1
                obs_metrics.SERVE_PREFIX_HITS.inc()
            else:
                self.misses += 1
                obs_metrics.SERVE_PREFIX_MISSES.inc()
            matched = len(blocks) * bs
            self.hit_tokens += matched
            self.lookup_tokens += len(tokens)
            if matched:
                obs_metrics.SERVE_PREFIX_HIT_TOKENS.inc(matched)
        return blocks, window, matched

    # -- insertion ---------------------------------------------------------

    def insert(
        self, tokens: Sequence[int], blocks: Sequence[int], window_blocks: Optional[dict[int, int]] = None
    ) -> int:
        """Index the full blocks of a prefilled/completed sequence.

        ``blocks[i]`` must hold tokens ``tokens[i*bs : (i+1)*bs]``; only
        ``len(tokens) // block_size`` full blocks are considered. New
        nodes adopt the caller's block with a cache-owned reference
        (:meth:`BlockAllocator.retain`); chunks already present keep the
        existing node's block — the caller's duplicate stays the
        caller's to release. ``window_blocks`` maps a block of the sequence to
        the sliding layers' block of it, where the caller still holds one: a
        node without a window block adopts it the same way. Returns the number
        of newly adopted blocks (of the full layers).
        """
        bs = self.block_size
        n_full = min(len(tokens) // bs, len(blocks))
        adopted = 0
        with self._lock:
            stamp = next(self._stamp)
            parent: Optional[_Node] = None
            children = self._root
            for i in range(n_full):
                chunk = tuple(tokens[i * bs : (i + 1) * bs])
                node = children.get(chunk)
                if node is None:
                    if (
                        self.max_blocks is not None
                        and self._nodes >= self.max_blocks
                        and not self._evict_locked(1)
                    ):
                        break  # cap reached, nothing evictable
                    block = int(blocks[i])
                    self.alloc.retain([block])
                    node = _Node(chunk, block, parent, stamp)
                    children[chunk] = node
                    self._nodes += 1
                    adopted += 1
                node.last_used = stamp
                if node.window is None and window_blocks and i in window_blocks and self.window_alloc is not None:
                    node.window = int(window_blocks[i])
                    self.window_alloc.retain([node.window])
                    heapq.heappush(self._windows, (stamp, next(self._tie), node))
                parent = node
                children = node.children
            self._note_leaf(parent)
            obs_metrics.SERVE_PREFIX_CACHED_BLOCKS.set(self._nodes)
        return adopted

    # -- eviction ----------------------------------------------------------

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` cache-only blocks (refcount 1), least
        recently used leaves first. Returns how many were freed — the
        engine calls this under pool pressure before preempting slots."""
        with self._lock:
            freed = self._evict_locked(n_blocks)
            obs_metrics.SERVE_PREFIX_CACHED_BLOCKS.set(self._nodes)
            return freed

    def evict_window(self, n_blocks: int) -> int:
        """Give up to ``n_blocks`` window blocks that only the cache holds
        back to the sliding layers' allocator, least recently used first; the
        nodes stay, and a later match is cut back past them. -> how many."""
        freed, in_use = 0, []
        with self._lock:
            while freed < n_blocks and self._windows:
                stamp, tie, node = heapq.heappop(self._windows)
                if not node.live or node.window is None:
                    continue
                if stamp != node.last_used:  # touched since: back in at its new place
                    heapq.heappush(self._windows, (node.last_used, tie, node))
                    continue
                if self.window_alloc.refcount(node.window) != 1:
                    in_use.append((stamp, tie, node))
                    continue
                self.window_alloc.release([node.window])
                node.window = None
                self.window_evictions += 1
                freed += 1
            for entry in in_use:
                heapq.heappush(self._windows, entry)
        return freed

    def _note_leaf(self, node: Optional[_Node], stamp: Optional[int] = None) -> None:
        """``node`` was just stamped (or is about to be, with ``stamp``), or
        just lost its last child: if it is a leaf, it joins the eviction order
        at its stamp."""
        if node is not None and stamp is not None:
            node.last_used = stamp
        if node is not None and not node.children:
            heapq.heappush(self._leaves, (node.last_used, next(self._tie), node))
            if len(self._leaves) > 4 * self._nodes + 1024:  # mostly stale: start again
                self._leaves = [e for e in self._leaves if self._current(e)]
                heapq.heapify(self._leaves)

    @staticmethod
    def _current(entry: tuple[int, int, _Node]) -> bool:
        stamp, _, node = entry
        return node.live and not node.children and stamp == node.last_used

    def _evict_locked(self, n_blocks: int) -> int:
        """Least recently used cache-only leaves first, a parent after its
        last child. A walk of the whole tree for every block (8,000 nodes
        where the pool holds 130 k tokens, a block a slot every 16 steps)
        cost the engine loop tens of milliseconds a step: the order is kept
        in a heap instead."""
        freed = 0
        in_use = []  # leaves a slot still reads: back into the order afterwards
        while freed < n_blocks and self._leaves:
            entry = heapq.heappop(self._leaves)
            victim = entry[2]
            if not self._current(entry):
                continue
            if self.alloc.refcount(victim.block) != 1:
                in_use.append(entry)
                continue
            siblings = (
                victim.parent.children if victim.parent is not None else self._root
            )
            del siblings[victim.chunk]
            victim.live = False
            self._nodes -= 1
            self.alloc.release([victim.block])
            if victim.window is not None:
                self.window_alloc.release([victim.window])
                victim.window = None
            self.evictions += 1
            obs_metrics.SERVE_PREFIX_EVICTIONS.inc()
            freed += 1
            self._note_leaf(victim.parent)
        for entry in in_use:
            heapq.heappush(self._leaves, entry)
        return freed

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        """Hit/miss accounting for ``/healthz`` and the bench scorecard."""
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "cached_blocks": self._nodes,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": self.hits / lookups if lookups else 0.0,
                "hit_tokens": self.hit_tokens,
                "lookup_tokens": self.lookup_tokens,
                "token_hit_rate": (
                    self.hit_tokens / self.lookup_tokens
                    if self.lookup_tokens
                    else 0.0
                ),
                "evictions": self.evictions,
                "window_evictions": self.window_evictions,
            }

    def summary(self, max_entries: int = 128) -> list[str]:
        """Digests of the most-recently-used cached prefixes, for the
        cache-aware router (compare against :func:`prefix_chain`)."""
        with self._lock:
            nodes: list[_Node] = []
            stack = list(self._root.values())
            while stack:
                node = stack.pop()
                nodes.append(node)
                stack.extend(node.children.values())
            nodes.sort(key=lambda n: n.last_used, reverse=True)
            return [n.digest.hex() for n in nodes[:max_entries]]
