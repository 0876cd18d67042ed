"""Prefix-cache + KV-transfer unit tests, host side only: allocator
refcounting/guards, the radix cache (match/insert/LRU eviction/summary),
pool occupancy accounting, transfer configs and transports, and the
cache-aware router scoring — no model, no device step."""

import threading

import pytest

from torchx_tpu.models import llama
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.serve.kv_pool import BlockAllocator, plan_pool
from torchx_tpu.serve.kv_transfer import (
    FileTransfer,
    KvPayload,
    LocalTransfer,
    TransferConfig,
    TransferError,
    TransferRejected,
    make_transfer,
    new_request_id,
    serve_spool,
)
from torchx_tpu.serve.pool import LeastLoadedRouter, ReplicaStatus
from torchx_tpu.serve.prefix_cache import PrefixCache, prefix_chain

import numpy as np

GIB = 1024**3


# -- allocator refcounting -------------------------------------------------


class TestAllocatorRefcount:
    def test_alloc_starts_at_one_reference(self):
        a = BlockAllocator(8)
        (b,) = a.alloc(1)
        assert a.refcount(b) == 1 and not a.is_shared(b)

    def test_retain_release_roundtrip(self):
        a = BlockAllocator(8)
        (b,) = a.alloc(1)
        a.retain([b])
        assert a.refcount(b) == 2 and a.is_shared(b)
        assert a.release([b]) == []  # still held by the other reference
        assert a.refcount(b) == 1 and a.free_blocks == 6
        assert a.release([b]) == [b]  # last reference frees it
        assert a.refcount(b) == 0 and a.free_blocks == 7

    def test_double_free_raises(self):
        a = BlockAllocator(8)
        (b,) = a.alloc(1)
        a.release([b])
        with pytest.raises(ValueError, match="double-free"):
            a.release([b])

    def test_batch_double_free_validated_before_any_count_moves(self):
        a = BlockAllocator(8)
        b1, b2 = a.alloc(2)
        with pytest.raises(ValueError, match="double-free"):
            a.release([b1, b2, b1])  # b1 twice against refcount 1
        # the raise left the allocator unchanged: both still allocated
        assert a.refcount(b1) == 1 and a.refcount(b2) == 1
        assert a.free_blocks == 5

    def test_trash_block_guards(self):
        a = BlockAllocator(8)
        with pytest.raises(ValueError, match="trash"):
            a.release([TRASH_BLOCK])
        with pytest.raises(ValueError, match="trash"):
            a.retain([TRASH_BLOCK])
        with pytest.raises(ValueError, match="trash"):
            a.refcount(TRASH_BLOCK)

    def test_retain_free_block_raises(self):
        a = BlockAllocator(8)
        (b,) = a.alloc(1)
        a.release([b])
        with pytest.raises(ValueError, match="retaining free"):
            a.retain([b])

    def test_out_of_pool_block_raises(self):
        a = BlockAllocator(8)
        with pytest.raises(ValueError, match="outside pool"):
            a.release([99])


# -- occupancy accounting --------------------------------------------------


class TestOccupancyReport:
    def test_kv_bytes_and_slack_sum_to_budget(self):
        cfg = llama.CONFIGS["tiny"]()
        plan = plan_pool(cfg, hbm_bytes=1 * GIB, headroom=0.9, block_size=16)
        report = plan.occupancy_report()
        # the block grid rarely tiles the budget exactly: the actual pool
        # footprint plus the unusable remainder is the whole budget
        assert plan.kv_bytes + (plan.kv_budget_bytes - plan.kv_bytes) == (
            plan.kv_budget_bytes
        )
        itemsize = np.dtype(cfg.dtype).itemsize
        block_bytes = (
            cfg.n_layers * 2 * 16 * cfg.n_kv_heads * cfg.head_dim * itemsize
        )
        assert plan.kv_bytes == plan.num_blocks * block_bytes
        assert report["kv_bytes_gib"] == round(plan.kv_bytes / GIB, 6)
        assert report["kv_slack_gib"] == round(
            (plan.kv_budget_bytes - plan.kv_bytes) / GIB, 6
        )
        assert 0 <= report["kv_slack_gib"] * GIB < block_bytes + 1


# -- prefix_chain ----------------------------------------------------------


class TestPrefixChain:
    def test_full_blocks_only_and_cap(self):
        toks = list(range(50))
        assert len(prefix_chain(toks, 16)) == 3  # 50 // 16
        assert len(prefix_chain(toks, 16, max_blocks=2)) == 2
        assert prefix_chain([1, 2], 16) == []

    def test_chain_commits_to_the_whole_path(self):
        toks = list(range(48))
        chain = prefix_chain(toks, 16)
        # the chain of a shorter prefix is a prefix of the longer chain
        assert prefix_chain(toks[:32], 16) == chain[:2]
        # changing an *early* token changes every later digest
        other = [99] + toks[1:]
        assert prefix_chain(other, 16)[2] != chain[2]

    def test_same_block_different_position_differs(self):
        # positional chaining: identical 16 tokens at depth 0 vs depth 1
        # must not collide (a plain per-block hash would)
        block = list(range(16))
        assert prefix_chain(block * 2, 16)[1] != prefix_chain(block, 16)[0]


# -- PrefixCache -----------------------------------------------------------


def _cache(num_blocks=32, bs=4, **kw):
    alloc = BlockAllocator(num_blocks)
    return alloc, PrefixCache(alloc, bs, **kw)


class TestPrefixCache:
    def test_match_miss_then_insert_then_hit(self):
        alloc, pc = _cache()
        toks = list(range(12))  # 3 full blocks at bs=4
        blocks = alloc.alloc(3)
        assert pc.match(toks) == ([], 0)
        assert pc.insert(toks, blocks) == 3
        assert pc.cached_blocks == 3
        # the cache holds its own reference on every adopted block
        assert all(alloc.refcount(b) == 2 for b in blocks)
        alloc.release(blocks)  # the prefilling slot completes
        got, n = pc.match(toks)
        # never covers the final token: 2 of the 3 cached blocks match
        assert got == blocks[:2] and n == 8
        # match retained the matched blocks on behalf of the caller
        assert [alloc.refcount(b) for b in blocks] == [2, 2, 1]
        st = pc.stats()
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["hit_tokens"] == 8 and st["lookup_tokens"] == 24

    def test_match_never_covers_the_final_token(self):
        alloc, pc = _cache()
        toks = list(range(8))  # exactly 2 blocks
        pc.insert(toks, alloc.alloc(2))
        got, n = pc.match(toks)
        # the last token must stay uncached so prefill has logits to
        # sample from: only the first block matches
        assert len(got) == 1 and n == 4
        got, n = pc.match(toks + [42])
        assert len(got) == 2 and n == 8

    def test_insert_keeps_existing_node_on_duplicate(self):
        alloc, pc = _cache()
        toks = list(range(8))
        first = alloc.alloc(2)
        dup = alloc.alloc(2)
        assert pc.insert(toks, first) == 2
        assert pc.insert(toks, dup) == 0  # chunks present: caller keeps dup
        assert all(alloc.refcount(b) == 2 for b in first)
        assert all(alloc.refcount(b) == 1 for b in dup)

    def test_evict_lru_frees_only_unreferenced(self):
        alloc, pc = _cache()
        cold = list(range(100, 104))
        hot = list(range(200, 204))
        for toks in (cold, hot):
            blocks = alloc.alloc(1)
            pc.insert(toks, blocks)
            alloc.release(blocks)  # cache-only: refcount 1, evictable
        held, _ = pc.match(hot + [1])  # touch hot + hold a live reference
        free0 = alloc.free_blocks
        assert pc.evict(2) == 1  # cold goes; hot is refcount 2 (cache+us)
        assert alloc.free_blocks == free0 + 1
        assert pc.match(cold + [1]) == ([], 0)
        assert pc.stats()["evictions"] == 1
        alloc.release(held)

    def test_evict_leaves_before_parents(self):
        alloc, pc = _cache()
        toks = list(range(8))
        blocks = alloc.alloc(2)
        pc.insert(toks, blocks)
        alloc.release(blocks)
        assert pc.evict(1) == 1
        # the leaf (depth 2) went first; the depth-1 prefix still matches
        got, n = pc.match(toks + [9])
        assert n == 4
        alloc.release(got)

    def test_max_blocks_cap_evicts_then_stops(self):
        alloc, pc = _cache(max_blocks=2)
        a, b = list(range(4)), list(range(10, 14))
        for toks in (a, b):
            blocks = alloc.alloc(1)
            pc.insert(toks, blocks)
            alloc.release(blocks)
        assert pc.cached_blocks == 2
        # a third distinct prefix evicts the LRU entry to stay under cap
        c_blocks = alloc.alloc(1)
        assert pc.insert(list(range(20, 24)), c_blocks) == 1
        assert pc.cached_blocks == 2
        assert pc.match(a + [0]) == ([], 0)  # a was LRU: gone

    def test_summary_matches_prefix_chain_digests(self):
        alloc, pc = _cache()
        toks = list(range(12))
        pc.insert(toks, alloc.alloc(3))
        digests = pc.summary()
        assert set(prefix_chain(toks, 4)) <= set(digests)


# -- TransferConfig --------------------------------------------------------


class TestTransferConfig:
    def test_spec_grammar_roundtrip(self):
        assert TransferConfig.from_spec("local").mode == "local"
        assert TransferConfig.from_spec("").mode == "local"
        fc = TransferConfig.from_spec("file:/var/spool/kv")
        assert fc.mode == "file" and fc.endpoints == ("/var/spool/kv",)
        hc = TransferConfig.from_spec("http:http://a:1,b:2")
        assert hc.mode == "http"
        assert hc.endpoints == ("http://a:1", "http://b:2")  # scheme added
        for spec in ("local", "file:/spool", "http:http://a:1,http://b:2"):
            assert TransferConfig.from_spec(spec).to_spec() == spec

    def test_bad_specs_raise(self):
        with pytest.raises(ValueError, match="no endpoints"):
            TransferConfig.from_spec("http:")
        with pytest.raises(ValueError, match="unknown kv-transfer"):
            TransferConfig.from_spec("carrier-pigeon:coop")

    def test_make_transfer_dispatch(self, tmp_path):
        assert isinstance(
            make_transfer(TransferConfig.from_spec("local")), LocalTransfer
        )
        ft = make_transfer(TransferConfig.from_spec(f"file:{tmp_path}/sp"))
        assert isinstance(ft, FileTransfer)


# -- payload + transports --------------------------------------------------


def _payload(**kw):
    defaults = dict(
        request_id=new_request_id(),
        tokens=[1, 2, 3, 4, 5],
        generated=[7],
        cache_len=5,
        max_new_tokens=4,
        temperature=0.5,
        seed=11,
        eos_id=None,
        block_size=4,
        k=np.arange(2 * 2 * 4 * 2 * 3, dtype=np.float32).reshape(2, 2, 4, 2, 3),
        v=np.zeros((2, 2, 4, 2, 3), np.float32),
    )
    defaults.update(kw)
    return KvPayload(**defaults)


class TestTransports:
    def test_payload_bytes_roundtrip(self):
        p = _payload()
        q = KvPayload.from_bytes(p.to_bytes())
        assert q.meta() == p.meta()
        assert (q.k == p.k).all() and (q.v == p.v).all()
        assert q.k.dtype == p.k.dtype

    def test_send_requeues_past_rejecting_target(self):
        served = []

        def draining(payload):
            raise TransferRejected("draining")

        def healthy(payload):
            served.append(payload.request_id)
            return {"tokens": [9, 9]}

        t = LocalTransfer({"a": draining, "b": healthy})
        p = _payload()
        out = t.send(p)
        # the drain-race contract: the rejection cost a retry, not the
        # request — the second target served it
        assert out == {"tokens": [9, 9]} and served == [p.request_id]

    def test_send_raises_when_all_targets_reject(self):
        t = LocalTransfer(
            {"a": lambda p: (_ for _ in ()).throw(TransferRejected("x"))}
        )
        with pytest.raises(TransferError, match="no decode target"):
            t.send(_payload())

    def test_file_spool_roundtrip_and_rejection(self, tmp_path):
        spool = str(tmp_path / "spool")
        calls = []

        def handler(payload):
            calls.append(payload.request_id)
            if len(calls) == 1:
                raise TransferRejected("draining")
            return {"tokens": [int(t) + 1 for t in payload.generated]}

        stop = threading.Event()
        pump = threading.Thread(
            target=serve_spool, args=(spool, handler, stop), daemon=True
        )
        pump.start()
        try:
            ft = FileTransfer(spool)
            with pytest.raises(TransferRejected, match="draining"):
                ft.transfer(_payload(), spool, timeout=30)
            out = ft.transfer(_payload(generated=[5]), spool, timeout=30)
            assert out == {"tokens": [6]}
        finally:
            stop.set()
            pump.join(timeout=10)


# -- cache-aware router ----------------------------------------------------


def _status(rid, summary=(), bs=4, queue=0.0):
    return ReplicaStatus(
        replica_id=rid,
        url=f"http://r{rid}",
        healthy=True,
        queue_depth=queue,
        prefix_summary=tuple(summary),
        block_size=bs,
    )


class TestCacheAwareRouter:
    def test_prefix_blocks_is_deepest_shared_digest(self):
        toks = list(range(12))
        chain = prefix_chain(toks, 4)
        r = LeastLoadedRouter()
        assert r.prefix_blocks(_status(0, chain[:2]), toks) == 2
        assert r.prefix_blocks(_status(0, chain), toks) == 3
        assert r.prefix_blocks(_status(0), toks) == 0
        # a foreign digest set shares nothing
        other = prefix_chain([9] * 12, 4)
        assert r.prefix_blocks(_status(0, other), toks) == 0

    def test_pick_prefers_cache_warm_replica(self):
        toks = list(range(12))
        chain = prefix_chain(toks, 4)
        r = LeastLoadedRouter(cache_bonus=1.0)
        # replica 1 is busier but holds the whole prefix: 2 - 3 < 0
        r.update([_status(0, queue=0.0), _status(1, chain, queue=2.0)])
        assert r.pick(toks).replica_id == 1
        # without tokens the same table degrades to plain least-loaded
        r.update([_status(0, queue=0.0), _status(1, chain, queue=2.0)])
        assert r.pick().replica_id == 0

    def test_pick_bumps_inflight(self):
        toks = list(range(8))
        chain = prefix_chain(toks, 4)
        r = LeastLoadedRouter(cache_bonus=1.0)
        r.update([_status(0, chain), _status(1, chain)])
        first = r.pick(toks).replica_id
        # the bonus ties; in-flight from the first pick breaks the tie
        assert r.pick(toks).replica_id != first
