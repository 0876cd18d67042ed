"""Disaggregated serving tests: prefill-only handoff export, transferred
decode parity against the unified engine and the dense reference, the
drain-race requeue contract, cached-vs-cold prefill parity (bit-identical
greedy and sampled outputs), and the copy-on-write tail guard."""

import numpy as np
import pytest

import jax

from torchx_tpu.models import generate as gen, llama
from torchx_tpu.serve.engine import (
    EngineStopped,
    ServeEngine,
    ServeRequest,
    serve_kv_payload,
)
from torchx_tpu.serve.kv_transfer import (
    LocalTransfer,
    TransferError,
    TransferRejected,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.CONFIGS["tiny"]()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def dense_generate(params, cfg, prompt, max_new, temperature=0.0, seed=0):
    out = gen.generate(
        params,
        np.array([prompt], np.int32),
        cfg,
        max_new_tokens=max_new,
        temperature=temperature,
        rng=jax.random.PRNGKey(seed) if temperature > 0 else None,
    )
    return [int(t) for t in np.asarray(out)[0]]


def make_engine(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    return ServeEngine(params, cfg, **kw).start()


# -- cached-vs-cold prefill parity -----------------------------------------


class TestPrefixCacheParity:
    def test_repeat_prompt_hits_cache_and_stays_bit_identical(self, tiny):
        cfg, params = tiny
        eng = make_engine(tiny, enable_prefix_cache=True)
        try:
            prompt = list(range(1, 20))  # spans 2 full blocks at bs=8
            cold = eng.generate(prompt, 6, timeout=120).tokens
            assert cold == dense_generate(params, cfg, prompt, 6)
            hits0 = eng.cache.prefix_cache.stats()["hits"]
            warm = eng.generate(prompt, 6, timeout=120).tokens
            assert eng.cache.prefix_cache.stats()["hits"] > hits0
            # the cache-hit suffix prefill reproduced the cold output
            # exactly — same tokens, not merely similar
            assert warm == cold
        finally:
            eng.stop()

    def test_sampled_parity_and_seed_sensitivity_with_cache(self, tiny):
        eng = make_engine(tiny, enable_prefix_cache=True)
        try:
            prompt = list(range(3, 21))
            a = eng.generate(prompt, 6, temperature=0.9, seed=7, timeout=120)
            b = eng.generate(prompt, 6, temperature=0.9, seed=7, timeout=120)
            c = eng.generate(prompt, 6, temperature=0.9, seed=8, timeout=120)
            # sampling keys are position-absolute, so the warm (cached)
            # run draws the same stream the cold run did
            assert b.tokens == a.tokens
            assert c.tokens != a.tokens
        finally:
            eng.stop()

    def test_extended_prompt_reuses_shared_prefix(self, tiny):
        cfg, params = tiny
        eng = make_engine(tiny, enable_prefix_cache=True)
        try:
            base = list(range(5, 22))
            eng.generate(base, 4, timeout=120)
            longer = base + [40, 41, 42]
            got = eng.generate(longer, 4, timeout=120).tokens
            assert got == dense_generate(params, cfg, longer, 4)
            assert eng.cache.prefix_cache.stats()["hit_tokens"] >= 16
        finally:
            eng.stop()


# -- copy-on-write tail guard ----------------------------------------------


class TestCopyOnWrite:
    def test_shared_tail_is_copied_before_write(self, tiny):
        # drive _make_writable directly: a slot whose tail block another
        # holder references must get a private copy, never write in place
        eng = make_engine(tiny)
        try:
            blocks = eng.cache.alloc.alloc(2)
            eng.cache.tables.assign(0, blocks)
            eng.cache.alloc.retain([blocks[1]])  # e.g. the prefix cache
            assert eng._make_writable(0, 8)  # write pos in block index 1
            tail = eng.cache.tables.blocks_of(0)[1]
            assert tail != blocks[1]
            assert not eng.cache.alloc.is_shared(tail)
            # the other holder keeps its (now sole) reference
            assert eng.cache.alloc.refcount(blocks[1]) == 1
            assert eng.cache.tables.blocks_of(0)[0] == blocks[0]  # untouched
        finally:
            eng.stop()

    def test_unshared_tail_is_left_in_place(self, tiny):
        eng = make_engine(tiny)
        try:
            blocks = eng.cache.alloc.alloc(2)
            eng.cache.tables.assign(0, blocks)
            assert eng._make_writable(0, 8)
            assert eng.cache.tables.blocks_of(0) == blocks
        finally:
            eng.stop()


# -- prefill-only handoff export -------------------------------------------


class TestPrefillOnly:
    def test_handoff_snapshot_shape_and_state(self, tiny):
        cfg, _ = tiny
        eng = make_engine(tiny)
        try:
            prompt = list(range(1, 11))
            req = ServeRequest(
                prompt=prompt, max_new_tokens=5, prefill_only=True
            )
            eng.submit(req)
            assert req.wait(timeout=120) and req.error is None
            assert len(req.generated) == 1  # prefill sampled exactly one
            h = req.handoff
            assert h is not None
            assert h.tokens == prompt and h.cache_len == len(prompt)
            assert h.generated == req.generated
            n_blocks = -(-len(prompt) // eng.block_size)
            assert h.k.shape == (
                cfg.n_layers,
                n_blocks,
                eng.block_size,
                cfg.n_kv_heads,
                cfg.head_dim,
            )
            # the exported blocks were released back to the pool
            assert eng.cache.alloc.used_blocks == eng.cache.prefix_cache.cached_blocks
        finally:
            eng.stop()

    def test_finished_at_prefill_needs_no_handoff(self, tiny):
        cfg, params = tiny
        eng = make_engine(tiny)
        try:
            req = ServeRequest(
                prompt=[1, 2, 3], max_new_tokens=1, prefill_only=True
            )
            eng.submit(req)
            assert req.wait(timeout=120) and req.error is None
            assert req.handoff is None  # nothing left for a decode side
            assert req.tokens == dense_generate(params, cfg, [1, 2, 3], 1)
        finally:
            eng.stop()


# -- prefill -> decode transfer parity -------------------------------------


class TestDisaggParity:
    def _disagg_generate(self, pre, dec, prompt, max_new, **kw):
        req = ServeRequest(
            prompt=list(prompt),
            max_new_tokens=max_new,
            prefill_only=True,
            **kw,
        )
        pre.submit(req)
        assert req.wait(timeout=120) and req.error is None
        if req.handoff is None:
            return req.tokens
        transfer = LocalTransfer(
            {"decode": lambda p: serve_kv_payload(dec, p, timeout=120)}
        )
        out = transfer.send(req.handoff)
        return list(prompt) + [int(t) for t in out["tokens"]]

    def test_greedy_matches_unified_and_dense(self, tiny):
        cfg, params = tiny
        pre = make_engine(tiny)
        dec = make_engine(tiny)
        try:
            for prompt in ([1, 2, 3], list(range(4, 17)), [9]):
                got = self._disagg_generate(pre, dec, prompt, 6)
                assert got == dense_generate(params, cfg, prompt, 6)
        finally:
            pre.stop()
            dec.stop()

    def test_sampled_stream_continues_across_the_handoff(self, tiny):
        # decode must fold the same (seed, position) keys prefill would
        # have: the split sequence equals the unified sampled sequence
        pre = make_engine(tiny)
        dec = make_engine(tiny)
        uni = make_engine(tiny)
        try:
            prompt = list(range(2, 12))
            split = self._disagg_generate(
                pre, dec, prompt, 8, temperature=0.9, seed=7
            )
            whole = uni.generate(
                prompt, 8, temperature=0.9, seed=7, timeout=120
            ).tokens
            assert split == whole
        finally:
            pre.stop()
            dec.stop()
            uni.stop()

    def test_decode_side_respects_eos(self, tiny, early_stop_case):
        cfg, params = tiny
        pre = make_engine(tiny)
        dec = make_engine(tiny)
        try:
            prompt, full, cut = early_stop_case(
                lambda p, n: dense_generate(params, cfg, p, n), 8
            )
            eos = full[cut - 1]  # first emitted 3rd or later: decode side must stop there
            got = self._disagg_generate(pre, dec, prompt, 8, eos_id=eos)
            assert got == full[:cut]
        finally:
            pre.stop()
            dec.stop()


# -- the drain-race requeue contract ---------------------------------------


class TestDrainRace:
    def test_draining_target_rejects_and_next_target_serves(self, tiny):
        cfg, params = tiny
        pre = make_engine(tiny)
        drainer = make_engine(tiny)
        healthy = make_engine(tiny)
        try:
            assert drainer.drain(timeout=30)  # empty: drains immediately
            req = ServeRequest(
                prompt=list(range(1, 8)), max_new_tokens=5, prefill_only=True
            )
            pre.submit(req)
            assert req.wait(timeout=120) and req.handoff is not None
            order = []

            def via(name, eng):
                def handler(payload):
                    order.append(name)
                    return serve_kv_payload(eng, payload, timeout=120)

                return handler

            transfer = LocalTransfer(
                {"a": via("a", drainer), "b": via("b", healthy)}
            )
            out = transfer.send(req.handoff)
            # the draining replica rejected; the request was requeued to
            # the next target and completed — not dropped
            assert order == ["a", "b"]
            got = list(req.prompt) + [int(t) for t in out["tokens"]]
            assert got == dense_generate(params, cfg, list(range(1, 8)), 5)
        finally:
            pre.stop()
            drainer.stop()
            healthy.stop()

    def test_all_targets_draining_surfaces_transfer_error(self, tiny):
        pre = make_engine(tiny)
        drainer = make_engine(tiny)
        try:
            assert drainer.drain(timeout=30)
            req = ServeRequest(
                prompt=[1, 2, 3, 4], max_new_tokens=4, prefill_only=True
            )
            pre.submit(req)
            assert req.wait(timeout=120) and req.handoff is not None
            transfer = LocalTransfer(
                {"a": lambda p: serve_kv_payload(drainer, p, timeout=120)}
            )
            with pytest.raises(TransferError, match="no decode target"):
                transfer.send(req.handoff)
            # the handoff payload is still intact for a later retry
            assert req.handoff.cache_len == 4
        finally:
            pre.stop()
            drainer.stop()

    def test_submit_prefilled_validates_geometry(self, tiny):
        cfg, _ = tiny
        pre = make_engine(tiny)
        dec = make_engine(tiny)
        try:
            req = ServeRequest(
                prompt=list(range(1, 10)), max_new_tokens=4, prefill_only=True
            )
            pre.submit(req)
            assert req.wait(timeout=120) and req.handoff is not None
            h = req.handoff
            bad = ServeRequest(
                prompt=h.tokens,
                max_new_tokens=h.max_new_tokens,
                generated=list(h.generated),
            )
            with pytest.raises(ValueError, match="blocks"):
                dec.submit_prefilled(
                    bad, h.k[:, :1], h.v[:, :1], h.cache_len, h.generated[-1]
                )
            with pytest.raises(ValueError, match="max_seq"):
                big = ServeRequest(
                    prompt=h.tokens,
                    max_new_tokens=cfg.max_seq,
                    generated=list(h.generated),
                )
                dec.submit_prefilled(
                    big, h.k, h.v, h.cache_len, h.generated[-1]
                )
        finally:
            pre.stop()
            dec.stop()

    def test_rejection_propagates_through_serve_kv_payload(self, tiny):
        pre = make_engine(tiny)
        drainer = make_engine(tiny)
        try:
            assert drainer.drain(timeout=30)
            req = ServeRequest(
                prompt=[5, 6, 7], max_new_tokens=3, prefill_only=True
            )
            pre.submit(req)
            assert req.wait(timeout=120) and req.handoff is not None
            with pytest.raises(TransferRejected):
                serve_kv_payload(drainer, req.handoff, timeout=30)
            with pytest.raises(EngineStopped):
                drainer.submit(ServeRequest(prompt=[1], max_new_tokens=1))
        finally:
            pre.stop()
            drainer.stop()
