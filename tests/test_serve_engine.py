"""Serving runtime tests: paged KV pool planning/allocation, paged-vs-dense
decode equivalence, and the continuous-batching engine end to end (slots,
EOS eviction, preemption under block pressure, drain)."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchx_tpu.models import generate as gen, llama
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.serve.engine import EngineStopped, ServeEngine, ServeRequest, _fold_keys
from torchx_tpu.serve.kv_pool import (
    BlockAllocator,
    SlotTables,
    plan_pool,
)

GIB = 1024**3


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


# -- plan_pool -------------------------------------------------------------


class TestPoolPlan:
    def test_budget_math_and_oversubscription(self, tiny):
        cfg, _ = tiny
        plan = plan_pool(cfg, hbm_bytes=1 * GIB, headroom=0.9, block_size=16)
        # budget = hbm*headroom - params, filled with whole blocks
        itemsize = np.dtype(cfg.dtype).itemsize
        block_bytes = (
            cfg.n_layers * 2 * 16 * cfg.n_kv_heads * cfg.head_dim * itemsize
        )
        budget = int(1 * GIB * 0.9) - cfg.param_count() * itemsize
        assert plan.num_blocks == budget // block_bytes
        assert plan.kv_budget_bytes == budget
        # paged admits more concurrent sequences than the dense cache
        # at the same budget (the point of the whole exercise)
        assert plan.max_slots > plan.dense_slots
        report = plan.occupancy_report()
        assert report["paged_slots"] == plan.max_slots
        assert report["dense_slots"] == plan.dense_slots

    def test_params_exceeding_budget_raise(self, tiny):
        cfg, _ = tiny
        with pytest.raises(ValueError, match="exceed HBM budget"):
            plan_pool(cfg, hbm_bytes=1024, headroom=0.9)

    def test_pool_too_small_for_one_sequence_raises(self, tiny):
        cfg, _ = tiny
        itemsize = np.dtype(cfg.dtype).itemsize
        param_bytes = cfg.param_count() * itemsize
        with pytest.raises(ValueError, match="fits only"):
            plan_pool(
                cfg, hbm_bytes=int(param_bytes / 0.9) + 4096, headroom=0.9
            )

    def test_explicit_max_slots_wins(self, tiny):
        cfg, _ = tiny
        plan = plan_pool(cfg, hbm_bytes=1 * GIB, max_slots=3)
        assert plan.max_slots == 3


# -- allocator + tables ----------------------------------------------------


class TestBlockAllocator:
    def test_all_or_nothing(self):
        a = BlockAllocator(4)  # 3 usable (block 0 is trash)
        assert a.free_blocks == 3
        got = a.alloc(2)
        assert got is not None and TRASH_BLOCK not in got
        assert a.alloc(2) is None  # only 1 left: refuse, take nothing
        assert a.free_blocks == 1
        a.release(got)
        assert a.free_blocks == 3

    def test_trash_block_protected(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError, match="trash"):
            a.release([TRASH_BLOCK])
        with pytest.raises(ValueError, match="blocks"):
            BlockAllocator(1)


class TestSlotTables:
    def test_assign_release_roundtrip(self):
        t = SlotTables(max_slots=2, blocks_per_slot=3)
        assert (t.tables == TRASH_BLOCK).all()
        t.assign(0, [5, 7])
        assert list(t.tables[0]) == [5, 7, TRASH_BLOCK]
        assert t.blocks_of(0) == [5, 7]
        t.assign(0, [9])
        assert t.blocks_of(0) == [5, 7, 9]
        with pytest.raises(ValueError, match="exceeds"):
            t.assign(0, [11])
        freed = t.release(0)
        assert freed == [5, 7, 9]
        assert (t.tables[0] == TRASH_BLOCK).all()


# -- paged vs dense equivalence --------------------------------------------


class TestPagedEquivalence:
    def test_prefill_plus_decode_matches_dense_greedy(self, tiny):
        cfg, params = tiny
        bs = 8
        pools = gen.init_kv_pools(cfg, num_blocks=33, block_size=bs)
        alloc = BlockAllocator(33)
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9]]
        max_new = 6
        width = bs  # all prompts fit one block at width 8
        pad = np.zeros((4, width), np.int32)  # rows padded to pow2
        true_lens = np.ones((4,), np.int32)
        rows_blocks = np.full((4, cfg.max_seq // bs), TRASH_BLOCK, np.int32)  # the rows' block tables
        held = []
        for i, p in enumerate(prompts):
            pad[i, : len(p)] = p
            true_lens[i] = len(p)
            blocks = alloc.alloc(1)
            rows_blocks[i, 0] = blocks[0]
            held.append(blocks)
        seeds = np.zeros((4,), np.int32)
        temps = np.zeros((4,), np.float32)
        keys = jax.vmap(jax.random.PRNGKey)(seeds)
        first, pools = gen.paged_prefill_chunk(  # nothing cached ahead of it: a cold prefill
            params,
            jnp.asarray(pad),
            jnp.zeros((4,), jnp.int32),
            jnp.asarray(true_lens),
            jnp.asarray(rows_blocks),
            pools,
            cfg,
            keys,
            jnp.asarray(temps),
        )
        # decode the 3 real rows in one fixed slot array
        tables = SlotTables(max_slots=4, blocks_per_slot=cfg.max_seq // bs)
        out = [list(p) for p in prompts]
        last = [int(first[i]) for i in range(3)]
        lens = list(true_lens[:3])
        for i in range(3):
            tables.assign(i, held[i])
            out[i].append(last[i])
        for _ in range(max_new - 1):
            for i in range(3):  # lazy block growth, like the engine
                if lens[i] + 1 > len(tables.blocks_of(i)) * bs:
                    tables.assign(i, alloc.alloc(1))
            toks = np.array(last + [0], np.int32)
            poss = np.array(lens + [0], np.int32)
            step_keys = jax.vmap(jax.random.PRNGKey)(np.zeros((4,), np.int32))
            nxt, pools = gen.paged_decode_step(
                params,
                jnp.asarray(toks),
                jnp.asarray(poss),
                jnp.asarray(tables.tables),
                pools,
                cfg,
                step_keys,
                jnp.zeros((4,), jnp.float32),
            )
            for i in range(3):
                out[i].append(int(nxt[i]))
                last[i] = int(nxt[i])
                lens[i] += 1
        for i, p in enumerate(prompts):
            expect = dense_generate(params, cfg, p, max_new)
            assert out[i] == expect, f"row {i} diverged from dense decode"


# -- the engine ------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, params = tiny
    eng = ServeEngine(
        params, cfg, max_slots=4, block_size=8, max_prefill_batch=2
    ).start()
    yield eng
    eng.stop()


class TestServeEngine:
    def test_greedy_matches_dense_across_mixed_lengths(self, tiny, engine):
        cfg, params = tiny
        prompts = [[1, 2, 3], [7, 8], [4, 5, 6, 7, 8, 9, 10], [11], [3, 1]]
        reqs = [
            engine.submit(ServeRequest(prompt=p, max_new_tokens=5))
            for p in prompts
        ]
        for r in reqs:
            assert r.wait(timeout=120) and r.error is None
        for p, r in zip(prompts, reqs):
            assert r.tokens == dense_generate(params, cfg, p, 5)

    def test_continuous_batching_shares_steps(self, tiny, engine):
        # N concurrent requests must cost far fewer decode steps than
        # serial batch-to-completion would (slots share every step)
        steps0 = engine.steps
        reqs = [
            engine.submit(ServeRequest(prompt=[i + 1, i + 2], max_new_tokens=6))
            for i in range(4)
        ]
        for r in reqs:
            assert r.wait(timeout=120)
        assert engine.steps - steps0 < 4 * 6

    def test_eos_evicts_early(self, tiny, engine, early_stop_case):
        cfg, params = tiny
        prompt, full, cut = early_stop_case(
            lambda p, n: dense_generate(params, cfg, p, n), 8
        )
        eos = full[cut - 1]  # first emitted third or later; use it as EOS
        r = engine.generate(prompt, max_new_tokens=8, eos_id=eos, timeout=120)
        assert r.tokens == full[:cut]  # stopped right after emitting EOS
        assert r.generated[-1] == eos

    def test_sampled_determinism_and_seed_sensitivity(self, tiny, engine):
        a = engine.generate([5, 6], 6, temperature=0.8, seed=42, timeout=120)
        b = engine.generate([5, 6], 6, temperature=0.8, seed=42, timeout=120)
        c = engine.generate([5, 6], 6, temperature=0.8, seed=43, timeout=120)
        assert a.tokens == b.tokens
        assert a.tokens != c.tokens

    def test_submit_validation(self, tiny, engine):
        cfg, _ = tiny
        with pytest.raises(ValueError, match="max_seq"):
            engine.submit(
                ServeRequest(prompt=[1] * cfg.max_seq, max_new_tokens=4)
            )
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(ServeRequest(prompt=[1], max_new_tokens=0))

    def test_stats_shape(self, engine):
        s = engine.stats()
        for k in (
            "active_slots",
            "occupancy",
            "queue_depth",
            "kv_blocks_used",
            "requests_done",
            "steps",
        ):
            assert k in s

    def test_drain_then_submit_raises(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, max_slots=2, block_size=8).start()
        try:
            r = eng.submit(ServeRequest(prompt=[1, 2], max_new_tokens=3))
            assert eng.drain(timeout=120) is True
            assert r.done.is_set() and r.error is None
            with pytest.raises(EngineStopped):
                eng.submit(ServeRequest(prompt=[1], max_new_tokens=1))
        finally:
            eng.stop()

    def test_geometry_validation(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError, match="power of 2"):
            ServeEngine(params, cfg, block_size=12)
        with pytest.raises(ValueError, match="num_blocks"):
            ServeEngine(params, cfg, block_size=8, num_blocks=4)

    def test_from_plan_geometry(self, tiny):
        cfg, params = tiny
        plan = plan_pool(
            cfg, hbm_bytes=1 * GIB, block_size=8, max_slots=2
        )
        eng = ServeEngine.from_plan(params, cfg, plan)
        assert eng.max_slots == 2 and eng.block_size == 8
        assert eng.cache.num_blocks == plan.num_blocks


# -- one decode step always in flight -----------------------------------------


class _Unfetched:
    """What the spied ``_decode`` hands the engine in place of a step's
    tokens: converting it to numpy is the engine's fetch of that step."""

    def __init__(self, n, value, log, fail=False):
        self.n, self.value, self.log, self.fail = n, value, log, fail

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        if self.fail:
            raise RuntimeError("device fell over")
        return np.asarray(self.value)


def spy_on_decode(eng, at_dispatch=None, fail_fetch_of=None):
    """Record ``("dispatch", n, host tokens, chunk)`` and ``("fetch", n)`` in
    the order the engine's thread makes them, whichever of its two programs
    step ``n`` is; ``chunk`` is ``(start, real tokens, slot or -1)`` of the
    chunk a step carries, None on a pure decode step. ``at_dispatch(n)`` runs on
    that thread before step ``n`` is enqueued."""
    log = []

    def spied(real):
        def program(params, tokens, prev, *rest):
            n = sum(ev[0] == "dispatch" for ev in log)
            if at_dispatch is not None:
                at_dispatch(n)
            chunk = tuple(int(x) for x in np.asarray(rest[-2])) if len(rest) > 5 else None
            log.append(("dispatch", n, np.asarray(tokens).copy(), chunk))
            nxt, pools = real(params, tokens, getattr(prev, "value", prev), *rest)
            return _Unfetched(n, nxt, log, fail=n == fail_fetch_of), pools

        return program

    eng._decode, eng._decode_chunk = spied(eng._decode), spied(eng._decode_chunk)
    return log


def paged_generate(params, cfg, prompt, max_new, temperature, seed, slots=4, bs=8):
    """The blocking loop, written out for one sequence: prefill, then one
    step at a time with every token fetched before the next step is built.
    Keys as the engine folds them, so sampled tokens are comparable."""
    per_slot = cfg.max_seq // bs
    pools = gen.init_kv_pools(cfg, num_blocks=per_slot + 1, block_size=bs)
    table = np.full((slots, per_slot), TRASH_BLOCK, np.int32)
    table[0] = np.arange(1, per_slot + 1)
    width = max(bs, 1 << (len(prompt) - 1).bit_length())
    toks = np.zeros((1, width), np.int32)
    toks[0, : len(prompt)] = prompt
    seeds = jnp.full((slots,), seed, jnp.int32)
    temps = jnp.full((slots,), temperature, jnp.float32)
    n = jnp.asarray([len(prompt)], jnp.int32)
    first, pools = gen.paged_prefill_chunk(
        params, jnp.asarray(toks), jnp.zeros((1,), jnp.int32), n, jnp.asarray(table[:1]), pools, cfg,
        _fold_keys(seeds[:1], n - 1), temps[:1],
    )  # fmt: skip
    out = list(prompt) + [int(first[0])]
    while len(out) < len(prompt) + max_new:
        positions = jnp.zeros((slots,), jnp.int32).at[0].set(len(out) - 1)
        tokens = jnp.zeros((slots,), jnp.int32).at[0].set(out[-1])
        nxt, pools = gen.paged_decode_step(
            params, tokens, positions, jnp.asarray(table), pools, cfg, _fold_keys(seeds, positions), temps
        )
        out.append(int(nxt[0]))
    return out


class TestStepInFlight:
    def test_next_step_is_enqueued_before_this_one_is_fetched(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, max_slots=2, block_size=8)
        log = spy_on_decode(eng)
        eng.start()
        try:
            r = eng.generate([1, 2, 3], max_new_tokens=12, timeout=120)
        finally:
            eng.stop()
        assert r.tokens == dense_generate(params, cfg, [1, 2, 3], 12)
        order = [ev[:2] for ev in log]
        steps = eng.stats()["steps"]
        assert steps == 12  # the first token is the step's that carried the prompt
        for n in range(steps - 1):
            assert order.index(("dispatch", n + 1)) < order.index(("fetch", n))
        assert order[-1] == ("fetch", steps - 1)  # the last step: nothing to enqueue, still fetched
        assert eng.stats()["steps_overlapped"] == steps - 1
        assert eng.stats()["tokens_discarded"] == 0
        dispatches = [ev for ev in log if ev[0] == "dispatch"]
        # the first step carries the whole prompt and ends it in slot 0; no step reads a token of the
        # host's: the first token too is read where the step before left it, on the device
        assert [ev[3] for ev in dispatches] == [(0, 3, 0)] + [None] * (steps - 1)
        assert [int(ev[2][0]) for ev in dispatches] == [0] + [-1] * (steps - 1)

    @pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
    def test_arrivals_while_a_step_is_in_flight(self, tiny, temperature):
        """A prompt that arrives while others decode rides their steps, a
        step in flight across each of its chunks, and its slot's first step
        reads its first token on the device as its neighbours read theirs."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg, max_slots=4, block_size=8, max_prefill_batch=2, chunk_width=8)
        prompts = [[1, 2, 3], [7, 8], list(range(4, 23)), [11], [3, 1]]
        lengths = [14, 6, 9, 12, 5]
        reqs = [
            ServeRequest(prompt=p, max_new_tokens=n, temperature=temperature, seed=40 + i)
            for i, (p, n) in enumerate(zip(prompts, lengths))
        ]
        arrive = {2: reqs[1:3], 4: reqs[3:4], 7: reqs[4:]}  # before dispatch n, on the engine's thread
        log = spy_on_decode(eng, at_dispatch=lambda n: [eng.submit(r) for r in arrive.get(n, [])])
        eng.submit(reqs[0])
        eng.start()
        try:
            for r in reqs:
                assert r.wait(timeout=120) and r.error is None
        finally:
            eng.stop()
        for r in reqs:
            if temperature == 0.0:
                assert r.tokens == dense_generate(params, cfg, list(r.prompt), r.max_new_tokens)
            assert r.tokens == paged_generate(
                params, cfg, list(r.prompt), r.max_new_tokens, temperature, r.seed
            )
        dispatches = [ev for ev in log if ev[0] == "dispatch"]
        riding = [ev for ev in dispatches if ev[3] is not None and (ev[2] == -1).any()]
        assert len(riding) >= 5  # every arrival's chunks sat beside slots already stepping
        assert all((ev[2] <= 0).all() for ev in dispatches)  # no token goes through the host
        # the 19-token prompt went in three chunks, one behind the other, each enqueued with the step before in flight
        long_chunks = [ev for ev in dispatches if ev[3] is not None and ev[3][:2] in ((0, 8), (8, 8), (16, 3))]
        assert [ev[3][2] >= 0 for ev in long_chunks] == [False, False, True]
        assert [ev[1] for ev in long_chunks] == list(range(long_chunks[0][1], long_chunks[0][1] + 3))
        order = [ev[:2] for ev in log]
        for ev in long_chunks[1:]:
            assert order.index(("dispatch", ev[1])) < order.index(("fetch", ev[1] - 1))
        assert eng.stats()["tokens_discarded"] == 0

    def test_a_slot_whose_last_token_is_in_flight_writes_no_row(self, tiny):
        """Known to finish by count, a slot is not stepped again, but the
        program writes a row for every slot: that one's must not land at
        position 0 of its first block, which the prefix cache shares out."""
        cfg, params = tiny
        head = list(range(20, 36))  # two full blocks, shared
        eng = ServeEngine(params, cfg, max_slots=2, block_size=8).start()
        try:
            short = eng.submit(ServeRequest(prompt=head + [1], max_new_tokens=3))
            long = eng.submit(ServeRequest(prompt=head + [2, 3], max_new_tokens=12))
            assert short.wait(timeout=120) and long.wait(timeout=120)
            later = eng.generate(head + [4], max_new_tokens=4, timeout=120)
            assert eng.stats()["prefix_cache"]["hit_tokens"] >= 16
        finally:
            eng.stop()
        for r in (short, long, later):
            assert r.tokens == dense_generate(params, cfg, list(r.prompt), r.max_new_tokens)

    def test_eos_overrun_is_dropped_and_its_block_reused(self, tiny, early_stop_case):
        cfg, params = tiny
        prompt, full, cut = early_stop_case(lambda p, n: dense_generate(params, cfg, p, n), 8)
        # the smallest pool: the long request after the EOS must take the
        # block that holds the overrun's row
        eng = ServeEngine(
            params, cfg, max_slots=2, block_size=8, num_blocks=cfg.max_seq // 8 + 1, enable_prefix_cache=False
        ).start()
        try:
            free = eng.cache.alloc.free_blocks
            r = eng.generate(prompt, max_new_tokens=8, eos_id=full[cut - 1], timeout=120)
            assert r.tokens == full[:cut] and cut < len(full)
            long_prompt = list(range(5, 5 + cfg.max_seq - 16))
            after = eng.generate(long_prompt, max_new_tokens=16, timeout=120)
            assert after.tokens == dense_generate(params, cfg, long_prompt, 16)
            assert eng.drain(timeout=120) and eng.cache.alloc.free_blocks == free
            # learnt a step late: the slot was stepped once more, that token dropped
            assert eng.stats()["tokens_discarded"] == 1
            # one more than the tokens it gave: each request's first token is a step's too
            assert eng.stats()["steps"] == len(r.generated) + 1 + 16
        finally:
            eng.stop()

    def test_drain_leaves_no_token_on_the_device(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, max_slots=2, block_size=8).start()
        try:
            reqs = [
                eng.submit(ServeRequest(prompt=[i + 1, i + 2], max_new_tokens=4 + 3 * i)) for i in range(3)
            ]
            assert eng.drain(timeout=120) is True
            for r in reqs:
                assert r.done.is_set() and r.error is None
                assert r.tokens == dense_generate(params, cfg, list(r.prompt), r.max_new_tokens)
        finally:
            eng.stop()

    def test_stop_fails_the_request_whose_step_is_in_flight(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, max_slots=2, block_size=8)
        stepping = threading.Event()
        spy_on_decode(eng, at_dispatch=lambda n: stepping.set() if n == 2 else None)
        eng.start()
        r = eng.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=100))
        assert stepping.wait(timeout=120)
        eng.stop()
        assert r.done.is_set() and r.error == "engine stopped"
        assert eng._in_flight is None and all(s is None for s in eng._slots)

    @pytest.mark.parametrize("where", ["dispatch", "fetch"])
    def test_a_raising_step_fails_every_waiter(self, tiny, where):
        """An error of the device surfaces where the host waits for it: at
        the fetch, a turn after the step was enqueued."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg, max_slots=2, block_size=8)

        def boom(n):
            if where == "dispatch" and n == 3:
                raise RuntimeError("device fell over")

        spy_on_decode(eng, at_dispatch=boom, fail_fetch_of=3 if where == "fetch" else None)
        eng.start()
        try:
            reqs = [eng.submit(ServeRequest(prompt=[i + 1, 2], max_new_tokens=20)) for i in range(3)]
            for r in reqs:  # two in slots (one step in flight), one still queued
                assert r.wait(timeout=120) and "device fell over" in r.error
            assert "device fell over" in eng.failed and eng._in_flight is None
            with pytest.raises(EngineStopped):
                eng.submit(ServeRequest(prompt=[1], max_new_tokens=1))
        finally:
            eng.stop()
