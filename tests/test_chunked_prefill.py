"""Prompts ride the decode steps (PR 40): the step that carries a chunk of a prompt
(``generate.paged_decode_chunk_step``) against the decode step and the chunk prefill run
apart, and the engine that feeds it (``ServeEngine._decode_once``) against the uncached
forward, for the five model kinds a serving cell runs, at the tiny float32 widths of the
benchmark's fixtures on the CPU.

Tolerances. The mixed step runs the same float32 products as the two programs apart,
a layer's norms, projections and feed-forward over ``slots + width`` rows where they run
them over ``slots`` and over ``width``: sampled tokens are equal, pools agree to ``1e-5``
(a row's sum may be ordered otherwise inside a larger matmul). The engine's greedy
tokens are the uncached forward's (``llama.forward``, the whole sequence at once), which
differs from any paged program in the order of its sums alone.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import models
from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.serve.engine import ServeEngine, ServeRequest, _fold_keys
from torchx_tpu.serve.kv_pool import window_ring

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "benchmark", "tests", "fixtures", "configs")
KINDS = {  # model kind -> the fixture that holds its published keys at test widths
    "llama": "tiny-dense",
    "moe": "tiny-moe",  # the capacity path: a group a row in a serving step, so no routing is dropped
    "mla_moe": "tiny-mla-moe",
    "exaone_moe": "tiny-exaone-moe",  # sliding and full layers: two pools, a ring a slot
    "mla_moe_hc": "tiny-mla-moe-hc",
}
MAX_SEQ, SLOTS, C = 128, 4, 16  # C: the chunk's width in every test here


@pytest.fixture(scope="module")
def built():
    """kind -> (cfg, params, block size), made once a kind."""
    made = {}
    _Paged.programs.clear()

    def of(kind: str):
        if kind not in made:
            with open(os.path.join(FIXTURES, KINDS[kind] + ".json")) as f:
                config = json.load(f)
            assert config["model"] == kind and config["torch_dtype"] == "float32"
            cfg = models.program_config(config, max_seq=MAX_SEQ, remat=False)
            made[kind] = (cfg, models.make_weights(config, 2147483659), int(config["deployment"]["block_size"]))
        return made[kind]

    return of


def _prompt(seed: int, n: int, vocab: int) -> list[int]:
    return np.random.default_rng([seed, n]).integers(1, vocab, n).tolist()


# -- (a) the mixed step against the decode step and the chunk prefill run apart ----------------------


class _Paged:
    """The pools, the tables and two slots that decode, for one kind: what the
    engine keeps on the host, written out for the programs alone. Slot 2 is the
    request whose prompt is fed; slot 3 stays empty."""

    programs: dict = {}  # cfg -> the three programs jitted, compiled once a kind and width

    def __init__(self, cfg, params, bs: int):
        self.cfg, self.params, self.bs = cfg, params, bs
        if cfg not in self.programs:
            self.programs[cfg] = {
                "prefill": jax.jit(lambda p, *a: gen.paged_prefill_chunk(p, *a[:5], cfg, *a[5:])),
                "decode": jax.jit(lambda p, *a: gen.paged_decode_step(p, *a[:4], cfg, *a[4:])),
                "decode_chunk": jax.jit(lambda p, *a: gen.paged_decode_chunk_step(p, *a[:8], cfg, *a[8:])),
            }
        self.run = self.programs[cfg]
        self.bps = MAX_SEQ // bs
        self.window = cfg.sliding_window if cfg.layers_of("window") else 0
        self.ring = window_ring(self.window, bs) if self.window else 0
        n_blocks = 1 + SLOTS * self.bps
        self.pools = gen.init_kv_pools(cfg, n_blocks, bs, n_blocks)
        # slot s owns blocks 1 + s * bps ..., in both kinds of pool: block b of its sequence is 1 + s * bps + b
        self.linear = np.stack([1 + s * self.bps + np.arange(self.bps, dtype=np.int32) for s in range(SLOTS)])
        self.lengths = [0] * SLOTS  # tokens of each slot in the pools
        self.last = [0] * SLOTS  # the token each slot feeds next
        for s, n in ((0, 5), (1, 11)):  # two slots with a context, prefilled whole
            self.last[s] = int(self.prefill(s, _prompt(s, n, cfg.vocab_size), 0, width=C)[0])

    def tables(self, rows, decode: bool):
        """The block tables of ``rows`` as a program takes them. A decode
        step's: trash where a slot does not step; a sliding layer's table a ring
        (block ``b`` at entry ``b % ring``, those its window still touches)."""
        full = np.full((len(rows), self.bps), TRASH_BLOCK, np.int32)
        window = np.full((len(rows), self.ring if decode else self.bps), TRASH_BLOCK, np.int32)
        for i, s in enumerate(rows):
            if s is None:
                continue
            full[i] = self.linear[s]
            if not self.window:
                continue
            if not decode:
                window[i] = self.linear[s]
                continue
            first = max(0, self.lengths[s] - self.window + 1) // self.bs
            for b in range(first, self.lengths[s] // self.bs + 1):
                window[i, b % self.ring] = self.linear[s, b]
        return {"full": jnp.asarray(full), "window": jnp.asarray(window)} if self.window else jnp.asarray(full)

    def prefill(self, slot: int, toks: list[int], start: int, width: int):
        """``paged_prefill_chunk`` of ``toks`` from position ``start`` of ``slot``. -> the sampled token"""
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(toks)] = toks
        n = jnp.asarray([len(toks)], jnp.int32)
        at = jnp.asarray([start], jnp.int32)
        first, self.pools = self.run["prefill"](
            self.params, jnp.asarray(padded), at, n, self.tables([slot], decode=False), self.pools,
            _fold_keys(jnp.zeros((1,), jnp.int32), at + n - 1), jnp.zeros((1,), jnp.float32),
        )  # fmt: skip
        self.lengths[slot] = start + len(toks)
        return first

    def _decode_args(self, stepping):
        rows = [s if s in stepping else None for s in range(SLOTS)]
        tokens = jnp.asarray([self.last[s] if s in stepping else 0 for s in range(SLOTS)], jnp.int32)
        positions = jnp.asarray([self.lengths[s] if s in stepping else 0 for s in range(SLOTS)], jnp.int32)
        return tokens, positions, self.tables(rows, decode=True)

    def _stepped(self, stepping, nxt):
        for s in stepping:
            self.lengths[s] += 1
            self.last[s] = int(nxt[s])
        return [int(nxt[s]) for s in stepping]

    def decode(self, stepping):
        tokens, positions, tables = self._decode_args(stepping)
        keys = _fold_keys(jnp.zeros((SLOTS,), jnp.int32), positions)
        nxt, self.pools = self.run["decode"](
            self.params, tokens, positions, tables, self.pools, keys, jnp.zeros((SLOTS,), jnp.float32)
        )
        return self._stepped(stepping, nxt)

    def decode_chunk(self, stepping, slot: int, toks: list[int], start: int):
        """The mixed step: ``stepping`` decode, ``toks`` of ``slot``'s prompt ride. -> (their tokens, the chunk's)"""
        tokens, positions, tables = self._decode_args(stepping)
        chunk = np.zeros((C,), np.int32)
        chunk[: len(toks)] = toks
        keys = _fold_keys(jnp.zeros((SLOTS + 1,), jnp.int32), jnp.append(positions, start + len(toks) - 1))
        sampled, self.pools = self.run["decode_chunk"](
            self.params, tokens, positions, tables, jnp.asarray(chunk), jnp.int32(start), jnp.int32(len(toks)),
            self.tables([slot], decode=False), self.pools, keys, jnp.zeros((SLOTS + 1,), jnp.float32),
        )  # fmt: skip
        self.lengths[slot] = start + len(toks)
        return self._stepped(stepping, sampled), int(sampled[SLOTS])

    def held(self):
        """The pools' rows that a slot's tokens lie in, as numpy, a list a leaf."""
        out = []
        for s in range(SLOTS):
            blocks = self.linear[s, : -(-self.lengths[s] // self.bs)]
            out += [np.asarray(leaf[:, blocks]) for leaf in jax.tree.leaves(self.pools)]
        return out


LENGTHS = {"one": 1, "C-1": C - 1, "C": C, "C+1": C + 1, "2C": 2 * C}


@pytest.mark.parametrize("cached", [0, 3 * 16], ids=["cold", "cached-head"])
@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_mixed_step_is_the_decode_step_and_the_chunk_prefill(built, kind, length, cached):
    """A prompt fed in chunks that ride two slots' decode steps, against the
    same chunks prefilled between those steps: every token either way sampled
    is the same, the first token too, the pools agree on every block held, and
    the slot decodes on from there the same way. Behind a cached head of three
    blocks of 16 the sliding layers' blocks go round the ring's edge (a window of
    20 in blocks of 8: a ring of 5 where the sequence comes to hold 6 to 10)."""
    cfg, params, bs = built(kind)
    toks = _prompt(7, cached + LENGTHS[length], cfg.vocab_size)
    apart, mixed = _Paged(cfg, params, bs), _Paged(cfg, params, bs)
    chunks = [(at, toks[at : at + C]) for at in range(cached, len(toks), C)]
    got = {}
    for name, paged in (("apart", apart), ("mixed", mixed)):
        if cached:  # what a prefix hit finds in the pool: the head, put there by an earlier request's prefill
            paged.prefill(2, toks[:cached], 0, width=cached)
        out = []
        for at, part in chunks:
            if name == "apart":
                out.append((paged.decode([0, 1]), int(paged.prefill(2, part, at, width=C)[0])))
            else:
                out.append(paged.decode_chunk([0, 1], 2, part, at))
        paged.last[2] = out[-1][1]  # the first token: the next step's input in that slot
        out.append(paged.decode([0, 1, 2]))
        got[name] = out
    assert got["mixed"] == got["apart"]
    assert mixed.lengths == apart.lengths == [5 + len(chunks) + 1, 11 + len(chunks) + 1, len(toks) + 1, 0]
    for a, b in zip(apart.held(), mixed.held()):
        np.testing.assert_allclose(b, a, atol=1e-5, rtol=1e-5)


# -- (b) the engine against the uncached forward, prompts arriving while others decode -------------------


@pytest.fixture(scope="module")
def greedy(built):
    """``(kind, prompt, n) -> prompt + n tokens``, each the first choice of the
    uncached forward over everything before it (one compile a kind: the
    sequence padded to ``MAX_SEQ``, which a causal forward does not see)."""
    fns = {}

    def run(kind: str, prompt: list[int], n: int) -> list[int]:
        cfg, params, _ = built(kind)
        if kind not in fns:
            fns[kind] = jax.jit(lambda p, t: llama.forward(p, t, cfg))
        seq = list(prompt)
        for _ in range(n):
            padded = jnp.asarray([seq + [0] * (MAX_SEQ - len(seq))], jnp.int32)
            seq.append(int(jnp.argmax(fns[kind](params, padded)[0, len(seq) - 1])))
        return seq

    return run


class _Unfetched:
    """What a spied program hands the engine in place of a step's tokens:
    converting it to numpy is the engine's fetch of that step."""

    def __init__(self, n, value, log):
        self.n, self.value, self.log = n, value, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        return np.asarray(self.value)


def spy(engine, at_dispatch=None):
    """``("dispatch", n, host tokens, chunk)`` and ``("fetch", n)`` in the order
    the engine's thread makes them; ``chunk`` is ``(start, real tokens, slot or
    -1)`` of a step that carries one, else None."""
    log = []

    def spied(real):
        def program(params, tokens, prev, *rest):
            n = sum(ev[0] == "dispatch" for ev in log)
            if at_dispatch is not None:
                at_dispatch(n)
            chunk = tuple(int(x) for x in np.asarray(rest[-2])) if len(rest) > 5 else None
            log.append(("dispatch", n, np.asarray(tokens).copy(), chunk))
            nxt, pools = real(params, tokens, getattr(prev, "value", prev), *rest)
            return _Unfetched(n, nxt, log), pools

        return program

    engine._decode, engine._decode_chunk = spied(engine._decode), spied(engine._decode_chunk)
    return log


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_engine_feeds_prompts_while_others_decode(built, greedy, kind):
    """Prompts of one to three chunks arrive while others decode: every
    request's greedy tokens are the uncached forward's; a step is in flight
    across every chunk; no token goes through the host, the first included."""
    cfg, params, bs = built(kind)
    engine = ServeEngine(params, cfg, max_slots=SLOTS, block_size=bs, max_prefill_batch=2, chunk_width=C)
    lengths = [3, C, 2 * C + 5, C + 1, 2 * C]
    reqs = [ServeRequest(prompt=_prompt(i, n, cfg.vocab_size), max_new_tokens=new)
            for i, (n, new) in enumerate(zip(lengths, [24, 6, 9, 12, 5]))]  # fmt: skip
    arrive = {2: reqs[1:3], 6: reqs[3:4], 9: reqs[4:]}  # before dispatch n, on the engine's thread
    log = spy(engine, at_dispatch=lambda n: [engine.submit(r) for r in arrive.get(n, [])])
    engine.submit(reqs[0])
    engine.start()
    try:
        for r in reqs:
            assert r.wait(timeout=300) and r.error is None, r.error
        stats = engine.stats()
    finally:
        engine.stop()
    for r in reqs:
        assert r.tokens == greedy(kind, list(r.prompt), r.max_new_tokens)
    dispatches = [ev for ev in log if ev[0] == "dispatch"]
    order = [ev[:2] for ev in log]
    carried = [ev for ev in dispatches if ev[3] is not None]
    assert stats["chunk_steps"] == len(carried) == sum(-(-n // C) for n in lengths)
    assert stats["prefill_tokens"] == sum(ev[3][1] for ev in carried) == sum(lengths)
    for ev in carried[1:]:  # every chunk but the idle engine's first was enqueued with the step before it in flight
        assert order.index(("dispatch", ev[1])) < order.index(("fetch", ev[1] - 1))
    assert sum((ev[2] == -1).any() for ev in carried) >= 7  # ... and rode beside slots that were decoding
    assert all((ev[2] <= 0).all() for ev in dispatches)  # the first token is read where the step left it
    ends = [ev for ev in carried if ev[3][2] >= 0]
    assert len(ends) == len(reqs)
    for ev in ends:  # the step behind a prompt's last chunk steps that slot from the device's token
        nxt = dispatches[ev[1] + 1]
        assert nxt[2][ev[3][2]] == -1
    assert stats["tokens_discarded"] == 0 and stats["preemptions"] == 0


# -- (c) a request leaves mid-prompt: its blocks, the staged window blocks too, go back ------------------


def _mid_prompt(built, kind, **kw):
    """An engine turned by hand, a request of three chunks that holds a slot
    with one chunk fed and one more in flight."""
    cfg, params, bs = built(kind)
    engine = ServeEngine(params, cfg, max_slots=2, block_size=bs, chunk_width=C, enable_prefix_cache=False, **kw)
    start = (engine.cache.alloc.free_blocks, engine.cache.window_alloc.free_blocks if engine.cache.num_window_blocks else 0)
    req = engine.submit(ServeRequest(prompt=_prompt(3, 2 * C + 3, cfg.vocab_size), max_new_tokens=4))
    assert engine._admit() and engine._decode_once() and engine._decode_once()
    ((slot, st),) = [(i, s) for i, s in enumerate(engine._slots) if s is not None]
    assert st.feeding is not None and st.cache_len == 2 * C and not req.t_first
    assert engine.cache.alloc.used_blocks == -(-len(req.prompt) // bs)
    if engine.cache.num_window_blocks:
        # staged for the whole prompt at admission; those below the next chunk's window went back already
        below = max(0, 2 * C - engine.cache.window + 1) // bs
        assert engine.cache.window_alloc.used_blocks == len(engine.cache._staged[slot]) == -(-len(req.prompt) // bs) - below
        assert engine.cache.window_blocks_released == below and engine.cache.window_tables.held_blocks == 0
    return engine, req, start


def _free(engine):
    return (engine.cache.alloc.free_blocks, engine.cache.window_alloc.free_blocks if engine.cache.num_window_blocks else 0)


@pytest.mark.parametrize("how", ["preempted", "drained", "stopped", "failed"])
@pytest.mark.parametrize("kind", ["llama", "exaone_moe", "mla_moe"])
def test_a_request_that_leaves_mid_prompt_gives_its_blocks_back(built, greedy, kind, how):
    engine, req, start = _mid_prompt(built, kind)
    if how == "preempted":
        assert engine._preempt_youngest() and _free(engine) == start and engine.stats()["queue_depth"] == 1
        assert all(s is None for s in engine._slots)
        while not req.done.is_set():  # given a slot again, fed from the start, served
            engine._admit()
            assert engine._decode_once()
        assert req.tokens == greedy(kind, list(req.prompt), 4) and engine.stats()["tokens_discarded"] == 0
    elif how == "drained":
        engine.start()
        assert engine.drain(timeout=300) and req.error is None
        assert req.tokens == greedy(kind, list(req.prompt), 4)
        engine.stop()
    elif how == "stopped":
        engine.stop()
        assert req.done.is_set() and req.error == "engine stopped"
    else:
        def boom(*a):
            raise RuntimeError("device fell over")

        engine._decode_chunk = boom
        engine.start()
        assert req.wait(timeout=300) and "device fell over" in req.error and "device fell over" in engine.failed
        engine.stop()
    assert _free(engine) == start and all(s is None for s in engine._slots) and engine._in_flight is None


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_prefill_only_request_fed_in_chunks_exports_what_one_chunk_does(built, kind):
    """The hand-off of a prompt fed in three chunks beside a slot that
    decodes, against the same prompt fed whole on an idle engine: the same
    first token, the same blocks (a sliding layer's below the window are the
    trash block's on both sides: the receiver does not read them)."""
    cfg, params, bs = built(kind)
    prompt = _prompt(5, 2 * C + 3, cfg.vocab_size)
    payloads = []
    for width, busy in ((C, True), (4 * C, False)):
        engine = ServeEngine(params, cfg, max_slots=2, block_size=bs, chunk_width=width).start()
        try:
            other = engine.submit(ServeRequest(prompt=_prompt(6, 9, cfg.vocab_size), max_new_tokens=40)) if busy else None
            req = engine.submit(ServeRequest(prompt=prompt, max_new_tokens=8, prefill_only=True))
            assert req.wait(timeout=300) and req.error is None and req.handoff is not None
            assert other is None or (other.wait(timeout=300) and other.error is None)
            assert engine.stats()["chunk_steps"] == (4 if busy else 1)
            payloads.append(req.handoff)
        finally:
            engine.stop()
    chunked, whole = payloads
    assert chunked.generated == whole.generated and len(chunked.generated) == 1
    assert chunked.cache_len == whole.cache_len == len(prompt) and chunked.tokens == whole.tokens == prompt
    live = np.ones(chunked.k.shape[:2], bool)  # [layers, blocks]: which of a layer's blocks the receiver reads
    if cfg.layer_types:
        below = max(0, len(prompt) - cfg.sliding_window + 1) // bs
        live[[i for i, k in enumerate(cfg.cache_kinds) if k == "window"], :below] = False
    for a, b in ((chunked.k, whole.k), (chunked.v, whole.v)):
        assert a.shape == b.shape
        if a.size:
            # past the prompt's last token a block holds what its earlier owner left: compare the rows written
            rows = np.arange(a.shape[1] * bs).reshape(a.shape[1], bs) < len(prompt)
            mask = (live[:, :, None] & rows[None]).reshape(*a.shape[:3], *([1] * (a.ndim - 3)))
            np.testing.assert_allclose(np.where(mask, a, 0), np.where(mask, b, 0), atol=1e-5, rtol=1e-5)


# -- (d) the closed set: two programs, whatever the prompts ------------------------------------------


@pytest.fixture(scope="module")
def warmed(built):
    """An engine after one prompt and one decode step."""
    cfg, params, bs = built("llama")
    engine = ServeEngine(params, cfg, max_slots=SLOTS, block_size=bs, max_prefill_batch=2, chunk_width=C).start()
    engine.generate([1, 2, 3], max_new_tokens=3, timeout=300)
    yield engine
    engine.stop()


def _programs(engine):
    return engine._decode._cache_size(), engine._decode_chunk._cache_size()


# every chunk edge among them, and the longest prompt the window takes with three tokens to generate
@pytest.mark.parametrize("length", [1, 2, C - 1, C, C + 1, 2 * C - 1, 2 * C, 2 * C + 1, 5 * C, MAX_SEQ - C, MAX_SEQ - 3])
def test_a_prompt_of_any_length_compiles_nothing_more(warmed, length):
    assert _programs(warmed) == (1, 1)
    reqs = [warmed.submit(ServeRequest(prompt=_prompt(length, length, 512), max_new_tokens=3)) for _ in range(2)]
    for r in reqs:
        assert r.wait(timeout=300) and r.error is None
    assert _programs(warmed) == (1, 1)
    assert not hasattr(warmed, "_prefill_fns") and not hasattr(warmed, "_prefill_fn")


@pytest.mark.parametrize("off", [1, -1, "none", "negative"])
def test_a_chunk_width_that_is_no_whole_number_of_blocks_is_refused(built, off):
    cfg, params, bs = built("llama")
    width = {"none": 0, "negative": -bs}.get(off) if isinstance(off, str) else bs + off
    with pytest.raises(ValueError, match="positive multiple of block_size"):
        ServeEngine(params, cfg, block_size=bs, chunk_width=width)


def test_the_chunk_width_is_compiled_geometry(built):
    cfg, params, bs = built("llama")
    # one number for every engine; no prompt is longer than a slot's blocks, so neither is a chunk
    assert ServeEngine(params, cfg, block_size=bs).chunk_width == MAX_SEQ
    assert ServeEngine(params, cfg, block_size=bs, chunk_width=2 * bs).stats()["chunk_width"] == 2 * bs
