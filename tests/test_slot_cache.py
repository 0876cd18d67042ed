"""The seam between ``ServeEngine``'s loop and a slot's cache (``serve/slot_cache.py``, PR 48), for the
four kinds of cache that exist, at the tiny float32 widths of the benchmark's fixtures on the CPU:

* the one preempt-and-retry loop the engine keeps (``ServeEngine._make_writable`` round ``grow``): an
  engine whose pool is too small for its slots' answers preempts, serves every request the tokens an
  engine with room serves, and ends with nothing held but what its prefix cache indexes;
* what the seam hands the two programs as ``tables`` and as the chunk's tables: the tree, the shapes and
  the dtypes that ``models/generate.py::_table_of`` documents, in numpy (nothing is put on the device
  while a step is prepared);
* the programs an engine with a mixer's state runs over a prompt and two steps: its two, and no other.
"""

from __future__ import annotations

import json
import logging
import os

import jax
import numpy as np
import pytest

from benchmark.lib import models
from torchx_tpu.models import llama
from torchx_tpu.ops.paged_attention import TRASH_BLOCK
from torchx_tpu.serve import slot_cache
from torchx_tpu.serve.engine import ServeEngine, ServeRequest
from torchx_tpu.serve.kv_pool import window_ring

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "benchmark", "tests", "fixtures", "configs")
#: kind -> its class, the fixture with its published keys at test widths (None: ``llama.CONFIGS["tiny"]``), max_seq
KINDS = {
    "paged": (slot_cache.PagedCache, None, 128),
    "ring": (slot_cache.RingCache, "tiny-exaone-moe", 256),
    "state": (slot_cache.StateCache, "tiny-falcon-h1", 128),
    "rows": (slot_cache.RowsCache, "tiny-evabyte", 160),
}
#: a pool too small for the slots' answers: engine geometry, prompt lengths, new tokens a request
PRESSED = {
    # 16 blocks where four growing sequences come to need 24 (the case tests/test_serve_engine.py held until PR 48)
    "paged": (dict(max_slots=4, block_size=8, num_blocks=17), [3, 3, 3, 3], [40, 40, 40, 40]),
    "ring": (dict(max_slots=4, num_blocks=40, max_prefill_batch=2, chunk_width=16), [53, 60, 67, 74, 81, 30], [60, 60, 60, 60, 40, 50]),
    "state": (dict(max_slots=3, num_blocks=10, max_prefill_batch=2, chunk_width=16), [37, 20, 50, 33, 5, 41, 16], [20, 30, 10, 12, 9, 25, 14]),
    "rows": (dict(max_slots=3, num_blocks=27, max_prefill_batch=2, chunk_width=8), [37, 20, 50, 33, 13, 41, 16], [60, 90, 30, 12, 80, 45, 70]),
}


@pytest.fixture(scope="module")
def built():
    """kind -> (cfg, params, block size), made once a kind."""
    made = {}

    def of(kind: str):
        if kind not in made:
            _, fixture, max_seq = KINDS[kind]
            if fixture is None:
                cfg = llama.CONFIGS["tiny"]()
                made[kind] = (cfg, llama.init_params(cfg, jax.random.PRNGKey(0)), 8)
            else:
                with open(os.path.join(FIXTURES, fixture + ".json")) as f:
                    config = json.load(f)
                extra = {"ssm_chunk": 16} if kind == "state" else {}
                cfg = models.program_config(config, max_seq=max_seq, remat=False, **extra)
                made[kind] = (cfg, models.make_weights(config, 2147483659), int(config["deployment"]["block_size"]))
        return made[kind]

    return of


def _cache_nodes(cache):
    """Every node of the prefix cache's tree (none without one)."""
    nodes, stack = [], list(cache.prefix_cache._root.values()) if cache.prefix_cache is not None else []
    while stack:
        nodes.append(stack.pop())
        stack.extend(nodes[-1].children.values())
    return nodes


def _serve(params, cfg, geometry, prompts, new):
    """All requests queued ahead of the loop's start (so the steps are the same run after run), served, drained."""
    engine = ServeEngine(params, cfg, **geometry)
    reqs = [engine.submit(ServeRequest(p, max_new_tokens=m, temperature=0.7 if i == 2 else 0.0, seed=11 + i))
            for i, (p, m) in enumerate(zip(prompts, new))]  # fmt: skip
    engine.start()
    try:
        for r in reqs:
            assert r.wait(900) and not r.error, r.error
        assert engine.drain(60)
        return engine, [r.generated for r in reqs], engine.stats()
    finally:
        engine.stop()


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_pool_too_small_preempts_and_serves_what_an_engine_with_room_serves(built, kind):
    """One loop where there were three (``_ensure_capacity``, ``_ensure_window``, ``_ensure_rows``): grow, preempt the
    youngest where a pool is short, stop if that was us. The victim's step was in flight: that token is dropped, and
    fed again the request draws the same one (greedy, and request 2 sampled: the key is seed and position)."""
    cfg, params, bs = built(kind)
    geometry, lengths, new = PRESSED[kind]
    geometry = {"block_size": bs, **geometry}
    rng = np.random.default_rng(7)
    vocab = min(cfg.vocab_size, 250)
    shared = rng.integers(1, vocab, 2 * bs + 3).tolist()  # a head every other request has: prefix hits where the kind has a cache
    prompts = [(shared if i % 2 and n > 3 else []) + rng.integers(1, vocab, n).tolist() for i, n in enumerate(lengths)]
    prompts = [p[: cfg.max_seq - m - 1] for p, m in zip(prompts, new)]
    roomy = {**geometry, "num_blocks": None, "max_slots": len(prompts)}  # a slot a request and the default pool
    _, want, at_ease = _serve(params, cfg, roomy, prompts, new)
    engine, got, stats = _serve(params, cfg, geometry, prompts, new)
    assert at_ease["preemptions"] == 0 and stats["preemptions"] > 0 and stats["tokens_discarded"] > 0
    assert got == want and stats["requests_done"] == len(prompts)
    # nothing is held but what the prefix cache indexes: a block a node in the table's pool, a window block where it still has one
    cache, nodes = engine.cache, _cache_nodes(engine.cache)
    assert isinstance(cache, KINDS[kind][0]) and (cache.prefix_cache is None) == (kind in ("state", "rows"))
    assert cache.alloc.used_blocks == len(nodes) and (cache.tables.tables == TRASH_BLOCK).all()
    assert stats["kv_blocks_full"] == stats["kv_blocks_window"] == 0
    if kind == "ring":
        assert cache.window_alloc.used_blocks == sum(node.window is not None for node in nodes)
        assert (cache.window_tables.tables == TRASH_BLOCK).all() and not any(cache._staged)
    if kind == "rows":
        assert (cache.tables.stage == TRASH_BLOCK).all() and stats["cache_rows_held"] == stats["kv_blocks_pooled"] == 0


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_tables_a_step_takes_are_what_the_programs_document(built, kind):
    """``generate._table_of``: one array for a stack of one kind; ``{"full", "window"}`` where kinds mix (a ring a slot;
    the chunk's staged blocks lie block b at entry b); ``{"full", "state": [rows] int32}`` beside a mixer's store;
    ``{"full", "stage": [rows, W / C / block_size]}`` under EVA attention. The chunk's are one row of the same tree."""
    cfg, _, bs = built(kind)
    slots = 3
    cache = slot_cache.slot_cache(cfg, max_slots=slots, block_size=bs, num_blocks=None, num_window_blocks=None,
                                  max_prefill_batch=2, prefix_cache=True, prefix_cache_reserve=0.0)  # fmt: skip
    assert type(cache) is KINDS[kind][0]
    plan = cache.plan(list(range(1, 2 * bs + 2)))  # slot 1 holds a prompt of three blocks; slot 0 decodes; slot 2 is empty
    cache.place(1, plan)
    assert cache.grow(0, 0)
    step, chunk = cache.step_tables([0], [1]), cache.chunk_tables(1)
    per_slot = cache.blocks_per_slot
    staging = cfg.eva_window // max(1, cfg.eva_chunk) // bs
    beside = {  # what rides beside the block table: name -> (the step's shape, the chunk's)
        "paged": {},
        "ring": {"window": ((slots, window_ring(cfg.sliding_window, bs)), (1, per_slot))},
        "state": {"state": ((slots,), (1,))},
        "rows": {"stage": ((slots, staging), (1, staging))},
    }[kind]
    want_step = {"full": (slots, per_slot), **{name: shapes[0] for name, shapes in beside.items()}}
    want_chunk = {"full": (1, per_slot), **{name: shapes[1] for name, shapes in beside.items()}}
    if kind == "paged":
        want_step, want_chunk = want_step["full"], want_chunk["full"]
    is_table = lambda t: isinstance(t, np.ndarray) and t.dtype == np.int32  # noqa: E731 - numpy: no device call while a step is prepared
    assert all(map(is_table, jax.tree.leaves(step))) and all(map(is_table, jax.tree.leaves(chunk)))
    assert jax.tree.map(np.shape, step) == want_step and jax.tree.map(np.shape, chunk) == want_chunk
    # the parked slot's rows go where an empty slot's go; the chunk's are its own; a decoding slot's state row is its own
    full, chunk_full = (step, chunk) if kind == "paged" else (step["full"], chunk["full"])
    assert (full[1:] == TRASH_BLOCK).all() and full[0, 0] != TRASH_BLOCK
    assert (chunk_full[0] != TRASH_BLOCK).sum() == len(plan.blocks) - (staging if kind == "rows" else 0)  # its staging lies beside
    if kind == "state":
        assert step["state"].tolist() == [1, 0, 0] and chunk["state"].tolist() == [2]
    if kind == "ring":
        assert (step["window"][1:] == TRASH_BLOCK).all() and (chunk["window"][0, :3] != TRASH_BLOCK).all()
    if kind == "rows":
        assert (step["stage"][1:] == TRASH_BLOCK).all() and (chunk["stage"] != TRASH_BLOCK).all() and (step["stage"][0] != TRASH_BLOCK).all()
    # the copies are the step's own: the loop goes on to change the tables while it is in flight
    cache.release(0)
    assert full[0, 0] != TRASH_BLOCK and (cache.tables.tables[0] == TRASH_BLOCK).all()


def test_an_engine_with_a_mixer_runs_its_two_programs_and_no_other(built):
    """The chunk's state row reaches ``_decode_chunk`` as an ``np.int32`` array: until PR 48 it was a Python list cast
    on the device, one ``jit(convert_element_type)`` ahead of every step that carried a chunk. Everything compiles anew
    here (``jax.clear_caches``), so a program run shows as a program compiled."""
    cfg, params, bs = built("state")
    engine = ServeEngine(params, cfg, max_slots=2, block_size=bs, chunk_width=16)
    compiled = []

    class Listen(logging.Handler):
        def emit(self, record):
            if record.getMessage().startswith("Compiling"):
                compiled.append(record.getMessage().split()[1])

    log, listen = logging.getLogger("jax._src.interpreters.pxla"), Listen()
    log.addHandler(listen)
    try:
        with jax.log_compiles():
            jax.clear_caches()
            req = engine.submit(ServeRequest(list(range(1, 20)), max_new_tokens=3))  # two chunks, then two steps
            assert engine._admit()
            while not req.done.is_set():
                assert engine._decode_once()
    finally:
        log.removeHandler(listen)
    assert engine.chunk_steps == 2 and engine.steps >= 4 and len(req.generated) == 3
    assert sorted(compiled) == ["jit(_decode)", "jit(_decode_chunk)"]
