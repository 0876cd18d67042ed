"""Hot-path spans and device scopes (``torchx_tpu/obs/hot.py``): recorded on
the profiler's clock while a ``jax.profiler`` session runs, nothing at all
without one; scope names in the lowered programs' ``op_name``s."""

import os
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchx_tpu.models import generate as gen, llama, moe
from torchx_tpu.obs import hot
from torchx_tpu.obs import metrics as obs_metrics
from torchx_tpu.obs import trace as obs_trace
from torchx_tpu.serve.engine import ServeEngine, ServeRequest

ENGINE_SPANS = [
    hot.SERVE_ADMIT,
    *hot.SERVE_SPAN_TREE[hot.SERVE_ADMIT],
    hot.SERVE_DECODE,
    *hot.SERVE_SPAN_TREE[hot.SERVE_DECODE],
    hot.SERVE_IDLE,
    hot.SERVE_KV_IMPORT,
    hot.SERVE_COW_COPY,
]
#: stats() count -> what of a span adds up to it over a run
RUNNING_COUNTS = {
    "prefill_rounds": lambda evs: sum(1 for ev in _rounds(evs)),
    "prefill_tokens": lambda evs: sum(ev[3]["tokens"] for ev in _rounds(evs)),
    "prefill_padded_tokens": lambda evs: sum(ev[3]["rows_padded"] * ev[3]["width"] for ev in _rounds(evs)),
    "prefill_programs_built": lambda evs: sum(ev[3]["built"] for ev in _rounds(evs)),
    "slot_steps_stalled": lambda evs: sum(ev[3]["slots_stalled"] for ev in _rounds(evs)),
}


def _rounds(events):
    return [ev for ev in events if ev[0] == hot.SERVE_ADMIT and "rows" in ev[3]]


def _session(tmp_path, body):
    """Run ``body()`` under a profiler session with the Python tracer off and
    return the host plane's lines as ``[[(name, start_ns, end_ns, stats)]]``."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (run,) = os.listdir(tmp_path / "plugins" / "profile")
    (pb,) = [f for f in os.listdir(tmp_path / "plugins" / "profile" / run) if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(str(tmp_path / "plugins" / "profile" / run / pb))
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    return [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)) for e in line.events]
        for line in host.lines
    ]


def _drive(engine):
    """Two admission rounds, some decode steps, a transferred prefill, and an
    idle turn of the loop."""
    reqs = [engine.submit(ServeRequest(prompt=[i + 1, i + 2, i + 3], max_new_tokens=5)) for i in range(3)]
    for r in reqs:
        assert r.wait(timeout=120) and not r.error
    head = engine.submit(ServeRequest(prompt=list(range(1, 20)), max_new_tokens=4, prefill_only=True))
    assert head.wait(timeout=120) and head.handoff is not None
    p = head.handoff
    moved = ServeRequest(prompt=list(p.tokens), max_new_tokens=p.max_new_tokens, generated=list(p.generated))
    engine.submit_prefilled(moved, p.k, p.v, p.cache_len, last_tok=p.generated[-1])
    assert moved.wait(timeout=120) and not moved.error
    idle = threading.Event()
    idle.wait(0.05)
    _force_copy_on_write(engine)


def _force_copy_on_write(engine):
    """On an idle engine, from the calling thread: slot 0 gets a tail block that
    another holder shares, and is made to write into it (no request of a
    running engine comes to that: the cache adopts whole blocks only)."""
    blocks = engine.alloc.alloc(2)
    engine.tables.assign(0, blocks)
    engine.alloc.retain([blocks[1]])
    assert engine._ensure_capacity(0, engine.block_size)
    assert engine.tables.blocks_of(0)[1] != blocks[1]
    engine.alloc.release([blocks[1]])
    engine.alloc.free(engine.tables.release(0))


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.CONFIGS["tiny"]()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def traced_drive(tiny, tmp_path_factory):
    """A traced ``_drive``: the host plane's lines, ``stats()`` and the decode
    tokens counted before and after it."""
    cfg, params = tiny
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, max_prefill_batch=2).start()
    counted = lambda: {phase: obs_metrics.SERVE_TOKENS.value(phase=phase) for phase in ("prefill", "decode")}  # noqa: E731
    try:
        _drive(engine)  # compile outside the session
        before, tokens_before = engine.stats(), counted()
        lines = _session(tmp_path_factory.mktemp("trace"), lambda: _drive(engine))
        after, tokens_after = engine.stats(), counted()
    finally:
        engine.stop()
    return {
        "lines": lines,
        "before": before,
        "after": after,
        "tokens": {phase: tokens_after[phase] - tokens_before[phase] for phase in tokens_after},
    }


@pytest.fixture(scope="module")
def engine_line(traced_drive):
    """The engine thread's line of a traced run: every ``serve.*`` event."""
    with_spans = [ln for ln in traced_drive["lines"] if any(name == hot.SERVE_DECODE for name, *_ in ln)]
    assert len(with_spans) == 1, "the engine's spans lie on one thread's line"
    return [ev for ev in with_spans[0] if ev[0].startswith("serve.")]


@pytest.fixture(scope="module")
def serve_events(traced_drive):
    """Every ``serve.*`` event of the traced run in time order, whatever
    thread it lies on (the forced copy-on-write is the test thread's)."""
    return sorted((ev for ln in traced_drive["lines"] for ev in ln if ev[0].startswith("serve.")), key=lambda ev: ev[1])


@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_engine_span_is_recorded(serve_events, name):
    assert any(ev[0] == name for ev in serve_events)


@pytest.mark.parametrize("parent", sorted(hot.SERVE_SPAN_TREE))
def test_children_lie_inside_their_parent_and_tile_it(engine_line, parent):
    parents = [ev for ev in engine_line if ev[0] == parent]
    kids = hot.SERVE_SPAN_TREE[parent]
    assert parents
    for child in kids:
        for _, s, e, _ in (ev for ev in engine_line if ev[0] == child):
            assert any(ps <= s and e <= pe for _, ps, pe, _ in parents), child
    full = [
        [c for c in engine_line if c[0] in kids and ps <= c[1] and c[2] <= pe]
        for _, ps, pe, _ in parents
    ]
    # a round that prefilled, a step that ran: every child once, in order
    assert any([c[0] for c in sorted(inside, key=lambda c: c[1])] == list(kids) for inside in full)


@pytest.mark.parametrize(
    "name,attrs",
    [
        (hot.SERVE_DECODE, {"step", "active", "steps_overlapped", "tokens_discarded"}),
        (hot.SERVE_ADMIT, {"rows", "width", "cached_tokens", "tokens", "queue_depth", "rows_padded", "slots_stalled", "built"}),
        (hot.SERVE_DECODE_COMMIT, {"finished"}),
        (hot.SERVE_KV_IMPORT, {"blocks", "cache_len"}),
    ],
)
def test_span_attributes(engine_line, name, attrs):
    events = [ev for ev in engine_line if ev[0] == name and ev[3]]
    assert events and all(attrs <= set(ev[3]) for ev in events)
    if name == hot.SERVE_DECODE:
        steps = [ev[3]["step"] for ev in events]
        # active 0: a turn that enqueues nothing and only fetches the step in flight
        assert steps == sorted(steps) and all(0 <= ev[3]["active"] <= 4 for ev in events)
        assert any(ev[3]["active"] for ev in events)
        overlapped = [ev[3]["steps_overlapped"] for ev in events]
        assert overlapped == sorted(overlapped) and overlapped[-1] > overlapped[0]
        assert all(ev[3]["tokens_discarded"] == 0 for ev in events)  # no EOS, no pool pressure
    if name == hot.SERVE_ADMIT:
        assert {ev[3]["rows"] for ev in events} <= {1, 2} and all(ev[3]["width"] >= 16 for ev in events)


@pytest.mark.parametrize(
    "name,calls",
    [
        (hot.SERVE_DECODE_DISPATCH, {"_decode"}),
        (hot.SERVE_PREFILL_DISPATCH, {"_prefill"}),
        (hot.SERVE_KV_IMPORT, {"scatter"}),  # eager updates of the pools' leaves, as the copy is
        (hot.SERVE_COW_COPY, {"scatter"}),
    ],
)
def test_a_compiled_call_lies_inside_the_span_that_names_it(traced_drive, name, calls):
    """What a reader links a program run by: the runtime's own ``PjitFunction(...)``
    event of each compiled call, on the calling thread's line, inside the span
    (rounds, steps, the block import, the forced copy-on-write)."""
    found = 0
    for line in traced_drive["lines"]:
        made = [(ev[0][len("PjitFunction("):-1], ev[1]) for ev in line if ev[0].startswith("PjitFunction(")]
        for span in (ev for ev in line if ev[0] == name):
            inside = {fn for fn, t in made if span[1] <= t < span[2]}
            assert inside & calls, (name, inside)
            found += 1
    assert found >= 1


@pytest.mark.parametrize("count", sorted(RUNNING_COUNTS))
def test_running_count_is_the_sum_of_its_span_attribute(traced_drive, serve_events, count):
    """What ``stats()`` counts over a traced run is what the spans of that run add up to."""
    grown = traced_drive["after"][count] - traced_drive["before"][count]
    assert grown == RUNNING_COUNTS[count](serve_events) and (grown > 0 or count == "prefill_programs_built")


def test_decode_tokens_are_counted_once_a_step_and_add_up_as_before(traced_drive):
    """``_commit_step`` adds a step's tokens to ``SERVE_TOKENS`` in one call: over
    ``_drive`` that is three requests' four decoded tokens each and the moved
    request's three, as when it was once a slot."""
    assert traced_drive["tokens"] == {"prefill": 4.0, "decode": 15.0}
    assert traced_drive["after"]["tokens_out"] - traced_drive["before"]["tokens_out"] == 19


# -- an admission round counted where it happens: a hand-built queue, the loop turned by hand ---------------


@pytest.fixture(scope="module")
def hand_driven(tiny, tmp_path_factory):
    """Four slots, rounds of at most two rows, a clock the test sets: two long
    requests take two slots; then three more arrive for the two that are free."""
    cfg, params = tiny
    now = [1.0]
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, max_prefill_batch=2, clock=lambda: now[0])
    ask = lambda n, new: engine.submit(ServeRequest(prompt=list(range(1, n + 1)), max_new_tokens=new))  # noqa: E731
    out = {"engine": engine}

    def body():
        first = [ask(3, 2), ask(4, 50)]
        assert engine._admit()
        now[0] = 2.0
        third = ask(5, 50)
        now[0] = 2.5
        late = [ask(6, 50), ask(7, 50)]
        now[0] = 3.0
        assert engine._admit() and not engine._admit()  # two rows into the two free slots; then none is free
        while not first[0].done.is_set():
            assert engine._decode_once()
        now[0] = 5.0
        assert engine._admit()
        assert engine._preempt_youngest()  # the last one in: back to the head of the queue
        now[0] = 7.0
        assert engine._admit()
        assert third.t_first == 3.0 and late[1].t_first == 5.0  # kept from the first admission

    lines = _session(tmp_path_factory.mktemp("rounds"), body)
    out["rounds"] = [ev[3] for ln in lines for ev in ln if ev[0] == hot.SERVE_ADMIT and "rows" in ev[3]]
    return out


@pytest.mark.parametrize(
    "round_no,expected",
    [
        (0, {"rows": 2, "rows_padded": 2, "slots_stalled": 0, "built": 1, "tokens": 7, "queue_depth": 0}),
        (1, {"rows": 2, "rows_padded": 2, "slots_stalled": 2, "built": 0, "tokens": 11, "queue_depth": 1}),
        (2, {"rows": 1, "rows_padded": 1, "slots_stalled": 3, "built": 1, "tokens": 7, "queue_depth": 0}),
        # the preempted request comes back alone, into the slot it left
        (3, {"rows": 1, "rows_padded": 1, "slots_stalled": 3, "built": 0, "queue_depth": 0}),
    ],
)
def test_round_attributes_against_a_hand_built_queue(hand_driven, round_no, expected):
    got = hand_driven["rounds"][round_no]
    assert {k: got[k] for k in expected} == expected and got["width"] == 16


def test_stats_sum_the_rounds_of_a_hand_built_queue(hand_driven):
    rounds, counts = hand_driven["rounds"], hand_driven["engine"].stats()
    assert counts["prefill_rounds"] == 4 and counts["slot_steps_stalled"] == 0 + 2 + 3 + 3
    assert counts["prefill_tokens"] == sum(r["tokens"] for r in rounds)
    assert counts["prefill_padded_tokens"] == 16 * (2 + 2 + 1 + 1) and counts["prefill_programs_built"] == 2


def test_without_a_session_the_engine_leaves_no_record(tiny, tmp_path, monkeypatch):
    """No launcher span object, no file under ``$TPX_OBS_DIR``: what the loop
    used to write per prefill and per 64 steps (``serve.prefill``,
    ``serve.window``) is gone."""
    obs_dir = tmp_path / "obs_engine"
    monkeypatch.setenv("TPX_OBS_DIR", str(obs_dir))
    made = []
    real_init = obs_trace.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(kw.get("name", a[0] if a else "?"))
        real_init(self, *a, **kw)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting_init)
    cfg, params = tiny
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, max_prefill_batch=2).start()
    try:
        _drive(engine)
        reqs = [engine.submit(ServeRequest(prompt=[7, 8, 9], max_new_tokens=70)) for _ in range(2)]
        for r in reqs:
            assert r.wait(timeout=120) and not r.error
        assert engine.steps >= 64
        counts = engine.stats()
    finally:
        engine.stop()
    # ... and the running counts count all the same
    assert counts["prefill_rounds"] >= 4 and counts["prefill_tokens"] >= 3 * 3 + 19 + 2 * 3
    assert counts["prefill_padded_tokens"] >= counts["prefill_tokens"] and counts["prefill_programs_built"] >= 2
    assert counts["slot_steps_stalled"] >= 0
    assert made == []
    assert not obs_dir.exists() or not [f for _, _, fs in os.walk(obs_dir) for f in fs]


# -- device scopes ---------------------------------------------------------------


def _op_names(lowered) -> set[str]:
    comps = set()
    for loc in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)):
        for part in loc.split("/"):
            while (m := re.match(r"^[A-Za-z_]+\((.*)\)$", part)) is not None:
                part = m.group(1)
            comps.add(part)
    return comps


def _serving_programs(cfg):
    init, _ = llama.model_fns(cfg)
    params = init(cfg, jax.random.PRNGKey(0))
    pools = gen.init_kv_pools(cfg, 9, 16)
    slots, bps = 4, cfg.max_seq // 16
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    keys = jnp.zeros((slots, 2), jnp.uint32)
    temps = jnp.zeros((slots,), jnp.float32)
    decode = jax.jit(
        lambda p, t, pos, tab, pl, k, tm: gen.paged_decode_step(p, t, pos, tab, pl, cfg, k, tm)
    ).lower(params, i32(slots), i32(slots), i32(slots, bps), pools, keys, temps)
    prefill = jax.jit(
        lambda p, t, pre, suf, tab, pl, k, tm: gen.paged_prefill_chunk(p, t, pre, suf, tab, pl, cfg, k, tm)
    ).lower(params, i32(slots, 32), i32(slots), i32(slots) + 1, i32(slots, bps), pools, keys, temps)
    return {"decode": decode, "prefill": prefill}


def _train_step(cfg):
    from torchx_tpu.train import step as tl
    from torchx_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(fsdp=1), devices=jax.devices()[:1])
    optimizer = tl.make_optimizer()
    state = jax.eval_shape(lambda: tl.init_state(cfg, mesh, optimizer))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    return tl.make_train_step(cfg, mesh, optimizer).lower(state, batch)


@pytest.fixture(scope="module")
def lowered():
    dense, sparse = llama.CONFIGS["tiny"](), moe.moe_tiny()
    out = {f"dense.{k}": _op_names(v) for k, v in _serving_programs(dense).items()}
    out.update({f"moe.{k}": _op_names(v) for k, v in _serving_programs(sparse).items()})
    out["dense.train"] = _op_names(_train_step(dense))
    out["moe.train"] = _op_names(_train_step(sparse))
    return out


SERVING = (hot.EMBED, hot.LAYERS, hot.NORM, hot.ATTN, hot.APPEND_KV, hot.PAGED_ATTENTION, hot.GATHER_KV,
           hot.SCORES, hot.VALUES, hot.LM_HEAD, hot.SAMPLE)  # fmt: skip
TRAINING = (hot.EMBED, hot.LAYERS, hot.NORM, hot.ATTN, hot.ATTN_KERNEL, hot.LM_HEAD, hot.LOSS, hot.GRAD_CLIP,
            hot.OPTIMIZER)  # fmt: skip
EXPERTS = (hot.MOE_ROUTER, hot.MOE_DISPATCH, hot.MOE_EXPERTS, hot.MOE_COMBINE)
CASES = (
    [(f"dense.{p}", s) for p in ("decode", "prefill") for s in SERVING + (hot.MLP,)]
    + [(f"moe.{p}", s) for p in ("decode", "prefill") for s in EXPERTS]
    + [("dense.train", s) for s in TRAINING + (hot.MLP,)]
    + [("moe.train", s) for s in EXPERTS]
)


@pytest.mark.parametrize("program,scope", CASES)
def test_scope_names_reach_the_lowered_program(lowered, program, scope):
    assert scope in lowered[program]


# -- training spans -----------------------------------------------------------------


@pytest.mark.parametrize("name", [hot.TRAIN_DATA_WAIT, hot.TRAIN_H2D])
def test_prefetcher_spans(tmp_path, name):
    from torchx_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchx_tpu.parallel.prefetch import device_prefetch

    mesh = make_mesh(MeshConfig(fsdp=1), devices=jax.devices()[:1])

    def drain():
        feed = device_prefetch(({"tokens": np.zeros((2, 8), np.int32)} for _ in range(4)), mesh, depth=2)
        assert len(list(feed)) == 4
        feed.close()

    lines = _session(tmp_path, drain)
    assert sum(ev[0] == name for ln in lines for ev in ln) >= 4
