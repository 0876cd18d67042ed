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

#: what the engine records: parent -> the children that tile it (admission is planning alone; the other
#: children ``hot.SERVE_SPAN_TREE`` names under ``serve.admit`` are those of traces from before PR 40)
RECORDED_TREE = {hot.SERVE_ADMIT: (hot.SERVE_ADMIT_PLAN,), hot.SERVE_DECODE: hot.SERVE_SPAN_TREE[hot.SERVE_DECODE]}
ENGINE_SPANS = [
    *(name for parent, kids in RECORDED_TREE.items() for name in (parent, *kids)),
    hot.SERVE_IDLE,
    hot.SERVE_KV_IMPORT,
    hot.SERVE_COW_COPY,
]
#: stats() count -> what of a span adds up to it over a run
RUNNING_COUNTS = {
    "chunk_steps": lambda evs: sum(1 for ev in _chunk_turns(evs)),
    "prefill_tokens": lambda evs: sum(ev[3]["chunk_tokens"] for ev in _chunk_turns(evs)),
    "prefill_padded_tokens": lambda evs: sum(ev[3]["chunk_width"] for ev in _chunk_turns(evs)),
}


def _chunk_turns(events):
    """The ``serve.decode`` turns whose step carried a chunk of a prompt."""
    return [ev for ev in events if ev[0] == hot.SERVE_DECODE and ev[3].get("chunk_tokens", 0) > 0]


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
    """Prompts fed while others decode, one of them in two chunks, some
    decode steps, a transferred prefill, and an idle turn of the loop."""
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
    blocks = engine.cache.alloc.alloc(2)
    engine.cache.tables.assign(0, blocks)
    engine.cache.alloc.retain([blocks[1]])
    assert engine._make_writable(0, engine.block_size)
    assert engine.cache.tables.blocks_of(0)[1] != blocks[1]
    engine.cache.alloc.release([blocks[1]])
    engine.cache.alloc.release(engine.cache.tables.release(0))


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.CONFIGS["tiny"]()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def traced_drive(tiny, tmp_path_factory):
    """A traced ``_drive``: the host plane's lines, ``stats()`` and the decode
    tokens counted before and after it."""
    cfg, params = tiny
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, max_prefill_batch=2, chunk_width=16).start()
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


@pytest.mark.parametrize("parent", sorted(RECORDED_TREE))
def test_children_lie_inside_their_parent_and_tile_it(engine_line, parent):
    parents = [ev for ev in engine_line if ev[0] == parent]
    kids = RECORDED_TREE[parent]
    assert parents
    for child in kids:
        for _, s, e, _ in (ev for ev in engine_line if ev[0] == child):
            assert any(ps <= s and e <= pe for _, ps, pe, _ in parents), child
    full = [
        [c for c in engine_line if c[0] in kids and ps <= c[1] and c[2] <= pe]
        for _, ps, pe, _ in parents
    ]
    # an admission that gave a slot, a step that ran: every child once, in order
    assert any([c[0] for c in sorted(inside, key=lambda c: c[1])] == list(kids) for inside in full)


@pytest.mark.parametrize(
    "name,attrs",
    [
        (hot.SERVE_DECODE, {"step", "active", "chunk_tokens", "chunk_width", "steps_overlapped", "tokens_discarded"}),
        (hot.SERVE_ADMIT, {"admitted", "cached_tokens", "queue_depth", "kv_bytes_per_token"}),
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
        # three prompts of 3 tokens, and of the 19-token one what its cached head of a block leaves to feed
        assert sorted(ev[3]["chunk_tokens"] for ev in events if ev[3]["chunk_tokens"]) == [3, 3, 3, 3]
        assert {ev[3]["chunk_width"] for ev in events} == {16}
    if name == hot.SERVE_ADMIT:
        # nothing a reader of the admission rounds looks for: no round's attribute outlives the rounds
        assert {ev[3]["admitted"] for ev in events} <= {1, 2} and max(ev[3]["cached_tokens"] for ev in events) == 16
        assert not any({"rows", "tokens", "rows_padded", "slots_stalled"} & set(ev[3]) for ev in events)


def test_a_mixers_state_rides_every_decode_span_and_no_other_engines(tmp_path, engine_line):
    """``state_bytes_per_slot`` on ``serve.decode`` is what ``benchmark/layer_metrics/
    engine.state_bytes_per_slot.py`` reads; an engine without recurrent state does not carry it."""
    cfg = llama.CONFIGS["tiny"](ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2)
    engine = ServeEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=2, block_size=16, chunk_width=16).start()
    try:
        lines = _session(tmp_path, lambda: engine.generate([1, 2, 3], 4, timeout=120))
        want = engine.stats()["state_bytes_per_slot"]
    finally:
        engine.stop()
    turns = [ev for ln in lines for ev in ln if ev[0] == hot.SERVE_DECODE]
    assert turns and want == cfg.n_layers * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert all(ev[3]["state_bytes_per_slot"] == want for ev in turns)
    assert not any("state_bytes_per_slot" in ev[3] for ev in engine_line)


def test_linear_layers_state_rides_every_decode_span_over_the_layers_that_have_it(tmp_path):
    """The same attribute where three layers of four are linear (a Gated DeltaNet state a slot and no K/V): counted
    over the linear layers alone, as ``kv_bytes_per_token`` on ``serve.admit`` is over the attending one."""
    cfg = llama.CONFIGS["tiny"](n_layers=4, layer_types=("linear", "linear", "linear", "full"), gdn_heads=4, gdn_key_heads=2, gdn_head_dim=8)
    engine = ServeEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=2, block_size=16, chunk_width=16).start()
    try:
        lines = _session(tmp_path, lambda: engine.generate([1, 2, 3], 4, timeout=120))
        stats = engine.stats()
    finally:
        engine.stop()
    turns = [ev for ln in lines for ev in ln if ev[0] == hot.SERVE_DECODE]
    admits = [ev for ln in lines for ev in ln if ev[0] == hot.SERVE_ADMIT]
    assert turns and stats["state_bytes_per_slot"] == 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    assert all(ev[3]["state_bytes_per_slot"] == stats["state_bytes_per_slot"] for ev in turns)
    assert admits and all(ev[3]["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 4 == stats["kv_bytes_per_token"] for ev in admits)


def test_rows_that_are_not_tokens_ride_every_decode_span_and_no_other_engines(tmp_path, engine_line):
    """``cache_rows_held``, ``cache_tokens_held``, ``cache_rows_read``, ``kv_blocks_pooled`` and the running
    ``pooled_blocks_promoted`` on ``serve.decode`` are what ``benchmark/layer_metrics/engine.cache_rows_per_token.py``
    and ``kernels.decode_eva_hbm_pct.py`` read: an engine under EVA attention carries them, counted from the cache
    coordinate, and no other engine does."""
    cfg = llama.CONFIGS["tiny"](n_kv_heads=4, eva_window=32, eva_chunk=4, max_seq=96)
    engine = ServeEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=2, block_size=4, chunk_width=8).start()
    try:
        lines = _session(tmp_path, lambda: engine.generate(list(range(1, 31)), 40, timeout=120))
        stats = engine.stats()
    finally:
        engine.stop()
    turns = [ev[3] for ln in lines for ev in ln if ev[0] == hot.SERVE_DECODE]
    eva = {"cache_rows_held", "cache_tokens_held", "cache_rows_read", "kv_blocks_pooled", "pooled_blocks_promoted"}
    assert turns and all(eva <= set(t) for t in turns)
    # one slot decoding position t reads 8 pooled rows a finished window and its window's rows up to t
    stepped = [t for t in turns if t["active"] == 1 and not t["chunk_tokens"]]
    assert {t["cache_rows_read"] for t in stepped} >= {31, 32, 8 + 1, 8 + 32, 16 + 1}  # positions 30, 31, 32, 63, 64
    assert all(t["cache_rows_held"] <= t["cache_tokens_held"] + 8 for t in turns)
    deep = [t for t in turns if t["cache_tokens_held"] > 64]
    assert deep and all(t["cache_rows_held"] < 0.7 * t["cache_tokens_held"] for t in deep)
    assert stats["pooled_blocks_promoted"] == 4 and stats["window_blocks_released"] == 16 and turns[-1]["pooled_blocks_promoted"] == 4
    assert not any(eva & set(ev[3]) for ev in engine_line)


@pytest.mark.parametrize(
    "name,calls",
    [
        (hot.SERVE_DECODE_DISPATCH, {"_decode", "_decode_chunk"}),
        (hot.SERVE_KV_IMPORT, {"scatter"}),  # eager updates of the pools' leaves, as the copy is
        (hot.SERVE_COW_COPY, {"scatter"}),
    ],
)
def test_a_compiled_call_lies_inside_the_span_that_names_it(traced_drive, name, calls):
    """What a reader links a program run by: the runtime's own ``PjitFunction(...)``
    event of each compiled call, on the calling thread's line, inside the span
    (steps with and without a chunk, the block import, the forced copy-on-write)."""
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
    assert grown == RUNNING_COUNTS[count](serve_events) and grown > 0


def test_decode_tokens_are_counted_once_a_step_and_add_up_as_before(traced_drive):
    """``_commit_step`` counts a prompt's first token once as ``prefill``, where
    the step that carried its last chunk is committed, and a step's other tokens
    in one call as ``decode``: over ``_drive`` that is four first tokens (three
    requests' and the ``prefill_only`` one's), three requests' four decoded
    tokens each and the moved request's three, as when a round fetched ``first``."""
    assert traced_drive["tokens"] == {"prefill": 4.0, "decode": 15.0}
    assert traced_drive["after"]["tokens_out"] - traced_drive["before"]["tokens_out"] == 19


def test_every_mixed_program_is_called_inside_a_turn_that_says_what_rides_it(traced_drive, engine_line):
    """``_decode_chunk`` inside the ``.dispatch`` of the turns with ``chunk_tokens``
    and of no other, ``_decode`` inside the others': a reader tells the two
    programs' turns apart by the span alone."""
    (line,) = [ln for ln in traced_drive["lines"] if any(ev[0] == hot.SERVE_DECODE for ev in ln)]
    calls = [(ev[0][len("PjitFunction("):-1], ev[1]) for ev in line if ev[0].startswith("PjitFunction(_decode")]
    turns = [ev for ev in engine_line if ev[0] == hot.SERVE_DECODE]
    called = [{fn for fn, t in calls if turn[1] <= t < turn[2]} for turn in turns]  # a call shows at two levels: a set
    assert all(len(fns) <= 1 for fns in called)  # one program a turn, or none where the turn only fetched
    for turn, fns in zip(turns, called):
        assert (turn[3]["chunk_tokens"] > 0) == (fns == {"_decode_chunk"}), (fns, turn[3])
    assert called.count({"_decode_chunk"}) == len(_chunk_turns(engine_line)) == 4 and {"_decode"} in called


# -- a prompt counted where it is fed: a hand-built queue, the loop turned by hand ---------------------------


@pytest.fixture(scope="module")
def hand_driven(tiny, tmp_path_factory):
    """Four slots, at most two requests mid-prompt, chunks of 16, a clock the
    test sets: a short and a long request arrive, then three more."""
    cfg, params = tiny
    now = [1.0]
    engine = ServeEngine(
        params, cfg, max_slots=4, block_size=16, max_prefill_batch=2, chunk_width=16, clock=lambda: now[0]
    )
    ask = lambda n, new: engine.submit(ServeRequest(prompt=list(range(1, n + 1)), max_new_tokens=new))  # noqa: E731
    out = {"engine": engine}

    def turn(at):
        now[0] = at
        admitted = engine._admit()
        assert engine._decode_once()
        return admitted

    def body():
        first = [ask(3, 2), ask(40, 50)]
        assert turn(2.0)  # both get a slot; the step carries the short prompt, whole
        late = [ask(5, 50), ask(6, 50), ask(7, 50)]
        assert turn(3.0)  # one is mid-prompt, so one more may be: a slot for the first of the three
        assert first[0].t_first == 3.0 and not first[1].t_first  # its first token came with that step's fetch
        assert not turn(4.0) and not turn(5.0)  # two mid-prompt: nobody is admitted, though a slot came free
        assert first[0].done.is_set() and not first[1].t_first  # the long prompt's last chunk is in flight
        assert turn(6.0) and turn(7.0)  # the long prompt is fed: the last two come in one by one
        assert first[1].t_first == 6.0
        assert engine._preempt_youngest()  # the last one in, its prompt not yet fed: back to the head of the queue
        assert turn(8.0) and not turn(9.0)
        assert late[2].t_first == 9.0 and engine.stats()["queue_depth"] == 0

    lines = _session(tmp_path_factory.mktemp("chunks"), body)
    out["turns"] = [ev[3] for ln in lines for ev in ln if ev[0] == hot.SERVE_DECODE]
    out["admits"] = [ev[3] for ln in lines for ev in ln if ev[0] == hot.SERVE_ADMIT and "admitted" in ev[3]]
    return out


@pytest.mark.parametrize(
    "turn_no,expected",
    [
        (0, {"chunk_tokens": 3, "active": 1}),  # the short prompt ends in this step: its slot has a token coming
        (1, {"chunk_tokens": 16, "active": 1}),  # the long one's first chunk, beside the short request's second token
        (2, {"chunk_tokens": 16, "active": 0}),  # whose budget is then spent: the chunk rides alone
        (3, {"chunk_tokens": 8, "active": 1}),  # its last: 40 = 16 + 16 + 8
        (4, {"chunk_tokens": 5, "active": 2}),  # oldest first: the third request's prompt, beside the long one's decode row
        (5, {"chunk_tokens": 6, "active": 3}),
        (6, {"chunk_tokens": 7, "active": 4}),  # given a slot again after its preemption
        (7, {"chunk_tokens": 0, "active": 4}),  # nothing left to feed: a pure decode step
    ],
)
def test_chunk_attributes_against_a_hand_built_queue(hand_driven, turn_no, expected):
    got = hand_driven["turns"][turn_no]
    assert {k: got[k] for k in expected} == expected and got["chunk_width"] == 16


def test_stats_sum_the_chunks_of_a_hand_built_queue(hand_driven):
    turns, counts = hand_driven["turns"], hand_driven["engine"].stats()
    assert [a["admitted"] for a in hand_driven["admits"]] == [2, 1, 1, 1, 1]
    assert counts["chunk_steps"] == sum(t["chunk_tokens"] > 0 for t in turns) == 7
    assert counts["prefill_tokens"] == sum(t["chunk_tokens"] for t in turns) == 3 + 40 + 5 + 6 + 7
    assert counts["prefill_padded_tokens"] == 16 * 7 and counts["preemptions"] == 1


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
    assert counts["chunk_steps"] >= 6 and counts["prefill_tokens"] >= 3 * 3 + 19 + 2 * 3
    assert counts["prefill_padded_tokens"] == counts["chunk_steps"] * counts["chunk_width"] >= counts["prefill_tokens"]
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
    slots, bps = 4, cfg.max_seq // 16
    pools = gen.init_kv_pools(cfg, 9, 16, slots=slots)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    keys = jnp.zeros((slots, 2), jnp.uint32)
    temps = jnp.zeros((slots,), jnp.float32)
    # a mixer's rows ride beside the block table: each slot its own row of the store
    tables = {"full": i32(slots, bps), "state": 1 + jnp.arange(slots, dtype=jnp.int32)} if cfg.ssm_heads or cfg.gdn_heads else i32(slots, bps)
    if cfg.eva_window:  # the rows' staging blocks ride beside it
        tables = {"full": i32(slots, bps), "stage": i32(slots, cfg.eva_window // cfg.eva_chunk // 16)}
    decode = jax.jit(
        lambda p, t, pos, tab, pl, k, tm: gen.paged_decode_step(p, t, pos, tab, pl, cfg, k, tm)
    ).lower(params, i32(slots), i32(slots), tables, pools, keys, temps)
    prefill = jax.jit(
        lambda p, t, pre, suf, tab, pl, k, tm: gen.paged_prefill_chunk(p, t, pre, suf, tab, pl, cfg, k, tm)
    ).lower(params, i32(slots, 32), i32(slots), i32(slots) + 1, tables, pools, keys, temps)
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
    mixer = llama.CONFIGS["tiny"](ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2)
    out = {f"dense.{k}": _op_names(v) for k, v in _serving_programs(dense).items()}
    out.update({f"moe.{k}": _op_names(v) for k, v in _serving_programs(sparse).items()})
    out.update({f"mixer.{k}": _op_names(v) for k, v in _serving_programs(mixer).items()})
    out["mixer.train"] = _op_names(_train_step(mixer))
    windowed = llama.CONFIGS["tiny"](n_kv_heads=4, eva_window=256, eva_chunk=16, max_seq=512)
    out.update({f"eva.{k}": _op_names(v) for k, v in _serving_programs(windowed).items()})
    out["dense.train"] = _op_names(_train_step(dense))
    out["moe.train"] = _op_names(_train_step(sparse))
    linear = moe.moe_tiny(n_layers=4, layer_types=("linear", "linear", "linear", "full"), gdn_heads=4, gdn_key_heads=2, gdn_head_dim=8,
                          attn_output_gate=True, n_shared_experts=1, shared_expert_gate=True, capacity_factor=0.0)  # fmt: skip
    out.update({f"linear.{k}": _op_names(v) for k, v in _serving_programs(linear).items()})
    return out


SERVING = (hot.EMBED, hot.LAYERS, hot.NORM, hot.ATTN, hot.APPEND_KV, hot.PAGED_ATTENTION, hot.GATHER_KV,
           hot.SCORES, hot.VALUES, hot.LM_HEAD, hot.SAMPLE)  # fmt: skip
TRAINING = (hot.EMBED, hot.LAYERS, hot.NORM, hot.ATTN, hot.ATTN_KERNEL, hot.LM_HEAD, hot.LOSS, hot.GRAD_CLIP,
            hot.OPTIMIZER)  # fmt: skip
EXPERTS = (hot.MOE_ROUTER, hot.MOE_DISPATCH, hot.MOE_EXPERTS, hot.MOE_COMBINE)
LINEAR = (hot.GDN, hot.GDN_PROJ, hot.GDN_CONV, hot.GDN_GATE_NORM)  # a linear layer's mixer, and the form each program takes
MIXER = (hot.SSM, hot.SSM_PROJ, hot.SSM_CONV, hot.SSM_GATE_NORM)  # and the form each program takes:
CASES = (
    [(f"dense.{p}", s) for p in ("decode", "prefill") for s in SERVING + (hot.MLP,)]
    + [(f"moe.{p}", s) for p in ("decode", "prefill") for s in EXPERTS]
    + [("dense.train", s) for s in TRAINING + (hot.MLP,)]
    + [("moe.train", s) for s in EXPERTS]
    + [(f"mixer.{p}", s) for p, form in (("decode", hot.SSM_STEP), ("prefill", hot.SSM_SCAN), ("train", hot.SSM_SCAN))
       for s in MIXER + (hot.ATTN, form)]
    + [(f"eva.{p}", s) for p in ("decode", "prefill") for s in (hot.ATTN, hot.APPEND_KV, hot.PAGED_ATTENTION, hot.EVA_POOL)]
    + [(f"linear.{p}", s) for p, form in (("decode", hot.GDN_STEP), ("prefill", hot.GDN_CHUNK))
       for s in LINEAR + (hot.ATTN, hot.ATTN_FULL, hot.ATTN_GATE, hot.MOE_SHARED, hot.MOE_EXPERTS, form)]
)  # fmt: skip


@pytest.mark.parametrize("program,scope", CASES)
def test_scope_names_reach_the_lowered_program(lowered, program, scope):
    assert scope in lowered[program]


def test_a_program_takes_one_form_of_the_mixer_and_a_model_without_one_neither(lowered):
    assert hot.SSM_SCAN not in lowered["mixer.decode"] and hot.SSM_STEP not in lowered["mixer.prefill"]
    assert not {hot.SSM, hot.SSM_STEP, hot.SSM_SCAN} & (lowered["dense.decode"] | lowered["dense.prefill"] | lowered["dense.train"])
    assert set(MIXER + (hot.SSM_STEP, hot.SSM_SCAN)) <= set(hot.DEVICE_SCOPES)


def test_a_program_takes_one_form_of_the_linear_mixer_and_a_model_without_one_neither(lowered):
    assert hot.GDN_CHUNK not in lowered["linear.decode"] and hot.GDN_STEP not in lowered["linear.prefill"]
    others = lowered["dense.decode"] | lowered["dense.prefill"] | lowered["dense.train"] | lowered["mixer.decode"] | lowered["moe.decode"]
    assert not {hot.GDN, hot.GDN_STEP, hot.GDN_CHUNK, hot.ATTN_GATE} & others
    assert set(LINEAR + (hot.GDN_STEP, hot.GDN_CHUNK, hot.ATTN_GATE)) <= set(hot.DEVICE_SCOPES)


def test_only_eva_attention_pools(lowered):
    assert hot.EVA_POOL in hot.DEVICE_SCOPES
    assert not any(hot.EVA_POOL in names for program, names in lowered.items() if not program.startswith("eva."))


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
