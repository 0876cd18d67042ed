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
from torchx_tpu.obs import trace as obs_trace
from torchx_tpu.serve.engine import ServeEngine, ServeRequest

ENGINE_SPANS = [
    hot.SERVE_ADMIT,
    *hot.SERVE_SPAN_TREE[hot.SERVE_ADMIT],
    hot.SERVE_DECODE,
    *hot.SERVE_SPAN_TREE[hot.SERVE_DECODE],
    hot.SERVE_IDLE,
    hot.SERVE_KV_IMPORT,
]


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


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.CONFIGS["tiny"]()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine_line(tiny, tmp_path_factory):
    """The engine thread's line of a traced run: every ``serve.*`` event."""
    cfg, params = tiny
    engine = ServeEngine(params, cfg, max_slots=4, block_size=16, max_prefill_batch=2).start()
    try:
        _drive(engine)  # compile outside the session
        lines = _session(tmp_path_factory.mktemp("trace"), lambda: _drive(engine))
    finally:
        engine.stop()
    with_spans = [ln for ln in lines if any(name == hot.SERVE_DECODE for name, *_ in ln)]
    assert len(with_spans) == 1, "the engine's spans lie on one thread's line"
    return [ev for ev in with_spans[0] if ev[0].startswith("serve.")]


@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_engine_span_is_recorded(engine_line, name):
    assert any(ev[0] == name for ev in engine_line)


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
        (hot.SERVE_ADMIT, {"rows", "width", "cached_tokens", "queue_depth"}),
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
    finally:
        engine.stop()
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
