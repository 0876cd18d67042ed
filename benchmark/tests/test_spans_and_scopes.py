"""The span and scope reductions: on made-up events, on a serving trace
recorded on the chip (``serve_spans.xplane.pb``: the backlog cell's engine,
Python tracer off, PR 24) and on the training trace of PR 23, whose paths
carry ``rematted_computation`` and ``transpose(jvp())`` but no scope of ours."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import host_spans, program_runs, scopes, spec, trace

from .conftest import DATA

hot = scopes.names()
SERVE_TRACE = os.path.join(DATA, "serve_spans.xplane.pb")
TRAIN_TRACE = os.path.join(DATA, "train_8steps.xplane.pb")
NEW_READERS = sorted(
    m["name"] for m in json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))["per_layer"]
    if m["name"].split(".latency")[0] in {
        "engine.host_ms_per_step", "engine.admit_host_ms", "engine.traced_step_ms",
        "device.idle_decode_host_pct", "device.idle_admit_host_pct", "device.idle_other_pct",
        "kernels.decode_attention_pct", "kernels.decode_experts_pct", "kernels.train_attention_pct",
        "kernels.train_optimizer_pct"}
)


def test_tree_and_self_times():
    ev = [("serve.decode", 0.0, 10.0, {"step": 1}), ("serve.decode.prepare", 0.0, 1.0, {}),
          ("serve.decode.dispatch", 1.0, 3.0, {}), ("serve.decode.fetch", 3.0, 8.0, {}),
          ("serve.decode.commit", 8.0, 9.5, {}), ("serve.idle", 10.0, 12.0, {}),
          ("serve.decode", 12.0, 20.0, {"step": 2})]
    roots = host_spans.build_tree(ev)
    assert [r.name for r in roots] == ["serve.decode", "serve.idle", "serve.decode"]
    first = roots[0]
    assert [c.name for c in first.children] == [e[0] for e in ev[1:5]]
    assert first.self_time == pytest.approx(0.5) and first.child_time("serve.decode.fetch") == 5.0
    assert roots[2].children == [] and roots[2].self_time == 8.0
    assert program_runs.host_classes(roots, hot) == {"decode_host": [(0.0, 3.0), (8.0, 10.0), (12.0, 20.0)], "admit_host": []}


def test_overlap_of_interval_lists():
    idle = [(0.0, 2.0), (5.0, 6.0), (9.0, 12.0)]
    assert host_spans.overlap(idle, [(1.0, 5.5), (10.0, 11.0), (11.5, 30.0)]) == pytest.approx(1 + 0.5 + 1 + 0.5)
    assert host_spans.overlap(idle, []) == 0.0 and host_spans.overlap([], idle) == 0.0


@pytest.mark.parametrize("path,parts", [
    ("jit(step)/transpose(jvp(attn))/attn_kernel/dot_general", ["step", "attn", "attn_kernel", "dot_general"]),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/mul",
     ["step", "while", "body", "closed_call", "checkpoint", "rematted_computation", "mlp", "mul"]),
    ("jit(_decode)/while/body/attn/paged_attention/scores/shd,sthd->sht/dot_general",
     ["_decode", "while", "body", "attn", "paged_attention", "scores", "shd,sthd->sht", "dot_general"]),
    ("", []),
])
def test_path_components(path, parts):
    assert scopes.components(path) == parts


def test_scopes_on_made_up_planes():
    plane = {"name": "/device:TPU:0",
             "modules": [("jit_a(1)", 0.0, 4.0, ""), ("jit_b(2)", 5.0, 6.0, "")],
             "ops": [("%while.1 = s32[] while()", 0.0, 4.0, "jit(a)/while"),
                     ("%fusion.1 = f32[] fusion()", 0.0, 1.0, "jit(a)/while/body/attn/attn_kernel/dot_general"),
                     ("%fusion.2 = f32[] fusion()", 1.0, 3.0, "jit(a)/transpose(jvp(mlp))/mul"),
                     ("%copy.3 = f32[] copy()", 3.0, 4.0, ""),
                     ("%fusion.9 = f32[] fusion()", 5.0, 6.0, "jit(b)/attn/add")]}
    ops = scopes.program_ops([plane], "jit_a")
    assert sum(d for d, _, _ in ops) == pytest.approx(4.0)  # the container is left out
    assert scopes.under(ops, ["attn"]) == pytest.approx(1.0)
    assert scopes.under(ops, ["attn", "attn_kernel", "mlp"]) == pytest.approx(3.0)  # counted once
    b = scopes.breakdown(ops, ["attn", "attn_kernel", "mlp"])
    assert b["by_scope"] == {"mlp": 2.0, "attn": 1.0, "attn_kernel": 1.0} and b["unscoped"] == {"copy": 1.0}
    assert scopes.program_ops([plane], "jit_c") is None


def test_training_trace_paths_and_device_time_agree_with_the_harness():
    """The wire reader sees what ``ProfileData`` sees, and the paths besides."""
    planes = scopes.read_planes(TRAIN_TRACE)
    ref = trace.reduce_planes(trace.read_planes(TRAIN_TRACE), chips=1)
    (p,) = planes
    assert len(p["modules"]) == 8 and len(p["ops"]) == 18280
    busy, _ = trace._union((s, e) for _, s, e, _ in p["ops"])
    assert busy == pytest.approx(ref["busy_s"], rel=1e-6)
    ops = scopes.program_ops(planes, "jit_step")
    total = sum(d for d, _, _ in ops)
    assert total == pytest.approx(ref["busy_s"], rel=1e-3)  # one stream: operations do not overlap
    assert total > sum(t for _, t in ref["device_ops"])  # the harness's top ten are part of it
    remat = scopes.under(ops, [scopes.REMAT])
    assert 0.1 < remat / total < 0.35  # full recomputation: about a quarter of the step
    assert scopes.under(ops, ["checkpoint"]) > remat  # transpose(jvp()) wrappers are taken off
    assert scopes.under(ops, hot.DEVICE_SCOPES) == 0.0  # recorded before the scopes existed


@pytest.fixture(scope="module")
def serving():
    r = host_spans.read(SERVE_TRACE)
    assert r is not None
    return r


def test_serving_trace_span_tree(serving):
    decode, admit = serving.named(hot.SERVE_DECODE), serving.named(hot.SERVE_ADMIT)
    assert len(decode) >= 3 and len(admit) >= 1
    full = [d for d in decode if len(d.children) == 4]
    assert full and all([c.name for c in d.children] == list(hot.SERVE_SPAN_TREE[hot.SERVE_DECODE]) for d in full)
    rounds = [a for a in admit if a.child_time(hot.SERVE_PREFILL_DISPATCH) > 0]
    assert rounds and [c.name for c in rounds[0].children] == list(hot.SERVE_SPAN_TREE[hot.SERVE_ADMIT])
    assert all(s.self_time >= -1e-9 for s in decode + admit)
    for parent in hot.SERVE_SPAN_TREE:
        assert host_spans.coverage(serving, parent) > 0.95
    assert {"step", "active"} <= set(full[0].attrs) and {"rows", "width"} <= set(rounds[0].attrs)
    assert 0 < host_spans.host_ms_per_step(serving) < host_spans.traced_step_ms(serving)
    assert host_spans.admit_host_ms(serving) > 0


@pytest.mark.parametrize("module", ["jit__decode", "jit__prefill"])
def test_serving_trace_scopes(module):
    ops = scopes.program_ops(scopes.read_planes(SERVE_TRACE), module)
    b = scopes.breakdown(ops, hot.DEVICE_SCOPES)
    assert b["scoped"] / b["total"] > 0.8
    inner = sum(b["by_scope"][s] for s in (hot.GATHER_KV, hot.SCORES, hot.VALUES))
    assert 0 < inner <= b["by_scope"][hot.PAGED_ATTENTION] * (1 + 1e-9) <= b["by_scope"][hot.ATTN] * (1 + 1e-9)
    assert b["by_scope"][hot.MOE_EXPERTS] > 0


class _Cell:
    def __init__(self, kind, name="no-such-cell"):
        self.kind, self.name, self.chips = kind, name, 1


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_where_there_is_nothing(name, tmp_path, monkeypatch):
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    # a run that was not traced, of either kind
    for kind in ("serve", "train"):
        assert reader.read({"cell": _Cell(kind), "trace": None, "counters": {}}) is None
    # a traced run of the other kind, with the recorded trace of that kind in place
    other = "train" if name.startswith(("engine.", "device.", "kernels.decode")) else "serve"
    monkeypatch.setattr(scopes, "trace_file", lambda run: TRAIN_TRACE if other == "train" else SERVE_TRACE)
    assert reader.read({"cell": _Cell(other), "trace": {"busy_s": 1.0, "window_s": 2.0}, "counters": {}}) is None
    # a program that has no spans or scopes at all (the parent commit)
    monkeypatch.setattr(scopes, "names", lambda: None)
    own = "serve" if other == "train" else "train"
    assert reader.read({"cell": _Cell(own), "trace": {"busy_s": 1.0, "window_s": 2.0}, "counters": {}}) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_on_the_recorded_traces(name, monkeypatch):
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    train = name.startswith("kernels.train")
    monkeypatch.setattr(scopes, "trace_file", lambda run: TRAIN_TRACE if train else SERVE_TRACE)
    value = reader.read({"cell": _Cell("train" if train else "serve"), "trace": {"busy_s": 1.0, "window_s": 2.0},
                         "counters": {}})
    if train:
        assert value is None  # PR 23's training trace was recorded before the scopes existed
    else:
        assert value is not None and 0 <= value < (100.0 if reader.UNIT == "%" else 1e4)
