"""The readers of a prompt's chunks (PR 40: ``engine.chunk_steps_pct`` and
``engine.chunk_fill_pct`` from the ``serve.decode`` spans; ``kernels.decode_chunk_hbm_pct``
and ``kernels.decode_chunk_cost_ms`` from the mixed program's runs on the device): nothing
where there is nothing to read (no trace, a training trace, the recorded traces of engines
whose admission was a round with a program of its own), numbers on a made-up plane."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import host_spans, scopes, spec, trace

from .conftest import DATA

hot = scopes.names()
SPAN_READERS = ("engine.chunk_steps_pct", "engine.chunk_fill_pct")
DEVICE_READERS = ("kernels.decode_chunk_hbm_pct", "kernels.decode_chunk_cost_ms")
READERS = SPAN_READERS + DEVICE_READERS
BETTER_LOWER = {"engine.chunk_steps_pct", "kernels.decode_chunk_cost_ms"}
RECORDED = {name: os.path.join(DATA, name) for name in ("serve_runs.xplane.pb", "serve_spans.xplane.pb")}
TRAIN_TRACE = os.path.join(DATA, "train_8steps.xplane.pb")
BACKLOG = ("mixtral8x7b-serve-backlog", "kimi-vl-a3b-serve-backlog", "k-exaone-serve-decode-long", "xing4-serve-decode-long")


class _Cell:
    def __init__(self, kind):
        self.kind = kind


def _traced(path, kind="serve"):
    return {"cell": _Cell(kind), "trace": trace.reduce_planes(trace.read_planes(path), chips=1), "counters": {}}


@pytest.mark.parametrize("name", READERS)
def test_the_manifest_lists_the_reader_for_the_backlog_cells(name):
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE)
    assert tuple(entry["workloads"]) == BACKLOG and reader.NAME == name
    assert entry["better"] == ("lower" if name in BETTER_LOWER else "higher")
    assert manifest["per_layer"].index(entry) >= len(manifest["per_layer"]) - len(READERS)  # appended, nothing moved


@pytest.mark.parametrize("name", READERS)
def test_no_trace_and_a_training_trace_read_none(name, monkeypatch):
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    for kind in ("serve", "train"):
        assert reader.read({"cell": _Cell(kind), "trace": None, "counters": {}}) is None
    monkeypatch.setattr(scopes, "trace_file", lambda run: TRAIN_TRACE)
    assert reader.read(_traced(TRAIN_TRACE, "train")) is None
    assert reader.read(_traced(TRAIN_TRACE)) is None  # a device plane and no engine span
    monkeypatch.setattr(scopes, "names", lambda: None)  # a program that has no spans at all
    assert reader.read(_traced(TRAIN_TRACE)) is None


@pytest.mark.parametrize("recorded", sorted(RECORDED))
@pytest.mark.parametrize("name", READERS)
def test_a_trace_from_before_the_chunks_reads_none(name, recorded, monkeypatch):
    """The parent's side of the driver's comparison: ``serve.decode`` spans without the attributes."""
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    monkeypatch.setattr(scopes, "trace_file", lambda run: RECORDED[recorded])
    assert host_spans.read(RECORDED[recorded]).named(hot.SERVE_DECODE)  # the turns are there
    assert reader.read(_traced(RECORDED[recorded])) is None


def _made_up(chunks):
    """Decode turns with the given ``chunk_tokens`` (None: a span without the attribute), 10 ms apart."""
    events = []
    for i, n in enumerate(chunks):
        attrs = {"step": i} if n is None else {"step": i, "chunk_tokens": n, "chunk_width": 256}
        events += [(hot.SERVE_DECODE, 0.01 * i, 0.01 * i + 0.009, attrs), (hot.SERVE_DECODE_FETCH, 0.01 * i + 0.002, 0.01 * i + 0.008, {})]
    events.append((hot.SERVE_ADMIT, 0.01 * len(chunks), 0.01 * len(chunks) + 0.001, {"admitted": 1, "cached_tokens": 1024}))
    return host_spans.Reading(host_spans.build_tree(events))


@pytest.mark.parametrize("chunks,steps_pct,fill_pct", [
    ([256, 256, 0, 128, 0], 60.0, 100.0 * 640 / 768),
    ([0, 0, 0], 0.0, None),  # turns that say so, and no chunk among them: a share of nought, nothing to fill
    ([1], 100.0, 100.0 / 256),
    ([None, None], None, None),  # an older program's turns
    ([None, 256, 0], 50.0, 100.0),  # only the turns that carry the attribute count
])  # fmt: skip
def test_a_made_up_plane_reads_numbers(chunks, steps_pct, fill_pct, monkeypatch):
    monkeypatch.setattr(host_spans, "of_run", lambda run, kind="serve": _made_up(chunks))
    got = [bench_run.load_reader(name, spec.BENCH_DIR).read({"cell": _Cell("serve"), "trace": {}, "counters": {}}) for name in SPAN_READERS]
    assert got == [pytest.approx(steps_pct), pytest.approx(fill_pct)]


def _device_run(medians, cell="kimi-vl-a3b-serve-backlog", active=64.0, held=130_000.0, peak=819e9):
    counters = {"traced_active_mean": active, "traced_tokens_held_mean": held}
    if peak:
        counters["peak_hbm_bytes_per_s"] = peak
    return {"cell": spec.load_cell(cell), "trace": {"module_median_s": medians}, "counters": counters}


@pytest.mark.parametrize("cell", BACKLOG)
def test_the_mixed_program_reads_under_the_decode_step_by_the_programs_ratio(cell):
    """The same bytes over the longer program: the two shares stand as the two medians do,
    and the cost is the medians' difference."""
    run = _device_run({"jit__decode": 0.0175, "jit__decode_chunk": 0.0225}, cell)
    pure = bench_run.load_reader("kernels.decode_hbm_pct", spec.BENCH_DIR).read(run)
    mixed, cost = (bench_run.load_reader(name, spec.BENCH_DIR).read(run) for name in DEVICE_READERS)
    assert 0 < mixed < pure < 105 and mixed == pytest.approx(pure * 17.5 / 22.5)
    assert cost == pytest.approx(5.0)


@pytest.mark.parametrize("medians,peak,expected", [
    ({"jit__decode": 0.0175, "jit__prefill": 0.033}, 819e9, (None, None)),  # the parent's side: rounds, no mixed step
    ({"jit__decode_chunk": 0.0225}, 819e9, (pytest.approx(62.0, abs=15), None)),  # every traced step carried a chunk
    ({"jit__decode": 0.0175, "jit__decode_chunk": 0.0225}, None, (None, pytest.approx(5.0))),  # a device with no published peak
    ({}, 819e9, (None, None)),
])  # fmt: skip
def test_a_device_reader_reads_only_what_the_trace_holds(medians, peak, expected):
    run = _device_run(medians, peak=peak)
    assert tuple(bench_run.load_reader(name, spec.BENCH_DIR).read(run) for name in DEVICE_READERS) == expected
