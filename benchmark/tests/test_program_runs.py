"""Program runs tied to engine turns by ``run_id`` (``lib/program_runs.py``): on made-up
planes, and on a trace recorded on the chip with the engine that keeps a step in flight
(``serve_runs.xplane.pb``: the Mixtral backlog cell's engine, Python tracer off, PR 38, by
``scripts/serve_trace_tax.py``)."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import host_spans, program_runs, scopes, spec, trace

from .conftest import DATA

hot = scopes.names()
RUNS_TRACE = os.path.join(DATA, "serve_runs.xplane.pb")
OLDER_TRACE = os.path.join(DATA, "serve_spans.xplane.pb")  # PR 24's engine: no round counted, no step in flight
TRAIN_TRACE = os.path.join(DATA, "train_8steps.xplane.pb")
NEW_READERS = sorted(
    m["name"] for m in json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))["per_layer"]
    if m["name"].split(".latency")[0] in {
        "engine.prefill_device_pct", "engine.prefill_rows_per_round", "engine.prefill_padding_pct",
        "engine.prefill_queued_ms", "device.idle_after_prefill_pct", "engine.fetch_return_ms", "device.clock_slack_us",
        "engine.prefill_slots_stalled"}
)
MS = 1e-3


# -- made-up planes: times in ms, the device's clock 0.1 ms ahead of the host's ---------------------


def _made_up():
    """Two decode turns, a round behind the second step, the turn after it.
    -> (modules, lines, enqueues, completes) as ``program_runs._events`` gives them."""
    call = lambda fn, s, e: [(f"PjitFunction({fn})", s, e, {}), (f"PjitFunction({fn})", s + 0.01, e - 0.01, {})]  # noqa: E731
    engine = [
        (hot.SERVE_DECODE, 0.0, 2.0, {"step": 1}),
        (hot.SERVE_DECODE_PREPARE, 0.0, 0.5, {}),
        (hot.SERVE_DECODE_DISPATCH, 0.5, 2.0, {}), *call("_decode", 1.5, 1.9),
        (hot.SERVE_DECODE, 2.0, 13.0, {"step": 1}),
        (hot.SERVE_DECODE_PREPARE, 2.0, 2.4, {}),
        (hot.SERVE_DECODE_DISPATCH, 2.4, 3.6, {}), *call("_decode", 3.2, 3.5),
        (hot.SERVE_DECODE_FETCH, 3.6, 12.9, {}),
        (hot.SERVE_DECODE_COMMIT, 12.9, 13.0, {"finished": 1}),
        (hot.SERVE_ADMIT, 13.0, 31.0, {"rows": 1, "rows_padded": 1, "width": 256, "tokens": 200, "slots_stalled": 3}),
        (hot.SERVE_ADMIT_PLAN, 13.0, 13.5, {}),
        (hot.SERVE_ADMIT_BUILD, 13.5, 13.6, {}),
        (hot.SERVE_PREFILL_DISPATCH, 13.6, 14.8, {}), *call("_prefill", 14.4, 14.7),
        (hot.SERVE_PREFILL_FETCH, 14.8, 30.9, {}),
        (hot.SERVE_ADMIT_COMMIT, 30.9, 31.0, {}),
        (hot.SERVE_DECODE, 31.0, 32.8, {"step": 2}),
        (hot.SERVE_DECODE_PREPARE, 31.0, 31.4, {}),
        (hot.SERVE_DECODE_DISPATCH, 31.4, 32.6, {}), *call("_decode", 32.2, 32.5),
        (hot.SERVE_DECODE_FETCH, 32.6, 32.7, {}),
        (hot.SERVE_DECODE_COMMIT, 32.7, 32.8, {"finished": 0}),
    ]
    modules = [(100, "jit__decode", 2.2, 12.2), (101, "jit__decode", 12.21, 22.21), (102, "jit__prefill", 22.22, 30.22),
               (103, "jit__decode", 32.8, 42.8)]
    enqueues = [(100, 2.05), (101, 3.6), (102, 14.8), (103, 32.6)]
    completes = [(100, 12.5), (101, 22.5), (102, 30.5), (103, 43.1)]
    scale = lambda evs: [tuple(x * MS if isinstance(x, float) else x for x in ev) for ev in evs]  # noqa: E731
    return scale(modules), [scale(engine)], scale(enqueues), scale(completes)


def test_runs_are_linked_to_the_span_that_enqueued_them():
    r = program_runs.build(*_made_up())
    assert r.problem is None and not r.edges and not r.unlinked
    assert [(x.run_id, x.span.name, x.span.start / MS) for x in r.runs] == [
        (100, hot.SERVE_DECODE_DISPATCH, pytest.approx(0.5)), (101, hot.SERVE_DECODE_DISPATCH, pytest.approx(2.4)),
        (102, hot.SERVE_PREFILL_DISPATCH, pytest.approx(13.6)), (103, hot.SERVE_DECODE_DISPATCH, pytest.approx(31.4))]
    assert [x.call for x in r.runs] == pytest.approx([1.5 * MS, 3.2 * MS, 14.4 * MS, 32.2 * MS])


def test_offset_is_bounded_on_both_sides_by_the_trace_itself():
    r = program_runs.build(*_made_up())
    assert r.lo == pytest.approx(-0.28 * MS) and r.hi == pytest.approx(0.15 * MS)  # the truth, 0.1 ms, lies inside
    assert r.offset == pytest.approx(-0.065 * MS) and program_runs.clock_slack_us(r) == pytest.approx(430.0)


def test_a_round_waits_behind_the_step_in_flight_and_leaves_an_exposed_turn():
    r = program_runs.build(*_made_up())
    (rnd,) = r.rounds
    assert rnd.run.run_id == 102 and rnd.queued == pytest.approx((22.22 - 14.8 + 0.065) * MS)
    assert rnd.exposed == pytest.approx(2.58 * MS)  # 30.22 -> 32.8: the device's clock alone
    want = {"result_on_its_way": 0.615, "commit": 0.1, "prepare": 0.4, "dispatch": 1.2, "rest": 0.265}
    assert {k: v / MS for k, v in rnd.parts.items()} == pytest.approx(want)
    assert sum(rnd.parts.values()) == pytest.approx(rnd.exposed)
    assert program_runs.prefill_queued_ms(r) == pytest.approx(7.485)
    assert program_runs.idle_after_prefill_s(r) == pytest.approx(2.58 * MS)
    assert program_runs.exposed_parts_ms(r) == pytest.approx(want)


def test_a_fetch_divides_into_device_still_busy_and_result_on_its_way():
    r = program_runs.build(*_made_up())
    got = [(f.span.name, f.run.run_id, f.busy / MS, f.back / MS) for f in r.fetches]
    assert [g[:2] for g in got] == [(hot.SERVE_DECODE_FETCH, 100), (hot.SERVE_PREFILL_FETCH, 102), (hot.SERVE_DECODE_FETCH, 101)]
    assert [g[2:] for g in got] == [pytest.approx(x) for x in ((8.665, 0.635), (15.485, 0.615), (0.0, 0.1))]
    assert all(f.busy + f.back == pytest.approx(f.span.duration) for f in r.fetches)
    assert program_runs.fetch_return_ms(r, hot.SERVE_DECODE_FETCH) == pytest.approx((0.635 + 0.1) / 2)


def test_an_empty_offset_interval_is_reported_and_nothing_crosses_the_clocks():
    modules, lines, enqueues, completes = _made_up()
    completes[1] = (101, 21.0 * MS)  # the host hears of run 101's end 1.2 ms before it ends
    r = program_runs.build(modules, lines, enqueues, completes)
    assert r.lo > r.hi and "empty" in r.problem and not r.rounds and not r.fetches
    assert program_runs.clock_slack_us(r) is None and program_runs.prefill_queued_ms(r) is None
    assert program_runs.idle_after_prefill_s(r) is None and program_runs.fetch_return_ms(r, hot.SERVE_DECODE_FETCH) is None
    assert program_runs.exposed_parts_ms(r) is None


def test_a_call_is_given_to_the_innermost_span_that_holds_it():
    """A copy-on-write inside ``.prepare`` (two eager updates under one ``serve.cow_copy``) and a gather in
    ``serve.admit.commit`` (a hand-off exported: no span of its own) are named by the span they lie in."""
    modules, lines, enqueues, completes = _made_up()
    cow = getattr(hot, "SERVE_COW_COPY", "serve.cow_copy")
    lines[0] += [(cow, 31.05 * MS, 31.35 * MS, {}), ("PjitFunction(scatter)", 31.1 * MS, 31.15 * MS, {}),
                 ("PjitFunction(scatter)", 31.2 * MS, 31.25 * MS, {}), ("PjitFunction(gather)", 30.92 * MS, 30.95 * MS, {})]
    lines[0].sort(key=lambda ev: (ev[1], -ev[2]))
    for run_id, module, enq in ((200, "jit_gather", 30.96), (201, "jit_scatter", 31.16), (202, "jit_scatter", 31.26)):
        modules.append((run_id, module, (enq + 0.12) * MS, (enq + 0.13) * MS))
        enqueues.append((run_id, enq * MS))
        completes.append((run_id, (enq + 0.45) * MS))
    r = program_runs.build(modules, lines, enqueues, completes)
    assert r.problem is None and not r.unlinked
    assert [(x.run_id, x.span.name) for x in r.runs if x.run_id >= 200] == [
        (200, hot.SERVE_ADMIT_COMMIT), (201, cow), (202, cow)]
    assert [x.run.run_id for x in r.rounds] == [102] and len(r.fetches) == 3  # the rounds and the fetches as before


def test_a_program_that_no_span_accounts_for_is_counted_by_module():
    modules, lines, enqueues, completes = _made_up()
    modules.append((104, "jit_other", 44.2 * MS, 44.3 * MS))
    enqueues.append((104, 44.0 * MS))
    completes.append((104, 44.5 * MS))
    lines.append([("PjitFunction(other)", 43.8 * MS, 43.9 * MS, {})])  # another thread's call
    modules.append((105, "jit_unseen", 45.2 * MS, 45.3 * MS))  # ... and one with no call in the trace
    enqueues.append((105, 45.0 * MS))
    completes.append((105, 45.5 * MS))
    r = program_runs.build(modules, lines, enqueues, completes)
    assert r.problem is None and r.unlinked == {"jit_other": 1, "jit_unseen": 1} and len(r.linked()) == 4
    assert r.runs[-2].call == pytest.approx(43.8 * MS) and r.runs[-1].call is None


def test_runs_at_the_edges_that_lack_a_side_are_kept_apart():
    modules, lines, enqueues, completes = _made_up()
    modules.insert(0, (99, "jit__decode", -9.0 * MS, 2.19 * MS))  # enqueued before the trace began
    completes.insert(0, (99, 2.4 * MS))
    del modules[-1], completes[-1]  # run 103 had not started when the trace ended
    r = program_runs.build(modules, lines, enqueues, completes)
    assert [x.run_id for x in r.edges] == [99, 103] and [x.run_id for x in r.runs] == [100, 101, 102]
    (rnd,) = r.rounds
    assert rnd.exposed is None and program_runs.idle_after_prefill_s(r) is None  # nothing follows the round's run


def test_a_run_that_lacks_a_side_inside_the_trace_still_takes_its_call():
    """A completion the profiler dropped (seen on the chip, PR 39: one ``CompleteCallbacks`` missing behind a
    0.4 s stall of the host) makes its run an edge; it must not leave its call to the run after it."""
    modules, lines, enqueues, completes = _made_up()
    completes = [c for c in completes if c[0] != 101]
    r = program_runs.build(modules, lines, enqueues, completes)
    assert [x.run_id for x in r.edges] == [101] and r.edges[0].span.start == pytest.approx(2.4 * MS) and not r.unlinked
    assert [(x.run_id, x.span.start / MS) for x in r.runs] == [(100, pytest.approx(0.5)), (102, pytest.approx(13.6)),
                                                                 (103, pytest.approx(31.4))]
    idle = [(12.2 * MS, 12.21 * MS), (22.21 * MS, 22.22 * MS), (30.22 * MS, 32.8 * MS)]
    want = {"decode_host": 1.6 + 0.01 * 0.1 / 1.9, "admit_host": 0.1 + 0.01 * 1.8 / 1.9}
    assert _ms(program_runs.idle_by_class(r, idle, _host(r))) == pytest.approx(want)  # as with every side there


def test_a_run_called_before_the_trace_began_is_an_edge_not_an_unaccounted_program():
    modules, lines, enqueues, completes = _made_up()
    lines[0] = [ev for ev in lines[0] if ev[1] >= 2.0 * MS]  # the trace begins between run 100's call and its enqueue
    r = program_runs.build(modules, lines, enqueues, completes)
    assert r.problem is None and not r.unlinked and [x.run_id for x in r.edges] == [100]
    assert [x.run_id for x in r.linked()] == [101, 102, 103] and r.hi == pytest.approx(0.15 * MS)  # it still bounds the offset


# -- the idle split by run_id (PR 39; until then by the span that held a program's midpoint) ---------


def _host(r):
    return program_runs.host_classes(r.spans, hot)


def _ms(by_class):
    return {k: v / MS for k, v in by_class.items()}


def test_a_gap_goes_to_the_turn_that_enqueued_b_by_what_the_thread_did_since_its_last_result():
    r = program_runs.build(*_made_up())
    idle = [(12.2 * MS, 12.21 * MS), (22.21 * MS, 22.22 * MS), (30.22 * MS, 32.8 * MS)]
    # behind the round (2.58): from the round's fetch, serve.admit.commit 30.9-31.0, then the next turn's prepare and
    # dispatch 31.0-32.6; ahead of the round (0.01): enqueued with a step in flight, since the fetch that ended at 12.9
    # the thread did .commit 12.9-13.0 and the round's plan, build and dispatch 13.0-14.8; the first gap has no
    # fetch ahead of its enqueue in the trace: no class's
    want = {"decode_host": 1.6 + 0.01 * 0.1 / 1.9, "admit_host": 0.1 + 0.01 * 1.8 / 1.9}
    assert _ms(program_runs.idle_by_class(r, idle, _host(r))) == pytest.approx(want)


def test_a_gap_shorter_than_the_host_work_it_holds_is_shared_in_proportion():
    r = program_runs.build(*_made_up())
    # of the 2.58 ms between the round's program and the next the device idles 0.85 (bubbles aside, as where
    # the device starts before the dispatch call returns): less than the 1.7 ms of host work between them
    got = _ms(program_runs.idle_by_class(r, [(30.22 * MS, 31.07 * MS)], _host(r)))
    assert got == pytest.approx({"decode_host": 0.85 * 1.6 / 1.7, "admit_host": 0.85 * 0.1 / 1.7})


def _made_up_stall():
    """Four decode turns with a step always in flight (a program of 18 ms); the third turn's dispatch takes
    51.2 ms where the others take 1.2, so the chip ends run 101 at 38.21 and waits until 72.4 for run 102."""
    call = lambda fn, s, e: [(f"PjitFunction({fn})", s, e, {})]  # noqa: E731
    engine = [
        (hot.SERVE_DECODE, 0.0, 2.0, {"step": 1}),
        (hot.SERVE_DECODE_PREPARE, 0.0, 0.5, {}),
        (hot.SERVE_DECODE_DISPATCH, 0.5, 2.0, {}), *call("_decode", 1.5, 1.9),
        (hot.SERVE_DECODE, 2.0, 20.6, {"step": 1}),
        (hot.SERVE_DECODE_PREPARE, 2.0, 2.4, {}),
        (hot.SERVE_DECODE_DISPATCH, 2.4, 3.6, {}), *call("_decode", 3.2, 3.5),
        (hot.SERVE_DECODE_FETCH, 3.6, 20.5, {}),
        (hot.SERVE_DECODE_COMMIT, 20.5, 20.6, {"finished": 0}),
        (hot.SERVE_DECODE, 20.6, 72.4, {"step": 2}),
        (hot.SERVE_DECODE_PREPARE, 20.6, 21.0, {}),
        (hot.SERVE_DECODE_DISPATCH, 21.0, 72.2, {}), *call("_decode", 71.8, 72.1),  # the thread 50 ms late
        (hot.SERVE_DECODE_FETCH, 72.2, 72.3, {}),  # run 101's result had long been there
        (hot.SERVE_DECODE_COMMIT, 72.3, 72.4, {"finished": 0}),
        (hot.SERVE_DECODE, 72.4, 90.8, {"step": 3}),
        (hot.SERVE_DECODE_PREPARE, 72.4, 72.8, {}),
        (hot.SERVE_DECODE_DISPATCH, 72.8, 74.0, {}), *call("_decode", 73.6, 73.9),
        (hot.SERVE_DECODE_FETCH, 74.0, 90.7, {}),
        (hot.SERVE_DECODE_COMMIT, 90.7, 90.8, {"finished": 0}),
    ]
    modules = [(100, "jit__decode", 2.2, 20.2), (101, "jit__decode", 20.21, 38.21), (102, "jit__decode", 72.4, 90.4),
               (103, "jit__decode", 90.41, 108.41)]
    enqueues = [(100, 1.95), (101, 3.55), (102, 72.15), (103, 73.95)]
    completes = [(100, 20.4), (101, 38.5), (102, 90.6), (103, 108.7)]
    scale = lambda evs: [tuple(x * MS if isinstance(x, float) else x for x in ev) for ev in evs]  # noqa: E731
    return scale(modules), [scale(engine)], scale(enqueues), scale(completes)


def test_a_dispatch_that_comes_late_with_a_step_in_flight_is_the_decode_hosts():
    """The engine keeps a step in flight, so B is always enqueued before A is fetched; a thread 50 ms late to
    enqueue B leaves the chip done with A and waiting, and that wait is how far the decode host holds it back."""
    r = program_runs.build(*_made_up_stall())
    assert r.problem is None and not r.unlinked and [x.span.start / MS for x in r.runs] == pytest.approx([0.5, 2.4, 21.0, 72.8])
    idle = [(20.2 * MS, 20.21 * MS), (38.21 * MS, 72.4 * MS), (90.4 * MS, 90.41 * MS)]
    # 34.19 of the 51.7 ms the thread worked between the fetch that ended at 20.5 and the late enqueue (run 101
    # ran through the rest); the 0.01 behind run 102 goes to the turn that enqueued run 103 too; the first gap
    # has no fetch ahead of its enqueue
    assert _ms(program_runs.idle_by_class(r, idle, _host(r))) == pytest.approx({"decode_host": 34.19 + 0.01, "admit_host": 0.0})


def test_a_run_that_lacks_its_link_gives_its_gap_to_no_class():
    modules, lines, enqueues, completes = _made_up()
    lines[0] = [ev for ev in lines[0] if not (ev[0].startswith("PjitFunction") and ev[1] > 32 * MS)]  # 103 has no call
    r = program_runs.build(modules, lines, enqueues, completes)
    assert r.unlinked == {"jit__decode": 1}
    assert _ms(program_runs.idle_by_class(r, [(30.22 * MS, 32.8 * MS)], _host(r))) == {"decode_host": 0.0, "admit_host": 0.0}
    r = program_runs.build(*_made_up())  # ... and so does one enqueued before the trace's first fetch
    assert _ms(program_runs.idle_by_class(r, [(12.2 * MS, 12.21 * MS)], _host(r))) == {"decode_host": 0.0, "admit_host": 0.0}


IDLE_READERS = ("device.idle_decode_host_pct", "device.idle_admit_host_pct", "device.idle_other_pct")


@pytest.mark.parametrize("suffix", ["", ".latency"])
@pytest.mark.parametrize("path", [RUNS_TRACE, OLDER_TRACE])
def test_the_three_idle_readers_add_up_to_the_idle_share_on_the_recorded_traces(path, suffix, monkeypatch):
    monkeypatch.setattr(scopes, "trace_file", lambda run: path)
    run = _traced(path)
    parts = [bench_run.load_reader(name + suffix, spec.BENCH_DIR).read(run) for name in IDLE_READERS]
    whole = bench_run.load_reader("device.idle_pct.serve" + suffix, spec.BENCH_DIR).read(run)
    assert all(p is not None and p >= 0 for p in parts) and sum(parts) == pytest.approx(whole, abs=1e-6)
    assert parts[0] > 0 and parts[1] > 0  # both traces hold a round and the turn behind it


@pytest.mark.parametrize("path", [RUNS_TRACE, OLDER_TRACE])
def test_the_split_is_of_the_idle_time_the_harness_reads(path):
    """``device.idle_other_pct`` is the rest of ``device.idle_pct.serve``, so the sum above holds whatever the
    split says; what holds the split: its own reading of the device's idle time (``lib/scopes.py``'s instants) is
    ``lib/trace.py``'s, a host class takes no more than the gaps behind the programs its turns enqueued, and
    no more than the thread worked in that class."""
    split, ref = program_runs.idle_split(path), _traced(path)["trace"]
    # picoseconds against rounded nanoseconds: a quarter of a microsecond over the shorter trace's quarter second
    assert split.idle_s == pytest.approx(ref["window_s"] - ref["busy_s"], abs=5e-7)
    assert split.window_s == pytest.approx(ref["window_s"], rel=1e-6)
    assert min(split.by_class.values()) >= 0.0 and sum(split.by_class.values()) <= split.idle_s
    r = program_runs.read(path)
    between_programs = sum(seconds for _, seconds in ref["idle_gaps"])
    assert sum(split.by_class.values()) <= between_programs + 1e-9
    for cls, intervals in _host(r).items():
        assert split.by_class[cls] <= sum(e - s for s, e in intervals)


def test_without_a_step_in_flight_the_link_shares_the_idle_time_out_as_the_midpoint_rule_did():
    """PR 24's engine fetched every program before it enqueued the next, so the span that held a program's
    midpoint was the turn that ran it and the latest fetch ahead of an enqueue the one that waited for the
    program before: the midpoint rule's seconds over this trace (PR 38's ``lib/host_spans.py``) were 0.01884477,
    0.009453179 and 0.013704605294, and the link by ``run_id`` gives the same to the nanosecond but for the gap
    ahead of the trace's last program (67.34 us of ``decode_host``), whose turn the trace cuts off before its
    fetch, so that the midpoint rule let it fall to ``other``."""
    split = program_runs.idle_split(OLDER_TRACE)
    assert split.by_class["decode_host"] == pytest.approx(0.01884477 + 0.00006734, abs=1e-9)
    assert split.by_class["admit_host"] == pytest.approx(0.009453179, abs=1e-9)
    assert split.idle_s - sum(split.by_class.values()) == pytest.approx(0.013704605294 - 0.00006734, abs=1e-9)


def test_with_a_step_in_flight_the_link_takes_the_results_way_back_out_of_decode_host():
    """On the engine that keeps a step in flight the midpoint rule gave a program to the turn after the one
    that enqueued it, found a later dispatch, more host work than the gap, and shared the whole gap out among
    the classes (5.678 + 0.430 + 0.023 ms here). By ``run_id`` the interval ends at the dispatch that did
    enqueue it: each round's result on its way back (0.8-0.9 ms) is no host work and no class's."""
    split = program_runs.idle_split(RUNS_TRACE)
    got = dict(_ms(split.by_class), other=(split.idle_s - sum(split.by_class.values())) / MS)
    assert got == pytest.approx({"decode_host": 3.660802, "admit_host": 0.569637, "other": 1.900785}, abs=1e-4)
    rounds = program_runs.read(RUNS_TRACE).rounds
    on_its_way = sum(x.parts["result_on_its_way"] for x in rounds if x.exposed is not None) / MS
    assert 0.5 * on_its_way < got["other"] < 2.0 * on_its_way + 0.5


@pytest.mark.parametrize("missing", ["enqueues", "completes", "modules", "engine"])
def test_without_the_runtimes_events_there_is_no_reading(missing):
    modules, lines, enqueues, completes = _made_up()
    args = {"modules": modules, "lines": lines, "enqueues": enqueues, "completes": completes}
    args.update({missing: []} if missing != "engine" else {"lines": [[ev for ev in lines[0] if not ev[0].startswith("serve.")]]})
    assert program_runs.build(**args) is None


def test_a_cpu_trace_has_no_reading():
    assert program_runs.read(TRAIN_TRACE) is None  # a device plane, no engine span


# -- the trace recorded on the chip --------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    r = program_runs.read(RUNS_TRACE)
    assert r is not None and r.problem is None
    return r


def test_recorded_every_run_but_the_edges_is_linked_once(recorded):
    assert not recorded.unlinked and len(recorded.edges) <= 4
    linked = recorded.linked()
    assert len(linked) == len(recorded.runs) >= 6
    assert len({(x.span.name, x.span.start) for x in linked}) == len(linked)  # no span enqueued two runs
    assert {x.module for x in linked} == {"jit__decode", "jit__prefill"}
    assert all(x.span.name == (hot.SERVE_PREFILL_DISPATCH if x.module == "jit__prefill" else hot.SERVE_DECODE_DISPATCH)
               for x in linked)
    # every dispatch span of the trace enqueued a run of the trace, but those at its end
    dispatches = [c for s in recorded.spans for c in s.children if c.name in (hot.SERVE_DECODE_DISPATCH, hot.SERVE_PREFILL_DISPATCH)]
    assert 0 <= len(dispatches) - len(linked) <= 2


def test_recorded_run_ids_rise_in_the_order_of_the_engines_turns(recorded):
    """One thread enqueues, in order: the runtime's numbering and the engine thread's spans agree, and the
    process enqueued nothing between two linked runs that no span accounts for."""
    pairs = [(x.run_id, x.span.start) for x in recorded.linked()]
    assert pairs == sorted(pairs) == sorted(pairs, key=lambda p: p[1])
    assert [b[0] - a[0] for a, b in zip(pairs, pairs[1:])] == [1] * (len(pairs) - 1)


def test_recorded_offset_interval(recorded):
    assert recorded.lo <= recorded.hi and 0 < program_runs.clock_slack_us(recorded) < 1000.0


def test_recorded_rounds_wait_behind_a_step_and_their_gaps_are_the_harness_gaps(recorded):
    assert len(recorded.rounds) >= 2
    assert any(x.queued > 1e-3 for x in recorded.rounds)  # behind a step in flight
    ref = trace.reduce_planes(trace.read_planes(RUNS_TRACE), chips=1)
    gaps = dict(ref["idle_gaps"])
    assert program_runs.idle_after_prefill_s(recorded) == pytest.approx(gaps["jit__prefill->jit__decode"], abs=1e-6)
    for x in recorded.rounds:
        if x.exposed is not None:
            assert sum(x.parts.values()) == pytest.approx(x.exposed, abs=1e-9) and min(x.parts.values()) >= -1e-9


def test_recorded_busy_and_return_make_up_each_fetch(recorded):
    spans = [c for s in recorded.spans for c in s.children if c.name in (hot.SERVE_DECODE_FETCH, hot.SERVE_PREFILL_FETCH)]
    assert 0 <= len(spans) - len(recorded.fetches) <= 1  # the first fetch may wait for a run enqueued before the trace
    for f in recorded.fetches:
        assert f.busy >= 0 and f.back > 0 and f.busy + f.back == pytest.approx(f.span.duration, abs=1e-12)
    assert 0.2 < program_runs.fetch_return_ms(recorded, hot.SERVE_DECODE_FETCH) < 2.0


def test_recorded_agrees_with_host_spans_on_the_tree(recorded):
    other = host_spans.read(RUNS_TRACE)
    assert [(s.name, s.start) for s in other.spans] == [(s.name, s.start) for s in recorded.spans]


# -- the readers ---------------------------------------------------------------------------------


class _Cell:
    def __init__(self, kind, name="no-such-cell"):
        self.kind, self.name, self.chips = kind, name, 1


def _traced(path):
    ref = trace.reduce_planes(trace.read_planes(path), chips=1)
    return {"cell": _Cell("serve"), "trace": ref, "counters": {}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_where_there_is_nothing(name, monkeypatch):
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    for kind in ("serve", "train"):  # a run that was not traced, of either kind
        assert reader.read({"cell": _Cell(kind), "trace": None, "counters": {}}) is None
    # a traced run of a training cell, with the recorded training trace in place
    monkeypatch.setattr(scopes, "trace_file", lambda run: TRAIN_TRACE)
    assert reader.read(dict(_traced(TRAIN_TRACE), cell=_Cell("train"))) is None
    if reader.SOURCE == "program_span":  # a program that has no spans at all
        monkeypatch.setattr(scopes, "names", lambda: None)
        monkeypatch.setattr(scopes, "trace_file", lambda run: RUNS_TRACE)
        assert reader.read(_traced(RUNS_TRACE)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_on_the_recorded_trace(name, monkeypatch):
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    monkeypatch.setattr(scopes, "trace_file", lambda run: RUNS_TRACE)
    value = reader.read(_traced(RUNS_TRACE))
    assert value is not None and 0 <= value < (100.0 if reader.UNIT == "%" else 1e3)
    if name.startswith("device.idle_after_prefill_pct"):
        ref = _traced(RUNS_TRACE)["trace"]
        assert value == pytest.approx(100.0 * dict(ref["idle_gaps"])["jit__prefill->jit__decode"] / ref["window_s"], abs=1e-9)
        # ... and the rounds found by ``run_id`` leave the same gaps behind them
        linked = program_runs.idle_after_prefill_s(program_runs.read(RUNS_TRACE))
        assert value == pytest.approx(100.0 * linked / ref["window_s"], abs=1e-4)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_on_a_trace_of_a_program_without_what_it_reads(name, monkeypatch):
    """An older program's trace (the parent's, in the driver's comparison): what the runtime and the device
    record reads as ever, what the round's new attributes would say reads None, nothing raises."""
    reader = bench_run.load_reader(name, spec.BENCH_DIR)
    monkeypatch.setattr(scopes, "trace_file", lambda run: OLDER_TRACE)
    value = reader.read(_traced(OLDER_TRACE))
    from_attributes = name in ("engine.prefill_padding_pct", "engine.prefill_slots_stalled")
    assert (value is None) if from_attributes else (value is not None and value >= 0)
