"""Every program run of a trace tied to the engine turn that enqueued it, and the
two clocks' offset bounded from the trace itself.

The runtime numbers its program runs: each ``XLA Modules`` event on the device
plane carries a stat ``run_id``, and on ``/host:CPU`` the runtime's own threads
record one ``DoEnqueueProgram`` event a run and one ``CompleteCallbacks`` event
a finished run **with the same** ``run_id``. The engine thread's line holds a
``PjitFunction(<name>)`` event for every compiled call, inside the program's
span that made it (``serve.decode.dispatch``, ``serve.prefill.dispatch``,
``serve.kv_import``, ``serve.cow_copy``: ``torchx_tpu/obs/hot.py``). All of
these are C++ events: they are there with the Python tracer off, and the spans
carry nothing for the link. So, from one ``.xplane.pb``:

* **runs**: device event + enqueue + completion, joined by ``run_id``. One
  thread enqueues, in order, so each enqueue is given to the earliest call not
  yet taken that precedes it on the host's clock and names its module
  (``PjitFunction(_decode)`` -> ``jit__decode``), and through the call to the
  innermost ``serve.*`` span that holds it. A completion is given to the ``.fetch`` span whose
  ``np.asarray`` waited for it: the latest run of the fetch's kind that the
  runtime had completed when the span ended. Runs that lack a side (the trace's
  edges) are kept apart; runs whose call lies in no span are counted by module.
* **the offset** (device clock less host clock): a program cannot start before
  the host began to enqueue it, and the host cannot learn of its end before it
  ended, so ``lo = max(device end - CompleteCallbacks start)`` and ``hi =
  min(device start - DoEnqueueProgram start)`` bound it on both sides. Host
  instants move onto the device's clock by the interval's midpoint; whatever
  crosses clocks is good to half the slack ``hi - lo``.
* **per round**: ``queued``, from the enqueue of its ``jit__prefill`` run to the
  run's first instant; ``exposed``, the device's idle time from the run's last
  instant to the next run's first (device clock alone), shared out by overlap
  among the engine thread's spans.
* **per fetch**: ``busy`` (before its run's last instant) and ``return`` (after).
* **the idle split**: each gap between two runs goes to the turn that enqueued
  the second, shared among ``decode_host``, ``admit_host`` and the rest by what
  the engine thread did while it could have enqueued it (``idle_by_class``).

Where the runtime's events are absent (another jaxlib, a CPU trace), or a
program built before the spans existed, every function returns None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import re
import statistics
from typing import Optional

from . import host_spans, scopes
from .host_spans import Interval, Span
from .trace import MODULE_LINE, _union, module_name

ENQUEUE_EVENT = "DoEnqueueProgram"
COMPLETE_EVENT = "CompleteCallbacks"
CALL_EVENT = re.compile(r"^PjitFunction\((.*)\)$")
#: what the idle time after a round is shared out among
EXPOSED_PARTS = ("result_on_its_way", "commit", "prepare", "dispatch", "rest")


@dataclasses.dataclass
class Run:
    run_id: int
    module: str  # jit__decode; "" where the device's event is missing
    start: Optional[float] = None  # the device's clock, seconds
    end: Optional[float] = None
    enqueue: Optional[float] = None  # the host's clock: DoEnqueueProgram's start
    complete: Optional[float] = None  # the host's clock: CompleteCallbacks' start
    call: Optional[float] = None  # the host's clock: the compiled call the enqueue follows
    span: Optional[Span] = None  # the span that holds the call

    @property
    def whole(self) -> bool:
        return None not in (self.start, self.enqueue, self.complete)


@dataclasses.dataclass
class Round:
    span: Span  # the serve.admit span
    run: Run
    queued: float  # seconds from the enqueue to the run's first instant, on the aligned clocks
    exposed: Optional[float]  # device idle seconds to the next run's first instant; None for the trace's last run
    parts: dict[str, float]  # EXPOSED_PARTS -> seconds of ``exposed``


@dataclasses.dataclass
class Fetch:
    span: Span
    run: Run
    busy: float  # seconds of the span before the run's last instant
    back: float  # seconds after it: the result on its way ("return")


@dataclasses.dataclass
class Reading:
    runs: list[Run]  # with all three sides, by run_id
    #: lacking a side: enqueued before the trace began, unfinished when it ended; or (also in ``runs``: the
    #: runtime's three sides bound the offset all the same) called before it began and enqueued inside it
    edges: list[Run]
    unlinked: dict[str, int]  # module -> runs of the trace's inside whose call lies in no span (or that have no call)
    lo: float  # the offset's bounds, seconds (device clock less host clock)
    hi: float
    problem: Optional[str]  # why nothing that crosses clocks can be read, else None
    spans: list[Span]  # the engine thread's top-level spans, in time order
    rounds: list[Round]
    fetches: list[Fetch]

    @property
    def offset(self) -> float:
        return (self.lo + self.hi) / 2

    @property
    def slack(self) -> float:
        return self.hi - self.lo

    def linked(self) -> list[Run]:
        return [r for r in self.runs if r.span is not None]


def read(path: str) -> Optional[Reading]:
    """The runs, the offset, the rounds and the fetches of one trace file; None
    where it has no engine span or none of the runtime's events."""
    return _read(path, os.path.getmtime(path))


def of_run(run: dict) -> Optional[Reading]:
    """The reading of a traced run of a serving cell, else None."""
    if run["cell"].kind != "serve" or scopes.names() is None:
        return None
    path = scopes.trace_file(run)
    return read(path) if path else None


def sound(run: dict) -> Optional[Reading]:
    """``of_run`` where the links and the offset hold, else None."""
    r = of_run(run)
    return r if r is not None and r.problem is None else None


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime: float) -> Optional[Reading]:
    return build(*_events(path))


def _events(path: str) -> tuple[list, list[list], list, list]:
    """-> (the first device's ``XLA Modules`` events as ``(run_id, module,
    start, end)``, each host line's events as ``(name, start, end, stats)``,
    enqueues and completions as ``(run_id, start)``), seconds."""
    from jax.profiler import ProfileData

    modules, lines, enqueues, completes = [], [], [], []
    ordinal = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and ordinal is None:
            for line in plane.lines:
                if line.name != MODULE_LINE:
                    continue
                for ev in line.events:
                    run_id = dict(ev.stats).get("run_id")
                    if run_id is not None:
                        s = ev.start_ns * 1e-9
                        modules.append((int(run_id), module_name(ev.name), s, s + ev.duration_ns * 1e-9))
            if modules:
                ordinal = int(plane.name.rsplit(":", 1)[1])
        elif plane.name == host_spans.HOST_PLANE:
            for line in plane.lines:
                kept = []
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if ev.name in (ENQUEUE_EVENT, COMPLETE_EVENT):
                        stats = dict(ev.stats)
                        if "run_id" in stats:
                            into = enqueues if ev.name == ENQUEUE_EVENT else completes
                            into.append((int(stats["run_id"]), s, int(stats.get("device_ordinal", 0))))
                    elif ev.name.startswith("serve.") or CALL_EVENT.match(ev.name):
                        kept.append((ev.name, s, s + ev.duration_ns * 1e-9, dict(ev.stats)))
                if kept:
                    lines.append(kept)
    on_device = lambda evs: [(i, s) for i, s, o in evs if o == (ordinal or 0)]  # noqa: E731
    return modules, lines, on_device(enqueues), on_device(completes)


def build(modules: list, lines: list[list], enqueues: list, completes: list) -> Optional[Reading]:
    """The reading of already-read events (``_events``): what the tests hand
    made-up planes to."""
    hot = scopes.names()
    if hot is None or not modules or not enqueues or not completes:
        return None
    engine = [ln for ln in lines if any(name in (hot.SERVE_DECODE, hot.SERVE_ADMIT) for name, *_ in ln)]
    if not engine:
        return None
    spans = host_spans.build_tree([ev for ev in engine[0] if ev[0].startswith("serve.")])

    by_id: dict[int, Run] = {}
    for run_id, module, s, e in modules:
        by_id[run_id] = Run(run_id, module, s, e)
    for run_id, s in enqueues:
        by_id.setdefault(run_id, Run(run_id, "")).enqueue = s
    for run_id, s in completes:
        by_id.setdefault(run_id, Run(run_id, "")).complete = s
    ordered = [by_id[i] for i in sorted(by_id)]
    runs, edges = [r for r in ordered if r.whole], [r for r in ordered if not r.whole]

    # every run that has its enqueue takes its call, an edge too: one that lacked a side mid-trace (a completion the
    # profiler dropped in a stall of the host) would leave its call to the next run, and every later link one turn off
    first_call = _link_calls([r for r in ordered if r.enqueue is not None], lines)
    unlinked: dict[str, int] = {}
    for r in runs:
        if r.call is None and r.enqueue < first_call:
            edges.append(r)  # the trace began between its call and its enqueue
        elif r.span is None:
            unlinked[r.module] = unlinked.get(r.module, 0) + 1

    lo = max(r.end - r.complete for r in runs) if runs else 0.0
    hi = min(r.start - r.enqueue for r in runs) if runs else 0.0
    problem = None
    if not runs:
        problem = "no run of the trace has its device event, its enqueue and its completion"
    elif lo > hi:
        problem = f"the offset's interval is empty: lo {lo * 1e6:.1f} us > hi {hi * 1e6:.1f} us"
    reading = Reading(runs, edges, unlinked, lo, hi, problem, spans, [], [])
    if problem is None:
        reading.rounds = _rounds(reading, ordered, hot)
        reading.fetches = _fetches(reading, hot)
    return reading


def _link_calls(runs: list[Run], lines: list[list]) -> float:
    """Give each run the compiled call its enqueue follows, and the innermost
    ``serve.*`` span that holds the call. Calls of every host thread are taken in time order (one
    device queue): an enqueue takes the earliest call not yet taken that began
    before it and names its module. -> the first call's instant."""
    calls = []  # (start, module, span or None)
    for line in lines:
        holders = [ev for ev in line if ev[0].startswith("serve.")]
        outer_end = 0.0
        for name, s, e, _ in sorted((ev for ev in line if CALL_EVENT.match(ev[0])), key=lambda ev: (ev[1], -ev[2])):
            if s < outer_end:
                continue  # the same call, a level down
            outer_end = e
            holder = max((h for h in holders if h[1] <= s < h[2]), key=lambda h: h[1], default=None)
            calls.append((s, "jit_" + CALL_EVENT.match(name).group(1), holder and Span(*holder)))
    calls.sort(key=lambda c: c[0])
    taken = [False] * len(calls)
    first = 0
    for run in sorted(runs, key=lambda r: r.enqueue):
        while first < len(calls) and taken[first]:
            first += 1
        for i in range(first, len(calls)):
            start, module, span = calls[i]
            if start > run.enqueue:
                break
            if not taken[i] and module == (run.module or module):  # no device event, no name: the next call is its
                taken[i], run.call, run.span = True, start, span
                break
    return calls[0][0] if calls else float("inf")


def _rounds(reading: Reading, ordered: list[Run], hot) -> list[Round]:  # noqa: ANN001
    on_device = sorted((r for r in ordered if r.start is not None), key=lambda r: r.start)
    following = {a.run_id: b for a, b in zip(on_device, on_device[1:])}
    shift = reading.offset
    leaves: dict[str, list[Interval]] = {}
    for top in reading.spans:
        for c in top.children:
            leaves.setdefault(c.name, []).append((c.start + shift, c.end + shift))
    named = {
        "result_on_its_way": (hot.SERVE_PREFILL_FETCH,),
        "commit": (hot.SERVE_ADMIT_COMMIT,),
        "prepare": (hot.SERVE_DECODE_PREPARE,),
        "dispatch": (hot.SERVE_DECODE_DISPATCH, hot.SERVE_PREFILL_DISPATCH),
    }
    out = []
    linked = {(r.span.name, r.span.start): r for r in reading.linked()}
    for top in reading.spans:
        dispatch = host_spans._child(top, (hot.SERVE_PREFILL_DISPATCH,)) if top.name == hot.SERVE_ADMIT else None
        run = linked.get((dispatch.name, dispatch.start)) if dispatch else None
        if run is None:
            continue
        nxt = following.get(run.run_id)
        exposed, parts = None, {}
        if nxt is not None:
            exposed = max(0.0, nxt.start - run.end)
            gap = [(run.end, nxt.start)] if exposed > 0 else []
            parts = {
                part: sum(host_spans.overlap(gap, sorted(leaves.get(n, []))) for n in names) for part, names in named.items()
            }
            parts["rest"] = exposed - sum(parts.values())
        out.append(Round(top, run, run.start - (run.enqueue + shift), exposed, parts))
    return out


def _fetches(reading: Reading, hot) -> list[Fetch]:  # noqa: ANN001
    kinds = {hot.SERVE_DECODE_FETCH: hot.SERVE_DECODE_DISPATCH, hot.SERVE_PREFILL_FETCH: hot.SERVE_PREFILL_DISPATCH}
    shift, linked = reading.offset, reading.linked()
    out = []
    for top in reading.spans:
        for c in top.children:
            if c.name not in kinds:
                continue
            done = [r for r in linked if r.span.name == kinds[c.name] and r.complete <= c.end]
            if not done:
                continue
            run = done[-1]
            busy = min(max(run.end - shift - c.start, 0.0), c.duration)
            out.append(Fetch(c, run, busy, c.duration - busy))
    return out


# -- the device's idle time, shared out among what the engine thread did ---------


def host_classes(spans: list[Span], hot) -> dict[str, list[Interval]]:  # noqa: ANN001
    """Where the engine thread worked and did not wait for the device:
    ``decode_host`` inside ``serve.decode`` and outside its ``.fetch`` (commit
    of the step before, prepare and dispatch of the step after, the step's
    self time), ``admit_host`` inside ``serve.admit`` and outside
    ``serve.prefill.fetch`` (plan, build, dispatch, commit). Sorted, disjoint."""

    def outside(parent: str, waiting: str) -> list[Interval]:
        out = []
        for sp in spans:
            if sp.name != parent:
                continue
            t = sp.start
            for c in sp.children:
                if c.name == waiting:
                    out.append((t, c.start))
                    t = c.end
            out.append((t, sp.end))
        return [(s, e) for s, e in out if e > s]

    return {"decode_host": outside(hot.SERVE_DECODE, hot.SERVE_DECODE_FETCH),
            "admit_host": outside(hot.SERVE_ADMIT, hot.SERVE_PREFILL_FETCH)}


def idle_by_class(r: Reading, idle: list[Interval], host: dict[str, list[Interval]]) -> dict[str, float]:
    """Each of the device's ``idle`` gaps between two consecutive runs A and B
    goes to the turn that enqueued B (found by ``run_id``, never by where an
    instant falls on the other clock), shared out among the classes of ``host``
    (class -> the host intervals that count for it) by what the engine thread
    did of each from the last result it had in hand before that enqueue (the
    end of the latest ``.fetch`` span that precedes the span that enqueued B)
    to the end of that span: the stretch in which it could have enqueued B and
    had not yet. With a step in flight that fetch is the one before A's (B is
    enqueued while A runs), so a thread late to enqueue B, the chip done with A
    and waiting, reads as the class it was late in; behind a round it is the
    round's own fetch. A gap shorter than that host work (the usual case: A ran
    through most of it; and every microsecond gap between two programs both
    already enqueued) is shared in proportion; of a longer one the rest is no
    class's (a result on its way back, ``serve.idle``, time under no span).
    A run that lacks its link gives its gap to no class. Durations alone are
    taken from each clock. -> class -> seconds"""
    hot = scopes.names()
    fetch_ends = sorted(c.end for top in r.spans for c in top.children
                        if c.name in (hot.SERVE_DECODE_FETCH, hot.SERVE_PREFILL_FETCH))
    on_device = sorted({x.run_id: x for x in r.runs + r.edges if x.start is not None}.values(), key=lambda x: x.start)
    by_class = dict.fromkeys(host, 0.0)
    for a, b in zip(on_device, on_device[1:]):
        gap = host_spans.overlap(idle, [(a.end, b.start)])
        i = bisect.bisect_right(fetch_ends, b.span.start) - 1 if b.span is not None else -1
        if gap <= 0.0 or i < 0:
            continue
        work = {cls: host_spans.overlap([(fetch_ends[i], b.span.end)], iv) for cls, iv in host.items()}
        total = sum(work.values())
        for cls in work:
            by_class[cls] += work[cls] * min(1.0, gap / total) if total > 0 else 0.0
    return by_class


@dataclasses.dataclass
class IdleSplit:
    by_class: dict[str, float]  # decode_host, admit_host: seconds of the device's idle time
    idle_s: float  # all of it: the gaps of the first device's busy union, this module's own reading
    window_s: float  # first to last device operation


def idle_split(path: str) -> Optional[IdleSplit]:
    """The first device's idle time in one trace file (the engine is one thread
    driving one chip; on a sharded engine every chip runs the same programs and
    the first stands for all) and the host classes' parts of it; None where
    the file has no engine span, no device work or none of the runtime's events."""
    return _idle_split(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _idle_split(path: str, _mtime: float) -> Optional[IdleSplit]:
    r = read(path)
    planes = [p for p in scopes.read_planes(path) if p["ops"] or p["modules"]]
    if r is None or not planes:
        return None
    _, busy = _union((s, e) for _, s, e, _ in planes[0]["ops"] or planes[0]["modules"])
    idle = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:]) if b_start > a_end]
    by_class = idle_by_class(r, idle, host_classes(r.spans, scopes.names()))
    return IdleSplit(by_class, sum(e - s for s, e in idle), busy[-1][1] - busy[0][0])


def idle_split_pct(run: dict, cls: str) -> Optional[float]:
    """Class ``cls`` of a traced serving run's idle time as a share of the
    traced window: a host class's seconds over ``device.idle_pct.serve``'s own
    window (``run["trace"]``), ``other`` the rest of that idle share, so that
    the three add up to it to the last digit whichever reader's instants each
    was made from (``lib/trace.py`` reads whole nanoseconds, ``lib/scopes.py``
    picoseconds: half a nanosecond an operation, 3 to 100 us of a 4 s trace,
    0.003 points at most on the chip runs made). What holds the split itself
    is in the tests: the module's own idle total is ``lib/trace.py``'s to that
    rounding, and a class exceeds neither the gaps between programs nor what
    the thread worked in it."""
    tr = run.get("trace")
    if not tr or run["cell"].kind != "serve" or scopes.names() is None:
        return None
    path = scopes.trace_file(run)
    split = idle_split(path) if path else None
    if split is None:
        return None
    host = {c: 100.0 * s / tr["window_s"] for c, s in split.by_class.items()}
    return host[cls] if cls in host else 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) - sum(host.values())


# -- what the readers under layer_metrics/ return --------------------------------


def round_attrs(run: dict, key: str) -> list[dict]:
    """The attributes of the traced ``serve.admit`` spans that carry ``key``: the
    rounds that prefilled (a program from before the attribute gives none)."""
    hot = scopes.names()
    r = host_spans.of_run(run) if hot else None
    return [s.attrs for s in r.named(hot.SERVE_ADMIT) if key in s.attrs] if r else []


def clock_slack_us(r: Reading) -> Optional[float]:
    return r.slack * 1e6 if r.problem is None else None


def prefill_queued_ms(r: Reading) -> Optional[float]:
    """Median over the traced rounds of the time from the enqueue of the
    round's program to its first instant on the device."""
    return statistics.median(x.queued for x in r.rounds) * 1e3 if r.rounds else None


def idle_after_prefill_s(r: Reading) -> Optional[float]:
    """Device idle seconds behind the traced rounds' programs, the device's clock alone."""
    gaps = [x.exposed for x in r.rounds if x.exposed is not None]
    return sum(gaps) if gaps else None


def exposed_parts_ms(r: Reading) -> Optional[dict[str, float]]:
    """Mean over the traced rounds of each part of the exposed turn, ms."""
    rounds = [x for x in r.rounds if x.exposed is not None]
    if not rounds:
        return None
    return {part: 1e3 * sum(x.parts[part] for x in rounds) / len(rounds) for part in EXPOSED_PARTS}


def fetch_return_ms(r: Reading, name: str, part: str = "back") -> Optional[float]:
    """Median over the ``name`` fetch spans of the part after their run's last
    instant (``part`` "busy": of the part before it)."""
    parts = [getattr(f, part) for f in r.fetches if f.span.name == name]
    return statistics.median(parts) * 1e3 if parts else None


def account(r: Reading) -> dict:
    """One traced run in the numbers PERF.md records of it."""
    out = {
        "runs": len(r.runs),
        "linked": len(r.linked()),
        "edges": len(r.edges),
        "unlinked": r.unlinked,
        "offset_lo_us": r.lo * 1e6,
        "offset_hi_us": r.hi * 1e6,
        "clock_slack_us": r.slack * 1e6,
        "problem": r.problem,
    }
    if r.problem is None:
        fetches = (scopes.names().SERVE_DECODE_FETCH, scopes.names().SERVE_PREFILL_FETCH)
        out.update({
            "rounds": len(r.rounds),
            "prefill_queued_ms": prefill_queued_ms(r),
            "idle_after_prefill_s": idle_after_prefill_s(r),
            "exposed_parts_ms": exposed_parts_ms(r),
            "fetch_busy_ms": {n: fetch_return_ms(r, n, "busy") for n in fetches},
            "fetch_return_ms": {n: fetch_return_ms(r, n) for n in fetches},
        })
    return out
