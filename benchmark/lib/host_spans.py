"""The serving engine's spans and the device's idle time, from one trace.

The program's hot-path spans (``torchx_tpu/obs/hot.py``) are
``jax.profiler.TraceAnnotation`` events on the engine thread's line of the
``/host:CPU`` plane, in the same ``.xplane.pb`` as the device's ``XLA Ops``.
This module reads that line into a span tree, and splits the device's idle
time (the gaps of ``lib/trace.py``'s busy union, over the same window) by what
the engine thread did between the two programs on either side of each gap:

* ``decode_host``: inside ``serve.decode`` but not waiting in
  ``serve.decode.fetch`` (commit of the step before, prepare and dispatch of
  the step after, the steps' self time),
* ``admit_host``: inside ``serve.admit`` but not waiting in
  ``serve.prefill.fetch`` (plan, build, dispatch, commit),
* ``other``: the rest: the wait of a fetch after the device finished, the way
  from a dispatch to the device's first operation, ``serve.idle``, time under
  no span, bubbles inside a program.

The engine is one thread, so between the fetch that returned program A's
result and the dispatch that enqueued program B everything it did lies inside
the device's gap between A and B; a gap shorter than that host work (the
device starts before the dispatch call returns) is shared out in proportion.
Only durations are taken from each clock and the clocks are used together
only to find which span ran which program, because the profiler's alignment
of the device's clock with the host's moved by 1.3 ms between two captures of
one process (PERF.md, PR 24), half of a gap. The three add up to the window's
idle time exactly. A program built before the spans existed has none of
them: every function here then returns None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import statistics
from typing import Iterable, Optional

from . import scopes
from . import trace as trace_lib

HOST_PLANE = "/host:CPU"
Interval = tuple[float, float]


@dataclasses.dataclass
class Span:
    name: str
    start: float  # seconds, the trace's clock
    end: float
    attrs: dict
    children: list["Span"] = dataclasses.field(default_factory=list)

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def self_time(self) -> float:
        return self.duration - sum(c.duration for c in self.children)

    def child_time(self, name: str) -> float:
        return sum(c.duration for c in self.children if c.name == name)


def build_tree(events: Iterable[tuple[str, float, float, dict]]) -> list[Span]:
    """Top-level spans of one thread from ``(name, start, end, attrs)``: an
    event lies under the latest earlier one that contains it."""
    roots: list[Span] = []
    stack: list[Span] = []
    for name, s, e, attrs in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        span = Span(name, s, e, attrs)
        while stack and s >= stack[-1].end:
            stack.pop()
        (stack[-1].children if stack else roots).append(span)
        stack.append(span)
    return roots


@dataclasses.dataclass
class Reading:
    spans: list[Span]  # the engine thread's top-level spans, in time order
    window: Interval  # first to last device operation, as lib/trace.py has it
    idle_by_class: dict[str, float]  # decode_host, admit_host, other: seconds

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]


def read(path: str) -> Optional[Reading]:
    """The engine thread's spans and the device's idle time from one trace
    file; None where the file has no engine span or no device work."""
    return _read(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime: float) -> Optional[Reading]:
    hot = scopes.names()
    if hot is None:
        return None
    spans = engine_spans(path)
    planes = [p for p in scopes.read_planes(path) if p["ops"] or p["modules"]]
    if not spans or not planes:
        return None
    # the engine is one thread driving one chip; on a sharded engine every
    # chip runs the same programs and the first stands for all
    ops = planes[0]["ops"] or planes[0]["modules"]
    _, busy = trace_lib._union((s, e) for _, s, e, _ in ops)
    window = (busy[0][0], busy[-1][1])
    idle = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:]) if b_start > a_end]
    host = {
        "decode_host": _host_intervals(spans, hot.SERVE_DECODE, hot.SERVE_DECODE_FETCH),
        "admit_host": _host_intervals(spans, hot.SERVE_ADMIT, hot.SERVE_PREFILL_FETCH),
    }
    by_class = dict.fromkeys(host, 0.0)
    fetch = (hot.SERVE_DECODE_FETCH, hot.SERVE_PREFILL_FETCH)
    dispatch = (hot.SERVE_DECODE_DISPATCH, hot.SERVE_PREFILL_DISPATCH)
    runs = sorted((s, e) for _, s, e, _ in planes[0]["modules"])
    starts = [sp.start for sp in spans]
    owners = [_owner(spans, starts, (s + e) / 2) for s, e in runs]
    for (_, a_end), (b_start, _), a, b in zip(runs, runs[1:], owners, owners[1:]):
        gap = overlap(idle, [(a_end, b_start)])
        after, until = _child(a, fetch), _child(b, dispatch)
        if gap <= 0.0 or after is None or until is None:
            continue
        work = {cls: overlap([(after.end, until.end)], iv) for cls, iv in host.items()}
        total = sum(work.values())
        for cls in work:
            by_class[cls] += work[cls] * min(1.0, gap / total) if total > 0 else 0.0
    by_class["other"] = sum(e - s for s, e in idle) - sum(by_class.values())
    return Reading(spans, window, by_class)


def _owner(spans: list[Span], starts: list[float], t: float) -> Optional[Span]:
    """The top-level span that holds the instant ``t``: a program's midpoint
    lies in the step or round that ran it whatever the clocks' offset."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i] if i >= 0 and t < spans[i].end else None


def _child(span: Optional[Span], names: tuple[str, ...]) -> Optional[Span]:
    return next((c for c in span.children if c.name in names), None) if span else None


def engine_spans(path: str) -> list[Span]:
    """The top-level ``serve.*`` spans of the engine thread: the one line of
    the host plane that holds decode steps or admission rounds."""
    from jax.profiler import ProfileData

    hot = scopes.names()
    ours = {hot.SERVE_DECODE, hot.SERVE_ADMIT}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = [
                (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats))
                for ev in line.events
                if ev.name.startswith("serve.")
            ]
            if any(name in ours for name, *_ in events):
                return build_tree(events)
    return []


def _host_intervals(spans: list[Span], parent: str, waiting: str) -> list[Interval]:
    """Where the thread was inside a ``parent`` span and not in its
    ``waiting`` child: sorted, disjoint."""
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


def overlap(a: list[Interval], b: list[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def of_run(run: dict, kind: str = "serve") -> Optional[Reading]:
    """The reading of a traced run of a ``kind`` cell, else None."""
    if run["cell"].kind != kind:
        return None
    path = scopes.trace_file(run)
    return read(path) if path else None


# -- what the readers under layer_metrics/ return --------------------------------


def host_ms_per_step(r: Reading) -> Optional[float]:
    """Median over decode steps of the step's span less its wait for the device."""
    hot = scopes.names()
    steps = [(s.duration - s.child_time(hot.SERVE_DECODE_FETCH)) * 1e3 for s in r.named(hot.SERVE_DECODE)]
    return statistics.median(steps) if steps else None


def admit_host_ms(r: Reading) -> Optional[float]:
    """Mean over admission rounds that prefilled of the round's span less its
    wait for the device."""
    hot = scopes.names()
    rounds = [
        (s.duration - s.child_time(hot.SERVE_PREFILL_FETCH)) * 1e3
        for s in r.named(hot.SERVE_ADMIT)
        if s.child_time(hot.SERVE_PREFILL_DISPATCH) > 0
    ]
    return sum(rounds) / len(rounds) if rounds else None


def prefill_stall_pct(r: Reading) -> float:
    """Share of the window the engine thread spent inside ``serve.admit``:
    no running request gets a token then."""
    hot = scopes.names()
    inside = overlap([r.window], [(s.start, s.end) for s in r.named(hot.SERVE_ADMIT)])
    return 100.0 * inside / (r.window[1] - r.window[0])


def traced_step_ms(r: Reading) -> Optional[float]:
    """Median start-to-start period of two decode steps with nothing between
    them on the engine thread (no admission, no idle wait)."""
    hot = scopes.names()
    periods = [
        (b.start - a.start) * 1e3
        for a, b in zip(r.spans, r.spans[1:])
        if a.name == hot.SERVE_DECODE and b.name == hot.SERVE_DECODE
    ]
    return statistics.median(periods) if periods else None


def idle_pct(r: Reading, cls: str) -> float:
    return 100.0 * r.idle_by_class[cls] / (r.window[1] - r.window[0])


def coverage(r: Reading, parent: str) -> Optional[float]:
    """Share of the ``parent`` spans' time that their children cover."""
    ps = r.named(parent)
    total = sum(p.duration for p in ps)
    return 1.0 - sum(p.self_time for p in ps) / total if total > 0 else None
