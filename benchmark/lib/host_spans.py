"""The serving engine's spans, from one trace.

The program's hot-path spans (``torchx_tpu/obs/hot.py``) are
``jax.profiler.TraceAnnotation`` events on the engine thread's line of the
``/host:CPU`` plane, in the same ``.xplane.pb`` as the device's ``XLA Ops``.
This module reads that line into a span tree and answers what the tree alone
can say (a turn's host time, a round's, the decode period). What ties a span
to the device's programs, the split of the device's idle time among what the
thread did between two of them included, is ``lib/program_runs.py``'s, which
builds on this tree. A program built before the spans existed has none of
them: every function here then returns None.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import statistics
from typing import Iterable, Optional

from . import scopes

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

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]


def read(path: str) -> Optional[Reading]:
    """The engine thread's spans from one trace file; None where the file has
    no engine span or no device work."""
    return _read(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime: float) -> Optional[Reading]:
    if scopes.names() is None:
        return None
    spans = engine_spans(path)
    if not spans or not any(p["ops"] or p["modules"] for p in scopes.read_planes(path)):
        return None
    return Reading(spans)


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


def coverage(r: Reading, parent: str) -> Optional[float]:
    """Share of the ``parent`` spans' time that their children cover."""
    ps = r.named(parent)
    total = sum(p.duration for p in ps)
    return 1.0 - sum(p.self_time for p in ps) / total if total > 0 else None
