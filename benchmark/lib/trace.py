"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU's plane is
named ``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per run of
a compiled program (``jit_step(...)``, ``jit__decode(...)``) and its line
``XLA Ops`` one event per operation. Busy time is the union of the operations'
intervals; the traced window runs from the first operation's start to the last
one's end on that chip, so the profiler's own start and stop do not count as
idle. Numbers are averaged over the chips in use.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Iterable, Optional

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
CONTAINER_OP = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def find_xplane(profile_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return files[-1]


def _union(intervals: Iterable[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Length of the union of ``(start, end)`` intervals, and the merged list."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def module_name(event_name: str) -> str:
    """``jit__decode(1234)`` -> ``jit__decode``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def read_planes(path: str) -> list[dict]:
    """Each device plane as ``{"name", "modules": [(name, start_s, end_s)],
    "ops": [...]}`` with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        out = {"name": plane.name, "modules": [], "ops": [], "lines": sorted(lines)}
        for key, line_name in (("modules", MODULE_LINE), ("ops", OPS_LINE)):
            line = lines.get(line_name)
            if line is None:
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                out[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
        planes.append(out)
    return planes


def reduce_planes(planes: list[dict], chips: int, top: int = 10) -> Optional[dict]:
    """Busy seconds, window seconds, per-module run times, the operations that
    took most time and the longest idle gaps, averaged over ``chips`` planes.
    None where no operation ran on a device."""
    used = [p for p in planes if p["ops"] or p["modules"]][:chips]
    if not used:
        return None
    busy, window = [], []
    module_runs: dict[str, list[float]] = {}
    op_time: dict[str, float] = {}
    gap_time: dict[str, float] = {}
    for p in used:
        events = p["ops"] or p["modules"]
        total, merged = _union((s, e) for _, s, e in events)
        busy.append(total)
        window.append(merged[-1][1] - merged[0][0])
        for name, s, e in p["modules"]:
            module_runs.setdefault(module_name(name), []).append(e - s)
        for name, s, e in p["ops"]:
            short = name.split(" = ")[0].lstrip("%")  # the HLO text follows the name
            if not CONTAINER_OP.match(short):  # its body's operations are listed too
                op_time[short] = op_time.get(short, 0.0) + (e - s)
        # an idle gap is named by the programs on either side of it
        mods = sorted(p["modules"], key=lambda m: m[1])
        for (a, _, a_end), (b, b_start, _) in zip(mods, mods[1:]):
            if b_start > a_end:
                key = f"{module_name(a)}->{module_name(b)}"
                gap_time[key] = gap_time.get(key, 0.0) + (b_start - a_end)
    n = len(used)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "busy_s": sum(busy) / n,
        "window_s": sum(window) / n,
        "module_runs": module_runs,
        "module_median_s": {k: statistics.median(v) for k, v in module_runs.items()},
        "device_ops": [[k, v / n] for k, v in rank(op_time)],
        "idle_gaps": [[k, v / n] for k, v in rank(gap_time)],
    }


def reduce_trace(profile_dir: str, chips: int) -> Optional[dict]:
    return reduce_planes(read_planes(find_xplane(profile_dir)), chips)
