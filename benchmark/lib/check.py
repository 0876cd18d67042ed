"""The comparison that decides ``correct``: numbers beside their limits."""

from __future__ import annotations

import statistics


class Verdict:
    """Collects each number compared with its limit, prints them, and is
    correct only if every number is within its limit and nothing was flagged."""

    def __init__(self) -> None:
        self.rows: list[tuple[str, float, float]] = []
        self.flags: list[str] = []

    def compare(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    def flag(self, why: str) -> None:
        self.flags.append(why)

    @property
    def correct(self) -> bool:
        ok = all(v <= lim and v == v for _, v, lim in self.rows)
        return ok and not self.flags and bool(self.rows)

    def print(self) -> None:
        for name, v, lim in self.rows:
            mark = "ok" if v <= lim else "OVER"
            print(f"check: {name} = {v:.6g} (limit {lim:.6g}) {mark}", flush=True)
        for why in self.flags:
            print(f"check: FLAG {why}", flush=True)


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Largest gap between the program's and the reference's norm of a leaf,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger (some leaves' gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))}")
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, median, 1e-30)
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where
