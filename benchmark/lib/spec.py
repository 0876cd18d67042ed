"""Find a cell's files by the names in BENCHMARK.json.

A cell is ``workloads/<cell>.json`` (configuration, traffic, chips, why, and the
limits of its check), a
configuration is ``configs/<name>.json`` and a traffic mix or training job is
``traffic/<name>.json``. Nothing here knows a cell by name: a later PR adds
files and a BENCHMARK.json entry, and edits none of these.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def scratch_dir(cell: "Cell") -> str:
    """Where a run keeps its files (tokens, traces): inside the checkout,
    never at a fixed path outside it."""
    d = os.path.join(REPO_ROOT, ".bench_scratch", cell.name)
    os.makedirs(d, exist_ok=True)
    return d


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its configuration and traffic files."""

    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    check: dict  # the limits of the comparison that decides ``correct``
    end_to_end: tuple[str, ...]  # metric names of BENCHMARK.json for this cell
    per_layer: tuple[str, ...]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def load_cell(
    name: str, bench_dir: str = BENCH_DIR, manifest_path: Optional[str] = None
) -> Cell:
    """The cell ``name``: from BENCHMARK.json where it lists the cell, else
    from ``workloads/<name>.json`` alone (a cell that is being built)."""
    manifest_path = manifest_path or os.path.join(
        os.path.dirname(bench_dir), "BENCHMARK.json"
    )
    manifest: dict[str, Any] = (
        _load(manifest_path) if os.path.exists(manifest_path) else {}
    )
    cell_file = os.path.join(bench_dir, "workloads", f"{name}.json")
    if not os.path.exists(cell_file):
        raise SystemExit(f"no such cell: {cell_file} is missing")
    w = _load(cell_file)
    # where the configuration came from: its model kind's files are looked for there first
    config = dict(_load(os.path.join(bench_dir, "configs", f"{w['config']}.json")), bench_dir=bench_dir)
    traffic = _load(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        why=w["why"],
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=config,
        traffic=traffic,
        check=w["check"],
        end_to_end=tuple(
            m["name"] for m in manifest.get("end_to_end", []) if _applies(m, name)
        ),
        per_layer=tuple(
            m["name"] for m in manifest.get("per_layer", []) if _applies(m, name)
        ),
    )


def list_cells(bench_dir: str = BENCH_DIR) -> list[str]:
    d = os.path.join(bench_dir, "workloads")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))
