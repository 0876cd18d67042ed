"""BENCHMARK.json against the files it names and the contract's own rules."""

import json
import os
import re

from benchmark import run as bench_run
from benchmark.lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_files():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    for c in m["configs"]:
        with open(os.path.join(spec.REPO_ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(c["reduced"]) == set(body["reduced"]) and body["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(m["workloads"]) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    m = manifest()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1 and all(0.01 <= e["bound"] <= 0.1 for e in e2e.values())
    for w in m["workloads"]:
        cell = spec.load_cell(w["name"])
        assert (cell.config_name, cell.traffic_name, cell.chips, cell.why) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2 and cell.per_layer
    for metric in m["per_layer"]:
        reader = bench_run.load_reader(metric["name"], spec.BENCH_DIR)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            metric["name"], metric["unit"], metric["layer"], metric["moves"], metric["source"])
        moved = e2e[metric["moves"]]
        for cell in metric["workloads"]:  # each listed cell reports the metric that is moved
            assert "workloads" not in moved or cell in moved["workloads"]
