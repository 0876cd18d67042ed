"""The trace reduction, on made-up planes and on a trace recorded on the chip
(8 steps of mistral7b-train-4k, one v5e, PR 23)."""

import os

import pytest

from benchmark.lib import trace

from .conftest import DATA


def test_union_gaps_and_containers():
    plane = {
        "name": "/device:TPU:0",
        "modules": [("jit_a(1)", 0.0, 1.0), ("jit_b(2)", 1.5, 2.0), ("jit_a(1)", 2.0, 3.0)],
        "ops": [("%while.1 = s32[] while(...)", 0.0, 1.0), ("%fusion.1 = f32[] fusion()", 0.0, 0.6),
                ("%fusion.2 = f32[] fusion()", 0.5, 1.0), ("%fusion.1 = f32[] fusion()", 1.5, 3.0)],
    }
    r = trace.reduce_planes([plane], chips=1)
    assert r["busy_s"] == pytest.approx(2.5) and r["window_s"] == pytest.approx(3.0)
    assert r["module_median_s"] == {"jit_a": 1.0, "jit_b": 0.5}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.1)]
    assert all(name != "while.1" for name, _ in r["device_ops"])
    assert r["idle_gaps"] == [["jit_a->jit_b", pytest.approx(0.5)]]


def test_nothing_on_the_device_reads_as_nothing():
    assert trace.reduce_planes([{"name": "/device:TPU:0", "modules": [], "ops": []}], 1) is None


def test_recorded_trace():
    planes = trace.read_planes(os.path.join(DATA, "train_8steps.xplane.pb"))
    r = trace.reduce_planes(planes, chips=1)
    assert list(r["module_runs"]) == ["jit_step"] and len(r["module_runs"]["jit_step"]) == 8
    assert 0.5 < r["module_median_s"]["jit_step"] < 0.7
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 1 - r["busy_s"] / r["window_s"] < 0.01
    assert len(r["device_ops"]) == 10
