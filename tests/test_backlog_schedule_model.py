"""``scripts/backlog_schedule_model.py``: the engine's loop over a backlog cell's plan, with times given as arithmetic."""

from __future__ import annotations

import importlib.util
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts", "backlog_schedule_model.py")
_SPEC = importlib.util.spec_from_file_location("backlog_schedule_model", _PATH)
model = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(model)

CELL = "kimi-vl-a3b-serve-backlog"


def test_a_seed_gives_one_window_and_another_seed_another():
    a, b = model.window(CELL, 2147483693), model.window(CELL, 2147483693)
    assert a == b
    assert model.window(CELL, 1000000007) != a


def test_the_window_counts_what_full_slots_can_give():
    w = model.window(CELL, 2147483693)
    # 64 slots a step and one first token a request admitted; a round admits one or two
    assert w["tokens"] <= 64 * w["steps"] + 2 * w["rounds"]
    assert w["tokens"] > 63 * w["steps"]  # the backlog keeps the slots full
    assert w["rounds"] <= w["finished"] + 64 and w["finished"] > 100
    assert w["serve_tokens_per_s"] == w["tokens"] / 45.0


@pytest.mark.parametrize("dearer", [
    pytest.param(dict(round_ms=lambda rows, width: 60.0 + 0.022 * rows * width), id="a-round-30-ms-dearer"),
    pytest.param(dict(step_ms=lambda held: 40.0), id="a-step-of-40-ms"),
])  # fmt: skip
def test_dearer_rounds_or_steps_leave_fewer_steps_in_the_window(dearer):
    assert model.window(CELL, 5, **dearer)["steps"] < model.window(CELL, 5)["steps"]


def test_spread_is_the_quartile_distance_over_the_median():
    assert model.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0]) == pytest.approx(4.0 / 4.0)
