"""``train_call.py`` drives the program's own ``train()`` on a cell's job."""

from benchmark import train_call

from .conftest import FIXTURES


def test_train_call_reports_what_train_reaches():
    rec = train_call.time_train("tiny-train", steps=7, bench_dir=FIXTURES, allow_cpu=True)
    assert rec["tokens_per_sec_per_chip"] > 0 and rec["step_time_s"] > 0
    assert rec["device"]["platform"] == "cpu" and "init_state" in rec["launch_breakdown"]
