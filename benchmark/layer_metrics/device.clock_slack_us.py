"""Width of the interval the trace itself leaves for the offset between the device's clock
and the host's: a program cannot start before the host began to enqueue it
(``DoEnqueueProgram``) nor the host learn of its end before it ended
(``CompleteCallbacks``), both joined to the device's run by ``run_id``
(``lib/program_runs.py``). Every reading that crosses the clocks is good to half of it."""

NAME = "device.clock_slack_us"
UNIT = "us"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    r = program_runs.of_run(run)
    return program_runs.clock_slack_us(r) if r else None
