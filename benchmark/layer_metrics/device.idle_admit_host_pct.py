"""Device idle time, as a share of the traced window, that the engine thread's time inside
``serve.admit`` and outside ``serve.prefill.fetch`` accounts for, gap by gap: planning,
building, dispatching or committing an admission round (``lib/program_runs.py::idle_by_class``)."""

NAME = "device.idle_admit_host_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    return program_runs.idle_split_pct(run, "admit_host")
