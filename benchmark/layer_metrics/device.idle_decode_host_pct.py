"""Device idle time, as a share of the traced window, that the engine thread's time inside
``serve.decode`` and outside ``serve.decode.fetch`` accounts for, gap by gap between two
programs: a gap goes to the turn that enqueued the second, found by the runtime's ``run_id``, by what
the thread did from the last result it had in hand to that enqueue: how far the decode host holds
the chip back (``lib/program_runs.py::idle_by_class``, PR 39). With its two siblings it adds up to
``device.idle_pct.serve`` (``idle_split_pct`` there)."""

NAME = "device.idle_decode_host_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    return program_runs.idle_split_pct(run, "decode_host")
