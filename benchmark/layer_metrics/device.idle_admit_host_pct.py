"""Device idle time, as a share of the traced window, that the engine thread's time inside
``serve.admit`` and outside ``serve.prefill.fetch`` accounts for, gap by gap: planning,
building, dispatching or committing an admission round (``lib/host_spans.py``)."""

NAME = "device.idle_admit_host_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.idle_pct(r, "admit_host") if r else None
