"""Device idle time, as a share of the traced window, that the engine thread's time inside
``serve.decode`` and outside ``serve.decode.fetch`` accounts for, gap by gap between two
programs (``lib/host_spans.py``). With its two siblings it adds up to ``device.idle_pct.serve``."""

NAME = "device.idle_decode_host_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.idle_pct(r, "decode_host") if r else None
