"""Share of the traced window (first to last device operation) that the engine thread
spent inside ``serve.admit``: no running request gets a token then."""

NAME = "engine.prefill_stall_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.prefill_stall_pct(r) if r else None
