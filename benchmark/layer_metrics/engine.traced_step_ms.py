"""The decode step's period while the profiler runs: the median start-to-start time of
two ``serve.decode`` spans with nothing between them on the engine thread. Beside
``engine.step_ms`` (the whole window, mostly untraced) it is what the profiler does
to the loop."""

NAME = "engine.traced_step_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.traced_step_ms(r) if r else None
