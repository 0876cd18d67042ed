"""Host time of one decode step: the median over the traced ``serve.decode`` spans of
the span's duration less its ``serve.decode.fetch`` child (the wait for the device).
What is left is prepare, dispatch and commit: the engine loop's own cost a step."""

NAME = "engine.host_ms_per_step"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.host_ms_per_step(r) if r else None
