"""Host time of one admission round: the mean over the traced ``serve.admit`` spans that
prefilled of the span's duration less its ``serve.prefill.fetch`` child (the wait for
the device): plan, build, dispatch and commit."""

NAME = "engine.admit_host_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.admit_host_ms(r) if r else None
