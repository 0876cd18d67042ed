"""Device idle time, as a share of the traced window, that neither sibling claims: a result's
way back and a launch's way out, ``serve.idle``, time under no span, bubbles inside a program."""

NAME = "device.idle_other_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans

    r = host_spans.of_run(run)
    return host_spans.idle_pct(r, "other") if r else None
