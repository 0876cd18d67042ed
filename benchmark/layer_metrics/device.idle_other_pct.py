"""Device idle time, as a share of the traced window, that neither sibling claims: a result's
way back and a launch's way out, ``serve.idle``, time under no span, bubbles inside a program."""

NAME = "device.idle_other_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    return program_runs.idle_split_pct(run, "other")
