"""Share of the prefill programs' positions that hold no token: 100 x (1 - sum of
``tokens`` / sum of ``rows_padded`` x ``width``) over the traced ``serve.admit`` spans
that prefilled. A suffix is padded to a power-of-two width and a round's rows to a power
of two; the program computes every position."""

NAME = "engine.prefill_padding_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    rounds = program_runs.round_attrs(run, "rows_padded")
    padded = sum(int(a["rows_padded"]) * int(a["width"]) for a in rounds)
    return 100.0 * (1.0 - sum(int(a["tokens"]) for a in rounds) / padded) if padded else None
