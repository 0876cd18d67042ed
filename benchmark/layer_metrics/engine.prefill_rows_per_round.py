"""Requests an admission round prefills: the mean of ``rows`` over the traced
``serve.admit`` spans that prefilled. A full backlog frees one slot at a time, so a
round that waits for no second row reads 1."""

NAME = "engine.prefill_rows_per_round"
UNIT = "rows"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    rows = [int(a["rows"]) for a in program_runs.round_attrs(run, "rows")]
    return sum(rows) / len(rows) if rows else None
