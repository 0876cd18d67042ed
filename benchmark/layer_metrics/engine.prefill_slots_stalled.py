"""Slots an admission round stalls: the mean of ``slots_stalled`` over the traced
``serve.admit`` spans that prefilled. They hold a request that is decoding as the round's
program is dispatched and get no token while it runs; times ``engine.prefill_device_pct``
that is the decode work a round displaces, which chunked prefill is to give back."""

NAME = "engine.prefill_slots_stalled"
UNIT = "slots"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    stalled = [int(a["slots_stalled"]) for a in program_runs.round_attrs(run, "slots_stalled")]
    return sum(stalled) / len(stalled) if stalled else None
