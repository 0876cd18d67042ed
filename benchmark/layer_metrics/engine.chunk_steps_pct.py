"""How often a prompt rides a decode step: the share of the traced ``serve.decode`` spans
whose ``chunk_tokens`` is over 0 (the step the turn enqueued carried a chunk of a prompt
through the layer stack beside the slots' rows; 0 on a pure decode step). Lower is the
same prompts in fewer, fuller steps. A program from before the attribute (an admission
round with a program of its own) gives None."""

NAME = "engine.chunk_steps_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans, scopes

    hot = scopes.names()
    r = host_spans.of_run(run) if hot is not None else None
    turns = [s for s in r.named(hot.SERVE_DECODE) if "chunk_tokens" in s.attrs] if r else []
    return 100.0 * sum(int(s.attrs["chunk_tokens"]) > 0 for s in turns) / len(turns) if turns else None
