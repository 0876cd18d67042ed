"""How full the chunks are: the sum of ``chunk_tokens`` over the sum of ``chunk_width`` of
the traced ``serve.decode`` spans whose step carried a chunk of a prompt. The program
computes ``chunk_width`` positions whatever the chunk holds; a prompt's last chunk holds
what is left of it, so short prompts and short tails lower this, and a chunk packed from
two requests would raise it. A program from before the attributes gives None."""

NAME = "engine.chunk_fill_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans, scopes

    hot = scopes.names()
    r = host_spans.of_run(run) if hot is not None else None
    chunks = [s.attrs for s in r.named(hot.SERVE_DECODE) if int(s.attrs.get("chunk_tokens", 0)) > 0] if r else []
    width = sum(int(a["chunk_width"]) for a in chunks)
    return 100.0 * sum(int(a["chunk_tokens"]) for a in chunks) / width if width else None
