"""Rows of paged cache the slots hold for each token of context, where a slot's rows are
not its tokens: the mean over the traced ``serve.decode`` spans of ``cache_rows_held`` (a
window's rows, the pooled rows of the windows behind it, the rows staged of the current
window) over ``cache_tokens_held`` (the tokens those slots hold), both counted by the engine
as the step is dispatched. 1.0 would be a row a token, what every other kind holds; a
context of 6,000 bytes under a window of 2,048 and chunks of 16 holds ~0.3. A tripwire on
the windows being given back: a window kept past its end reads higher. None for an engine
whose spans carry no such count."""

NAME = "engine.cache_rows_per_token"
UNIT = "rows/token"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans, scopes

    hot = scopes.names()
    r = host_spans.of_run(run) if hot is not None else None
    spans = [s for s in r.named(hot.SERVE_DECODE) if int(s.attrs.get("cache_tokens_held", 0))] if r else []
    if not spans:
        return None
    return sum(int(s.attrs["cache_rows_held"]) / int(s.attrs["cache_tokens_held"]) for s in spans) / len(spans)
