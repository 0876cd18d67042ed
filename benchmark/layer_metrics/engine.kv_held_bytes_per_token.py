"""Bytes of K/V pool the slots hold for each token of context, where sliding and full
layers keep pools of their own: the mean over the traced ``serve.decode`` spans of
``kv_blocks_full`` x a full-layer block's bytes + ``kv_blocks_window`` x a sliding-layer
block's (the blocks the slots held as the step was dispatched), over the tokens the
slots held (polled while the trace ran). When the window blocks are released as the
windows move on it is what a token costs on the full layers plus a slot's ring shared
out over its rows (8.2 KB + 3.9 MB / rows at the published widths); when they are not,
every layer's K/V of every token (32.8 KB)."""

NAME = "engine.kv_held_bytes_per_token"
UNIT = "B/token"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans, kinds, scopes

    hot, c = scopes.names(), run["counters"]
    config = run["cell"].config
    kind = kinds.of(config)
    if hot is None or not hasattr(kind, "window_bytes_per_slot") or not c.get("traced_tokens_held_mean"):
        return None
    r = host_spans.of_run(run)
    spans = [s for s in r.named(hot.SERVE_DECODE) if "kv_blocks_full" in s.attrs] if r else []
    if not spans:
        return None
    bs = int(config["deployment"]["block_size"])
    full = bs * kind.kv_bytes_per_token(config)  # a block of every full layer
    window = kind.window_bytes_per_slot(config, bs)  # a block of every sliding layer
    held = sum(int(s.attrs["kv_blocks_full"]) * full + int(s.attrs["kv_blocks_window"]) * window for s in spans)
    return held / len(spans) / c["traced_tokens_held_mean"]
