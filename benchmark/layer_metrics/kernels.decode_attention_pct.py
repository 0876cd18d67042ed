"""Share of the decode program's device time (``jit__decode``, containers left out) whose
operations lie under the scope ``paged_attention``: gather, scores, values."""

NAME = "kernels.decode_attention_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or run["cell"].kind != "serve":
        return None
    return scopes.share_pct(run, MODULE, (hot.PAGED_ATTENTION,))
