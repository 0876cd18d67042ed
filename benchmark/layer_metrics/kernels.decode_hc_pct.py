"""Share of the decode program's device time (``jit__decode``, containers left out) under
the residual path's scopes ``hc_pre``, ``hc_sinkhorn``, ``hc_post`` and ``hc_head``: what
mixing four streams round every sublayer (a norm over 14,336 values, a 24-wide projection,
40 normalisations of a 4 x 4 matrix, a read-in and a write-back) costs beside the weights.
None for a program with no such scopes and a configuration with one stream."""

NAME = "kernels.decode_hc_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or not hasattr(hot, "HC_PRE") or run["cell"].kind != "serve" or not run["cell"].config.get("hc_mult"):
        return None
    return scopes.share_pct(run, MODULE, (hot.HC_PRE, hot.HC_SINKHORN, hot.HC_POST, hot.HC_HEAD))
