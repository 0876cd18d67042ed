"""Share of the decode program's device time (``jit__decode``, containers left out) that the
cache of EVA attention takes: the operations under the scopes ``paged_attention`` (the
kernel over a window's rows and the pooled rows behind it), ``append_kv`` (the step's rows
written at their cache coordinates) and ``eva_pool`` (the blocks a step filled read, pooled
and one row each written), beside the projections, the feed-forward and the head. None for
a program with no scope ``eva_pool`` and a configuration without a window."""

NAME = "kernels.decode_eva_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or not hasattr(hot, "EVA_POOL") or run["cell"].kind != "serve" or not run["cell"].config.get("window_size"):
        return None
    return scopes.share_pct(run, MODULE, (hot.PAGED_ATTENTION, hot.APPEND_KV, hot.EVA_POOL))
