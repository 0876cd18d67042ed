"""Share of the decode program's device time (``jit__decode``) under the scopes
``moe_router``, ``moe_dispatch`` and ``moe_combine``: what scoring 64 experts, sorting
the (token, choice) rows by expert and bringing them back costs beside the experts."""

NAME = "kernels.decode_moe_routing_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or run["cell"].kind != "serve" or not run["cell"].config.get("n_routed_experts"):
        return None
    return scopes.share_pct(run, MODULE, (hot.MOE_ROUTER, hot.MOE_DISPATCH, hot.MOE_COMBINE))
