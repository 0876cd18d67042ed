"""Share of the decode program's device time (``jit__decode``) under the scope
``moe_experts``: the expert matmuls that read the weights, which is what a step of a
sparse model should be spending its time on."""

NAME = "kernels.decode_experts_pct"
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
    return scopes.share_pct(run, MODULE, (hot.MOE_EXPERTS,))
