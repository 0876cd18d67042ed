"""Share of the decode program's device time (``jit__decode``, containers left out) under the
scope ``gdn``: the whole Gated DeltaNet mixer of every linear layer (its projections, the
convolution, the state's update and read-out, the gated norm) beside attention, the experts
and the head. None for a program with no such scope and a configuration without linear layers."""

NAME = "kernels.decode_gdn_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or not hasattr(hot, "GDN") or run["cell"].kind != "serve" or not run["cell"].config.get("linear_num_value_heads"):
        return None
    return scopes.share_pct(run, MODULE, (hot.GDN,))
