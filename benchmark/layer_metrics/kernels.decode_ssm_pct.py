"""Share of the decode program's device time (``jit__decode``, containers left out) under
the scope ``ssm``: the whole state-space mixer of every layer (its two projections, the
convolution, the state's update and read-out, the gated norm) beside attention, the
feed-forward and the head. None for a program with no such scope and a configuration
without a mixer."""

NAME = "kernels.decode_ssm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or not hasattr(hot, "SSM") or run["cell"].kind != "serve" or not run["cell"].config.get("mamba_n_heads"):
        return None
    return scopes.share_pct(run, MODULE, (hot.SSM,))
