"""Share of the decode program's device time (``jit__decode``, containers left out) under
the scope ``eva_pool`` alone: every slot's newest block read out of the pool, the softmax
over its 16 rows, the two weighted sums and one row written to the slot's staging blocks
(or to the trash block where the step did not fill a chunk: the program pools for every
slot, ``kinds/<kind>.py::pool_op_bytes`` counts the sixteenth that must be). None for a
program with no such scope and a configuration without a window."""

NAME = "kernels.decode_eva_pool_pct"
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
    return scopes.share_pct(run, MODULE, (hot.EVA_POOL,))
