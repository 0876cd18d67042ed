"""What a chunk of a prompt costs the step that carries it: the median device time of the
``jit__decode_chunk`` program less that of ``jit__decode`` in the same trace, the same
slots stepping in both. The round it replaced held the device for a whole program of its
own; this is what is left of a prompt's cost, and what a chunk attention that does not
gather, or a grouped matmul whose tiles straddle fewer experts, would lower. None unless
the trace holds runs of both programs."""

NAME = "kernels.decode_chunk_cost_ms"
UNIT = "ms"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MIXED, PURE = "jit__decode_chunk", "jit__decode"


def read(run: dict):
    medians = (run.get("trace") or {}).get("module_median_s", {})
    if MIXED not in medians or PURE not in medians:
        return None
    return 1e3 * (medians[MIXED] - medians[PURE])
