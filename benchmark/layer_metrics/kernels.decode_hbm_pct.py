"""The decode step's share of its memory roofline: the bytes one step must read
(every weight once, the K/V of the tokens the active slots hold, polled while
the trace ran) over the published HBM bandwidth, over the median device time
of the ``jit__decode`` program in the trace. Memory-bound: a step multiplies
each weight by at most 16 rows."""

NAME = "kernels.decode_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import counts

    c, tr = run["counters"], run.get("trace")
    if not tr or MODULE not in tr["module_median_s"] or "peak_hbm_bytes_per_s" not in c:
        return None
    need = counts.decode_step_bytes(
        run["cell"].config, c["traced_active_mean"], c["traced_tokens_held_mean"]
    )
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / tr["module_median_s"][MODULE]
