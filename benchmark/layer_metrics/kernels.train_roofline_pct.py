"""The training step's share of its compute roofline: the FLOPs one step needs
(the benchmark's count, nothing recomputed) over the published bf16 peak, over
the median device time of the ``jit_step`` program in the trace. Compute-bound:
64 TFLOP a step against 11 GB of state read and written."""

NAME = "kernels.train_roofline_pct"
UNIT = "%"
LAYER = "training kernels"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"
MODULE = "jit_step"


def read(run: dict):
    c, tr = run["counters"], run.get("trace")
    if not tr or MODULE not in tr["module_median_s"] or "peak_flops_per_s" not in c:
        return None
    least_s = c["flops_per_step"] / (c["chips"] * c["peak_flops_per_s"])
    return 100.0 * least_s / tr["module_median_s"][MODULE]
