"""The step that carries a chunk of a prompt, against the memory roofline of the decode
step inside it: the bytes a decode step must read (every weight once, the K/V of the
tokens the active slots hold, polled while the trace ran: ``kernels.decode_hbm_pct``'s
count, unchanged) over the published HBM bandwidth, over the median device time of the
``jit__decode_chunk`` program in the trace. What the chunk itself reads (its embedding
rows, the keys below it, the experts only its rows reach) is left out, so this is a floor:
100 less it is what the chunk's positions cost beside the weights' one read, and it reads
under ``kernels.decode_hbm_pct`` by the two programs' ratio. A program without the mixed
step (no ``jit__decode_chunk`` in the trace) gives None."""

NAME = "kernels.decode_chunk_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode_chunk"


def read(run: dict):
    from benchmark.lib import counts

    c, tr = run["counters"], run.get("trace")
    if not tr or MODULE not in tr.get("module_median_s", {}) or "peak_hbm_bytes_per_s" not in c:
        return None
    need = counts.decode_step_bytes(
        run["cell"].config, c["traced_active_mean"], c["traced_tokens_held_mean"]
    )
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / tr["module_median_s"][MODULE]
