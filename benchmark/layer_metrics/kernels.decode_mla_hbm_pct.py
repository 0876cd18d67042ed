"""The latent decode attention's share of its memory roofline: the bytes of the latent
rows the active slots hold (polled while the trace ran; 1,152 B a token a layer as
``kinds/mla_moe.py::kv_bytes_per_token`` counts them), which a step must read once,
over the published HBM bandwidth, over the device time a step of ``jit__decode`` spends
under the scope ``paged_attention`` (on the chip the Pallas call ``paged_mla_decode``,
all layers of a step together)."""

NAME = "kernels.decode_mla_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import counts, scopes

    c, tr, hot = run["counters"], run.get("trace"), scopes.names()
    path = scopes.trace_file(run)
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not run["cell"].config.get("kv_lora_rank"):
        return None
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.PAGED_ATTENTION,)) if ops else 0.0
    if not steps or seconds <= 0.0:
        return None
    need = c["traced_tokens_held_mean"] * counts.kv_bytes_per_token(run["cell"].config)
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
