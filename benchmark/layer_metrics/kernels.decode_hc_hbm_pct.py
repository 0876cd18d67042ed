"""The residual path's share of its memory roofline in decode: the bytes hyper-connections
must move for the slots that stepped (polled while the trace ran; each token's ``hc_mult x
hidden_size`` stream read once and written once a layer, each sublayer's input and output, its
``phi``: ``kinds/<kind>.py::hc_bytes``), over the published HBM bandwidth, over the device time
a step of ``jit__decode`` spends under the scopes ``hc_pre``, ``hc_sinkhorn``, ``hc_post`` and
``hc_head``. At 128 rows a step it is launches and not bytes that take the time: it reads low."""

NAME = "kernels.decode_hc_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import kinds, scopes

    c, tr, hot = run["counters"], run.get("trace"), scopes.names()
    path = scopes.trace_file(run)
    kind = kinds.of(run["cell"].config)
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(kind, "hc_bytes"):
        return None
    if not hasattr(hot, "HC_PRE"):
        return None
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.HC_PRE, hot.HC_SINKHORN, hot.HC_POST, hot.HC_HEAD)) if ops else 0.0
    if not steps or seconds <= 0.0:
        return None
    need = kind.hc_bytes(run["cell"].config, c["traced_active_mean"])
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
