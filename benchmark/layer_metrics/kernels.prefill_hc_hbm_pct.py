"""The residual path's share of its memory roofline in prefill: the bytes hyper-connections
must move for the tokens the traced admission rounds prefilled (each ``serve.admit`` span's
``tokens``, the real suffix tokens of its rows; ``kinds/<kind>.py::hc_bytes`` a round), over
the published HBM bandwidth, over the device time of ``jit__prefill`` under the scopes
``hc_pre``, ``hc_sinkhorn``, ``hc_post`` and ``hc_head``. Here the stream is bandwidth: four
times the hidden size a token a pass. Padding a bucket to its width lowers it."""

NAME = "kernels.prefill_hc_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__prefill"


def read(run: dict):
    from benchmark.lib import host_spans, kinds, scopes

    c, hot, path = run["counters"], scopes.names(), scopes.trace_file(run)
    kind = kinds.of(run["cell"].config)
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(kind, "hc_bytes"):
        return None
    if not hasattr(hot, "HC_PRE"):
        return None
    r = host_spans.of_run(run)
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    if r is None or not ops:
        return None
    rounds = [int(s.attrs.get("tokens", 0)) for s in r.named(hot.SERVE_ADMIT)]
    seconds = scopes.under(ops, (hot.HC_PRE, hot.HC_SINKHORN, hot.HC_POST, hot.HC_HEAD))
    need = sum(kind.hc_bytes(run["cell"].config, tokens) for tokens in rounds if tokens > 0)
    if need <= 0.0 or seconds <= 0.0:
        return None
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / seconds
