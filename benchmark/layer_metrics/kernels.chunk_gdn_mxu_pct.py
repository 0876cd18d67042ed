"""The chunked delta rule's share of the MXU's peak: the recurrence's own work for the real
prompt tokens the traced steps carried (each traced ``serve.decode`` span's ``chunk_tokens``;
``kinds/<kind>.py::gdn_chunk_flops``: three multiply-adds an element of the state a token a linear
layer, to read ``S^T k``, write ``k u^T`` and read ``S^T q``, whatever computes it), over the published
bf16 peak, over the device time of ``jit__decode_chunk`` under the scope ``gdn_chunk``. The chunked
form pays more than that count (the scores between a sub-chunk's positions, the triangular solve,
float32 products in several passes, the padding behind a short chunk, the state's read and write),
so it reads low and cannot pass 100. None for a program without the scope, a kind without the
count, or a trace in which no step carried a chunk."""

NAME = "kernels.chunk_gdn_mxu_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode_chunk"


def read(run: dict):
    from benchmark.lib import host_spans, kinds, peaks, scopes

    hot, path = scopes.names(), scopes.trace_file(run)
    config = run["cell"].config
    kind = kinds.of(config)
    if hot is None or path is None or not hasattr(hot, "GDN_CHUNK") or not hasattr(kind, "gdn_chunk_flops"):
        return None
    r = host_spans.of_run(run)
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    if r is None or not ops:
        return None
    tokens = sum(int(s.attrs.get("chunk_tokens", 0)) for s in r.named(hot.SERVE_DECODE))
    seconds = scopes.under(ops, (hot.GDN_CHUNK,))
    if tokens <= 0 or seconds <= 0.0:
        return None
    return 100.0 * kind.gdn_chunk_flops(config, tokens) / peaks.peak(run["device"]["kind"], "bf16_flops_per_s") / seconds
