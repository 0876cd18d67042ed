"""The prefill attention's share of the MXU's peak: the forward FLOPs of scores and
weighted values that the traced admission rounds needed (each ``serve.admit`` span's
``rows``, ``tokens`` and ``cached_tokens``, a round's rows taken at their mean lengths,
which counts no more than the rows as they were: causal on full layers, at most
``sliding_window`` keys a query on sliding ones, ``kinds/<kind>.py::
prefill_attention_flops``), over the published bf16 peak, over the device time of
``jit__prefill`` under the scope ``paged_attention`` (the walk over key blocks). Padding a
bucket to its width and scoring key blocks that the mask then discards lower it."""

NAME = "kernels.prefill_attention_mxu_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__prefill"


def read(run: dict):
    from benchmark.lib import host_spans, kinds, peaks, scopes

    hot, path = scopes.names(), scopes.trace_file(run)
    kind = kinds.of(run["cell"].config)
    if hot is None or path is None or not hasattr(kind, "prefill_attention_flops"):
        return None
    if not hasattr(hot, "ATTN_WINDOW") or run["device"].get("platform") != "tpu":
        return None
    r = host_spans.of_run(run)
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    if r is None or not ops:
        return None
    flops = 0.0
    for s in r.named(hot.SERVE_ADMIT):
        rows, tokens = int(s.attrs.get("rows", 0)), int(s.attrs.get("tokens", 0))
        if rows and tokens:
            cached = int(s.attrs.get("cached_tokens", 0))
            flops += rows * kind.prefill_attention_flops(run["cell"].config, cached / rows, tokens / rows)
    seconds = scopes.under(ops, (hot.PAGED_ATTENTION,))
    if flops <= 0.0 or seconds <= 0.0:
        return None
    return 100.0 * flops / peaks.peak(run["device"]["kind"], "bf16_flops_per_s") / seconds
