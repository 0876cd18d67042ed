"""The prefill expert matmuls' share of the MXU's peak: the forward FLOPs the expert
layers need for the tokens the traced admission rounds prefilled (each ``serve.admit``
span's ``tokens``, the real suffix tokens of its rows; per token the 6 chosen experts
and the shared one, ``kinds/mla_moe.py::prefill_expert_flops_per_token``), over the
published bf16 peak, over the device time of ``jit__prefill`` under the scopes
``moe_experts`` and ``moe_shared``. Padding a bucket to its width, a row tile to its
size or a group to a tile lowers it."""

NAME = "kernels.prefill_experts_mxu_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__prefill"


def read(run: dict):
    from benchmark.lib import host_spans, kinds, peaks, scopes

    hot, path = scopes.names(), scopes.trace_file(run)
    kind = kinds.of(run["cell"].config)
    if hot is None or path is None or not hasattr(kind, "prefill_expert_flops_per_token"):
        return None
    if not hasattr(hot, "MOE_SHARED") or run["device"].get("platform") != "tpu":
        return None
    r = host_spans.of_run(run)
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    if r is None or not ops:
        return None
    tokens = sum(int(s.attrs.get("tokens", 0)) for s in r.named(hot.SERVE_ADMIT))
    seconds = scopes.under(ops, (hot.MOE_EXPERTS, hot.MOE_SHARED))
    if tokens <= 0 or seconds <= 0.0:
        return None
    flops = tokens * kind.prefill_expert_flops_per_token(run["cell"].config)
    return 100.0 * flops / peaks.peak(run["device"]["kind"], "bf16_flops_per_s") / seconds
