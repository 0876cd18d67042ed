"""The held experts' share of their memory roofline in decode, where a chip holds a
share of each layer's experts: the bytes of the held experts that the active slots
(polled while the trace ran) are expected to reach, ``held (1 - (1 - k/E)^slots)`` a
sparse layer (``kinds/<kind>.py::decode_held_expert_bytes``), which a step must read
once, over the published HBM bandwidth, over the device time a step of ``jit__decode``
spends under the scope ``moe_experts`` (the grouped matmuls over the held experts)."""

NAME = "kernels.decode_experts_held_hbm_pct"
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
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(kind, "decode_held_expert_bytes"):
        return None
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.MOE_EXPERTS,)) if ops else 0.0
    if not steps or seconds <= 0.0:
        return None
    need = kind.decode_held_expert_bytes(run["cell"].config, c["traced_active_mean"])
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
