"""The recurrent state's share of its memory roofline in decode: the bytes a decode step
must move of the slots' state (each active slot's ``S [H, P, N]`` float32 and convolution
tail read once and written once a layer: ``kinds/<kind>.py::decode_state_bytes`` for the slots
that stepped, polled while the trace ran), over the published HBM bandwidth, over the device
time a step of ``jit__decode`` spends under the scope ``ssm_step`` (the rows' state read out
of the store, moved on one position, read out and written back). The count is the least any
implementation moves, so this cannot pass 100; what the compiler copies beside it (a gathered
set of rows, a scatter's operand) lowers it. None for a program without the scope or a kind
without recurrent state."""

NAME = "kernels.decode_ssm_hbm_pct"
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
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(kind, "decode_state_bytes"):
        return None
    if not hasattr(hot, "SSM_STEP"):
        return None
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.SSM_STEP,)) if ops else 0.0
    if not steps or seconds <= 0.0:
        return None
    need = kind.decode_state_bytes(run["cell"].config, c["traced_active_mean"])
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
