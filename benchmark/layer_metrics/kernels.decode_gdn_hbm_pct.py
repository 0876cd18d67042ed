"""The Gated DeltaNet state's share of its memory roofline in decode: the bytes a decode step
must move of the slots' state (each active slot's ``S [H, D, D]`` float32 and convolution tail
read once and written once a linear layer: ``kinds/<kind>.py::decode_state_bytes`` for the slots
that stepped, polled while the trace ran), over the published HBM bandwidth, over the device
time a step of ``jit__decode`` spends under the scope ``gdn_step`` (the rows' state read out of
the store, decayed, corrected by the delta rule, read out and written back: the Pallas kernel
``gdn_step`` and the tails' slices beside it). The count is the least any implementation moves,
so this cannot pass 100; what the compiler copies beside it lowers it. None for a program
without the scope or a kind without linear layers."""

NAME = "kernels.decode_gdn_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import kinds, scopes

    c, tr, hot = run["counters"], run.get("trace"), scopes.names()
    path = scopes.trace_file(run)
    config = run["cell"].config
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(hot, "GDN_STEP"):
        return None
    if not config.get("linear_num_value_heads") or not hasattr(kinds.of(config), "decode_state_bytes"):
        return None
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.GDN_STEP,)) if ops else 0.0
    if not steps or seconds <= 0.0:
        return None
    need = kinds.of(config).decode_state_bytes(config, c["traced_active_mean"])
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
