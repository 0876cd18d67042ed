"""The decode attention's share of its memory roofline where sliding and full layers mix:
the K/V rows a step must read (every held token on the full layers, the last
``sliding_window`` rows of every slot on the sliding ones, polled while the trace ran;
``kinds/<kind>.py::decode_attention_bytes``), over the published HBM bandwidth, over the
device time a step of ``jit__decode`` spends under the scope ``paged_attention`` (on the
chip the Pallas call ``paged_attention_decode``, all layers of both kinds together). A
kernel that read a sliding layer's whole context would read low here."""

NAME = "kernels.decode_swa_hbm_pct"
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
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(kind, "decode_attention_bytes"):
        return None
    if not hasattr(hot, "ATTN_WINDOW"):
        return None
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.PAGED_ATTENTION,)) if ops else 0.0
    if not steps or seconds <= 0.0:
        return None
    need = kind.decode_attention_bytes(run["cell"].config, c["traced_active_mean"], c["traced_tokens_held_mean"])
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
