"""The decode attention's share of its memory roofline where a slot's rows are not its tokens
(EVA attention: a window's exact rows behind the pooled rows of every window before it, one
table, one kernel). The bytes a step's kernel must read are the rows in front of every
stepping slot's cache coordinate, ``(W / C) (t // W) + t % W + 1`` of them, K and V of every
cache head in every layer: the engine counts them exactly as it dispatches a step
(``cache_rows_read`` on the ``serve.decode`` span; the mean over the traced turns that
dispatched a step without a chunk, which is what ``jit__decode`` runs), times
``kinds/<kind>.py::row_bytes``. Over the published HBM bandwidth, over the device time a
step of ``jit__decode`` spends under the scope ``paged_attention`` (on the chip the Pallas
call ``paged_attention_decode``, at one query head a cache head, 32 cache heads a position,
blocks of 128 KiB of K). The count is of rows, not of the whole blocks and groups the kernel
copies, so this cannot pass 100. None for a program without the scope ``eva_pool`` or a
span without the count."""

NAME = "kernels.decode_eva_hbm_pct"
UNIT = "%"
LAYER = "serving kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__decode"


def read(run: dict):
    from benchmark.lib import host_spans, kinds, scopes

    c, tr, hot = run["counters"], run.get("trace"), scopes.names()
    path = scopes.trace_file(run)
    kind = kinds.of(run["cell"].config)
    if hot is None or path is None or "peak_hbm_bytes_per_s" not in c or not hasattr(kind, "row_bytes"):
        return None
    if not hasattr(hot, "EVA_POOL"):
        return None
    r = host_spans.of_run(run)
    rows = [int(s.attrs["cache_rows_read"]) for s in r.named(hot.SERVE_DECODE)
            if int(s.attrs.get("cache_rows_read", 0)) and not int(s.attrs.get("chunk_tokens", 0))] if r else []  # fmt: skip
    ops = scopes.program_ops(scopes.read_planes(path), MODULE)
    steps = len(tr["module_runs"].get(MODULE, ()))
    seconds = scopes.under(ops, (hot.PAGED_ATTENTION,)) if ops else 0.0
    if not rows or not steps or seconds <= 0.0:
        return None
    need = sum(rows) / len(rows) * kind.row_bytes(run["cell"].config)
    return 100.0 * need / c["peak_hbm_bytes_per_s"] / (seconds / steps)
