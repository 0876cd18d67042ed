"""The whole window's share of the chip's peak in a serving cell: the forward FLOPs the
window needed over ``window_s`` x chips x the published peak (``lib/serve_cell.py``). Every
token put out counts the kind's forward at the mean context the polls saw, every prompt
token prefilled the forward less the head (``forward_flops`` there, over
``kinds/<kind>.py::forward_flops_per_token``). It stands behind ``serve_tokens_per_s`` where
a program's roofline falls silent; a decode step is bound by bytes, so it reads low."""

NAME = "model.serve_mfu_pct"
UNIT = "%"
LAYER = "model"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(run: dict):
    return run["counters"].get("serve_mfu_pct")
