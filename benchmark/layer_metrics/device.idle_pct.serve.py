"""Share of the traced window in which no operation ran on the device:
1 - union of the device plane's operation intervals over the traced window."""

NAME = "device.idle_pct.serve"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
KIND = "serve"


def read(run: dict):
    tr = run.get("trace")
    if not tr or run["cell"].kind != KIND:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
