"""Share of the traced window in which no operation ran on the device:
1 - union of the device plane's operation intervals over the traced window.
In a training cell the dispatch policy is the harness's (two steps in flight),
so this is the idle share of the program's step under that policy."""

NAME = "device.idle_pct.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"
KIND = "train"


def read(run: dict):
    tr = run.get("trace")
    if not tr or run["cell"].kind != KIND:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
