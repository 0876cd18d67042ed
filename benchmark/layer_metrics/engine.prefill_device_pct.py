"""Share of the traced window in which the device ran an admission round's program: the
sum of the ``jit__prefill`` runs' device time (``XLA Modules``) over the window, on the
device's clock alone. No running request gets a token then. Beside it,
``engine.prefill_stall_pct`` is the engine thread's time inside ``serve.admit``, which
since PR 30 also holds the rest of the decode program a round is enqueued behind."""

NAME = "engine.prefill_device_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MODULE = "jit__prefill"


def read(run: dict):
    tr = run.get("trace")
    if not tr or run["cell"].kind != "serve" or not tr["module_runs"].get(MODULE):
        return None
    return 100.0 * sum(tr["module_runs"][MODULE]) / tr["window_s"]
