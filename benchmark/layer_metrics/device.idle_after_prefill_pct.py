"""Share of the traced window the device idles behind an admission round's program: the
idle gaps that follow a ``jit__prefill`` run on the ``XLA Modules`` line, summed
(``lib/trace.py``'s ``idle_gaps``, the entries named ``jit__prefill->...``), over the
window. The device's clock alone: no host event is read. The engine fetches a round's first
tokens before it commits and enqueues the next step, so nothing is queued behind a round:
this is the exposed turn, which ``lib/program_runs.py`` divides into its parts."""

NAME = "device.idle_after_prefill_pct"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
AFTER = "jit__prefill->"


def read(run: dict):
    tr = run.get("trace")
    if not tr or run["cell"].kind != "serve":
        return None
    gaps = [seconds for name, seconds in tr["idle_gaps"] if name.startswith(AFTER)]
    return 100.0 * sum(gaps) / tr["window_s"] if gaps else None
