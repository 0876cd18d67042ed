"""The result on its way: the median over the traced ``serve.decode.fetch`` spans of the
part of the span after its run's last instant on the device (the runtime's callback, the
copy back, the thread's wake-up); the part before it is the device still busy. The run is
the latest decode run the runtime had completed (``CompleteCallbacks``, by ``run_id``) when
the span ended; its last instant is moved onto the host's clock by the offset the trace
bounds (``lib/program_runs.py``; good to half ``device.clock_slack_us``)."""

NAME = "engine.fetch_return_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs, scopes

    r = program_runs.sound(run)
    return program_runs.fetch_return_ms(r, scopes.names().SERVE_DECODE_FETCH) if r else None
