"""What a prompt waits behind programs already enqueued: the median over the traced rounds
of the time from the runtime's enqueue of the round's ``jit__prefill`` run
(``DoEnqueueProgram``, joined by ``run_id``) to the run's first instant on the device, the
host's instant moved onto the device's clock by the offset the trace bounds
(``lib/program_runs.py``; good to half ``device.clock_slack_us``)."""

NAME = "engine.prefill_queued_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import program_runs

    r = program_runs.sound(run)
    return program_runs.prefill_queued_ms(r) if r else None
