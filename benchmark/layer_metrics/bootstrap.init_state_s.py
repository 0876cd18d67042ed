"""Seconds to make the sharded weights and the optimizer state on the device:
the benchmark's seeded weight build (``lib/models.make_weights``), then the
program's ``optimizer.init`` and ``normalize_state_shardings``. The program's
``init_state`` is not called (its weights come from a fixed key), so a change
there does not move this."""

NAME = "bootstrap.init_state_s"
UNIT = "s"
LAYER = "job bootstrap"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run: dict):
    c = run["counters"]
    return c.get('init_state_s')
