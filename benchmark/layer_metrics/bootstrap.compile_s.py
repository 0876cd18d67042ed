"""Seconds to lower and compile the training step (a cache load on a warm run)."""

NAME = "bootstrap.compile_s"
UNIT = "s"
LAYER = "job bootstrap"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run: dict):
    c = run["counters"]
    return c.get('compile_s')
