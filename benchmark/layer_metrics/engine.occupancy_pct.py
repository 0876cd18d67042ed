"""Mean share of slots in use, polled every 50 ms."""

NAME = "engine.occupancy_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run: dict):
    c = run["counters"]
    return 100.0 * c['occupancy_mean'] if 'occupancy_mean' in c else None
