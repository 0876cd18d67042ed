"""Window seconds over decode steps in the window, prefills included in the time."""

NAME = "engine.step_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run: dict):
    c = run["counters"]
    return c['window_s'] / c['steps'] * 1e3 if c.get('steps') and 'tokens' in c else None
