"""Prompt tokens served from the prefix cache over prompt tokens looked up, in the window."""

NAME = "engine.prefix_hit_pct"
UNIT = "%"
LAYER = "serving engine"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"


def read(run: dict):
    c = run["counters"]
    return 100.0 * c['prefix_hit_tokens'] / c['prefix_lookup_tokens'] if c.get('prefix_lookup_tokens') else None
