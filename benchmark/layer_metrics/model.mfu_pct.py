"""Tokens/s times the benchmark's FLOPs per token over chips times the published peak."""

NAME = "model.mfu_pct"
UNIT = "%"
LAYER = "model"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run: dict):
    c = run["counters"]
    return 100.0 * c['tokens_per_s'] * c['flops_per_token'] / (c['chips'] * c['peak_flops_per_s']) if 'peak_flops_per_s' in c and 'flops_per_token' in c else None
