"""Share of the window the harness's loop waited for the program's
prefetcher (``device_prefetch`` over ``TokenDataset``): its own wait counter."""

NAME = "trainer.data_wait_pct"
UNIT = "%"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(run: dict):
    c = run["counters"]
    return 100.0 * c['data_wait_s'] / c['window_s'] if 'data_wait_s' in c else None
