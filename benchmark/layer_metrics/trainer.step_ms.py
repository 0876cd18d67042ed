"""Window seconds over steps in the window, host clock. The loop is the
harness's own around the program's compiled step and prefetcher, two steps in
flight; ``train()``'s loop (a fence at every step, logging, checkpoints) is not
in it and a change there does not move this (PERF.md sections 3 and 7)."""

NAME = "trainer.step_ms"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run: dict):
    c = run["counters"]
    return c['step_s'] * 1e3 if 'step_s' in c else None
