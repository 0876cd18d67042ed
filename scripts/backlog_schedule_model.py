"""The engine's loop on a backlog cell's schedule, on paper: which part of a cell's spread between seeds is the schedule's.

    python3 scripts/backlog_schedule_model.py kimi-vl-a3b-serve-backlog [seed ...]

A seed decides which of a backlog's queued requests the window serves, so how many finish inside it
(each finish is a prefill round in which every slot waits) and how wide their prompts are. This walks
``ServeEngine``'s loop over the plan ``benchmark/lib/traffic.py`` makes from the seed (one admission
round of the queue head's bucket, at most ``max_prefill_batch`` rows and the free slots; then one decode
step), with a time for a round and for a step given as two lines of arithmetic, and counts what the
harness counts in its window. No program runs; the times are the caller's, read from a traced chip run.
With a decode step of 32 + 4 x rows held / 148 k ms and a round of 30 + 0.022 x rows x width ms
(``kimi-vl-a3b-serve-backlog``; my chip run, PR 27) it gave six seeds' steps as 957, 969, 942, 980, 975, 962
where the chip counted 956, 974, 946, 986, 982, 976. With seeds it prints each seed's window; without, the
spread (quartile distance over the median) of ``serve_tokens_per_s`` over 200 seeds.

**The loop it walks is the engine's until PR 40** (an admission round with a program of its own, every
slot waiting through it). Since then a prompt rides the decode steps in chunks and no slot waits; the
model still says which requests a seed's window serves, and its round times no longer apply.
"""

from __future__ import annotations

import os
import statistics
import sys
from typing import Callable

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def window(
    cell_name: str,
    seed: int,
    step_ms: Callable[[int], float] = lambda held: 32.0 + 4.0 * held / 148e3,
    round_ms: Callable[[int, int], float] = lambda rows, width: 30.0 + 0.022 * rows * width,
    seconds: float = 45.0,
) -> dict:
    """One window of the cell under ``seed``: steps, tokens, requests finished, prefill rounds and their seconds."""
    from benchmark.lib import spec, traffic

    cell = spec.load_cell(cell_name)
    mix, dep = cell.traffic, cell.config["deployment"]
    slots, most, block = int(dep["max_slots"]), int(dep["max_prefill_batch"]), int(dep["block_size"])
    plan = traffic.build_schedule(mix, seed, seconds, vocab=2)  # lengths and shared heads; the tokens do not matter
    shared = int(mix["prompt"].get("shared_prefix_tokens", 0)) // block * block
    head = [tuple(r.prompt[:shared]) for r in plan]
    queue, cached, active = list(range(len(plan))), set(), []  # active: [request, generated, prompt length]
    t, t_open = 0.0, float(mix["arrivals"]["ramp_s"])
    t_close = t_open + seconds
    out = {"steps": 0, "tokens": 0, "finished": 0, "rounds": 0, "prefill_s": 0.0}
    while t < t_close and (queue or active):
        free = slots - len(active)
        if free and queue:
            admitted, width = [], None
            for r in queue:
                if len(admitted) >= min(free, most):
                    break
                hit = shared if shared and head[r] in cached else 0
                w = max(block, _pow2(len(plan[r].prompt) - hit))
                width = w if width is None else width
                if w == width:
                    admitted.append(r)
            dt = round_ms(_pow2(len(admitted)), width) / 1e3
            if t_open <= t < t_close:
                out["rounds"] += 1
                out["prefill_s"] += dt
                out["tokens"] += len(admitted)
            t += dt
            for r in admitted:
                queue.remove(r)
                cached.add(head[r])
                if plan[r].max_new_tokens > 1:
                    active.append([r, 1, len(plan[r].prompt)])
        if active:
            inside = t_open <= t < t_close
            t += step_ms(sum(n + g for _, g, n in active)) / 1e3
            for a in active:
                a[1] += 1
            done = [a for a in active if a[1] >= plan[a[0]].max_new_tokens]
            if inside:
                out["steps"] += 1
                out["tokens"] += len(active)
                out["finished"] += len(done)
            active = [a for a in active if a[1] < plan[a[0]].max_new_tokens]
    out["serve_tokens_per_s"] = out["tokens"] / seconds
    return out


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    cell, seeds = sys.argv[1], [int(a) for a in sys.argv[2:]]
    if seeds:
        for s in seeds:
            print(s, window(cell, s))
        return 0
    rates = [window(cell, 1000003 + 7919 * i)["serve_tokens_per_s"] for i in range(200)]
    print(f"{cell}: median {statistics.median(rates):.1f} tokens/s, spread {spread(rates):.4f},"
          f" deviation {statistics.pstdev(rates) / statistics.mean(rates):.4f} over {len(rates)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
