"""One general load generator for serving cells, driven by a traffic file.

Every seed gets the same set of prompt lengths, output lengths and arrival
gaps (the quantiles of the file's distributions), in another order and with
other tokens: a seed changes which request meets which, never how much work a
run holds. Arrivals are an open loop: each request is submitted at its due
time whether or not earlier ones have finished, and is timed from then.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import threading
import time
from typing import Callable, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule. ``due_s`` counts from the generator's
    start; the window opens ``ramp_s`` after it."""

    due_s: float
    prompt: list[int]
    max_new_tokens: int
    t_due: float = 0.0  # on the engine's clock, set when the generator starts
    t_submit: float = 0.0
    refused: Optional[str] = None
    request: object = None  # the program's request object


def _lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths: the (i + 1/2)/n quantiles of a clipped lognormal."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def _gaps(rate: float, n: int) -> list[float]:
    """``n`` gaps: the (i + 1/2)/n quantiles of the exponential at ``rate``.
    Not a Poisson process: the count and the set of gaps are fixed, only
    their order is the seed's, so the offered work is the same in every run
    and a burst is no deeper than the smallest gaps put side by side."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def build_schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> list[Planned]:
    """The run's requests from the seed. Raises where a length leaves the
    clip the deployment was sized for."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    arr = traffic["arrivals"]
    if arr["process"] == "exponential_quantiles":
        horizon = arr["ramp_s"] + seconds
        n = max(1, round(arr["rate_per_s"] * horizon))
        gaps = _gaps(arr["rate_per_s"], n)
        rng.shuffle(gaps)
        due = list(np.cumsum(gaps))
    elif arr["process"] == "backlog":
        n = int(arr["count"])
        due = [0.0] * n
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    p, o = traffic["prompt"], traffic["output"]
    prompt_lens = _lengths(p, n)
    output_lens = _lengths(o, n)
    rng.shuffle(prompt_lens)
    rng.shuffle(output_lens)
    n_shared = int(p.get("shared_prefixes", 0))
    shared_len = int(p.get("shared_prefix_tokens", 0))
    shared = [rng.integers(0, vocab, shared_len).tolist() for _ in range(n_shared)]
    which = rng.permutation(n) % max(n_shared, 1)
    plan = []
    for i in range(n):
        if prompt_lens[i] + output_lens[i] > traffic["max_total_tokens"]:
            raise ValueError(
                f"request {i}: {prompt_lens[i]}+{output_lens[i]} tokens leave the"
                f" clip of {traffic['max_total_tokens']}"
            )
        head = shared[which[i]] if n_shared else []
        own = rng.integers(0, vocab, prompt_lens[i] - len(head)).tolist()
        plan.append(Planned(float(due[i]), head + own, output_lens[i]))
    return plan


def prefill_widths(plan: list[Planned], traffic: dict, block_size: int) -> list[int]:
    """Every prefill width the plan can reach: a prompt is prefilled whole or,
    on a prefix hit, without its shared head (hits come in whole blocks)."""
    shared = int(traffic["prompt"].get("shared_prefix_tokens", 0))
    hit = shared // block_size * block_size
    widths = set()
    for r in plan:
        for suffix in {len(r.prompt), len(r.prompt) - hit}:
            widths.add(max(block_size, 1 << max(0, suffix - 1).bit_length()))
    return sorted(widths)


class Generator:
    """Submits each planned request at its due time from one thread and
    records how late it ran."""

    def __init__(
        self,
        plan: list[Planned],
        submit: Callable[[Planned], None],
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self._plan = sorted(plan, key=lambda r: r.due_s)
        self._submit = submit
        self._clock = clock
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-load", daemon=True)
        self.t_start = 0.0

    def start(self) -> float:
        self.t_start = self._clock()
        for r in self._plan:
            r.t_due = self.t_start + r.due_s
        self._thread.start()
        return self.t_start

    def _run(self) -> None:
        for r in self._plan:
            wait = r.t_due - self._clock()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            try:
                self._submit(r)
            except Exception as e:  # noqa: BLE001 - a refusal is a failed request
                r.refused = f"{type(e).__name__}: {e}"
            r.t_submit = self._clock()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            raise RuntimeError("load generator did not stop")
