"""The load generator: the same seed gives the same schedule, every seed the
same set of sizes and gaps, the clips hold, and lateness is accounted."""

import json
import os

import pytest

from benchmark.lib import traffic
from benchmark.lib.spec import BENCH_DIR


def mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,vocab", [("chat-poisson", 32768), ("batch-backlog", 32000)])
def test_seed_fixes_the_schedule_and_only_the_order_differs(name, vocab):
    m = mix(name)
    a, b = (traffic.build_schedule(m, 2**31 + 5, 45, vocab) for _ in range(2))
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == [
        (r.due_s, r.prompt, r.max_new_tokens) for r in b]
    c = traffic.build_schedule(m, 7, 45, vocab)
    assert [r.prompt for r in a] != [r.prompt for r in c]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, c))

    def gaps(plan):
        due = [0.0] + sorted(r.due_s for r in plan)
        return sorted(round(y - x, 6) for x, y in zip(due, due[1:]))

    assert gaps(a) == gaps(c)


@pytest.mark.parametrize("name,vocab", [("chat-poisson", 32768), ("batch-backlog", 32000)])
def test_clips_hold(name, vocab):
    m = mix(name)
    for r in traffic.build_schedule(m, 3, 45, vocab):
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new_tokens <= m["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= m["max_total_tokens"]
        assert all(0 <= t < vocab for t in r.prompt)


def test_a_length_outside_the_clip_is_refused():
    m = dict(mix("chat-poisson"), max_total_tokens=500)
    with pytest.raises(ValueError):
        traffic.build_schedule(m, 3, 45, 32768)


def test_shared_heads_and_widths():
    m = mix("chat-poisson")
    plan = traffic.build_schedule(m, 11, 45, 32768)
    heads = {tuple(r.prompt[:128]) for r in plan}
    assert len(heads) == 4
    assert traffic.prefill_widths(plan, m, 16) == [32, 64, 128, 256, 512, 1024]


def test_generator_submits_at_due_times_and_accounts_lateness():
    plan = [traffic.Planned(0.05 * i, [1, 2, 3], 4) for i in range(5)]
    seen = []

    def submit(r):
        if r is plan[3]:
            raise RuntimeError("refused")
        seen.append(r)

    gen = traffic.Generator(plan, submit)
    t0 = gen.start()
    import time

    time.sleep(0.4)
    gen.stop()
    assert len(seen) == 4 and plan[3].refused.startswith("RuntimeError")
    for r in plan:
        assert r.t_due == pytest.approx(t0 + r.due_s)
        assert 0 <= r.t_submit - r.t_due < 0.05
