"""The 50th percentile of time to first token, from each request's due time,
over the requests due in the window that got a first token. With some 45
requests a window it is set by where in a 150 ms decode step an arrival lands,
and swings by a tenth from run to run: recorded here, not judged."""

NAME = "engine.ttft_p50_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "tpot_p95_ms"
SOURCE = "host_clock"


def read(run: dict):
    from benchmark.lib import stats

    samples = run["counters"].get("ttft_ms")
    return stats.percentile(samples, 50) if samples else None
