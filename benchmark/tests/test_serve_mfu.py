"""``model.serve_mfu_pct`` (PR 39): the forward FLOPs a serving window needed over
the chip's peak, from the harness's own counts and each kind's count of a token."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import counts, peaks, serve_cell, spec

BACKLOG = ["mixtral8x7b-serve-backlog", "kimi-vl-a3b-serve-backlog", "k-exaone-serve-decode-long",
           "xing4-serve-decode-long"]


@pytest.mark.parametrize("cell", [*BACKLOG, "mistral7b-serve-chat", "mistral7b-train-4k"])
def test_the_forward_is_a_third_of_the_training_count(cell):
    """Where every layer attends all that came before, a token that sees ``keys`` positions costs a third of
    a trained token of a sequence ``2 x keys`` long; a sliding layer stops at its window, which the
    training count averages over the sequence."""
    c = spec.load_cell(cell).config
    for keys in (64.0, 256.0, 2048.0):
        third = counts.train_flops_per_token(c, 2 * keys) / 3.0
        mine = counts.forward_flops_per_token(c, keys)
        if c["model"] == "exaone_moe":
            assert mine == pytest.approx(third, rel=2e-3)
        else:
            assert mine == pytest.approx(third, rel=1e-12)
        head = 2.0 * c["hidden_size"] * c["vocab_size"]
        assert counts.forward_flops_per_token(c, keys, head=False) == pytest.approx(mine - head, rel=1e-12)


def test_the_windows_flops_are_the_tokens_out_and_the_prompt_tokens_prefilled():
    c = spec.load_cell("kimi-vl-a3b-serve-backlog").config
    # two requests behind a cached head of 1,024 tokens, request by request: 476 positions over keys 1,025..1,500
    # and 1,476 over 1,025..2,500; the longer prompt weighs more, which a mean prompt's keys would miss
    got = serve_cell.forward_flops(c, decoded=1000, context=2000.0, prefills=[(1500, 1024), (2500, 1024)])
    keys = (476 * 1262.5 + 1476 * 1762.5) / 1952
    assert got["prefilled_tokens"] == 1952 and got["prefill_keys_mean"] == pytest.approx(keys)
    assert keys > (1500 + 2500 + 2 * 1024) / 4 + 100
    assert got["forward_flops"] == pytest.approx(
        1000 * counts.forward_flops_per_token(c, 2000.0) + 1952 * counts.forward_flops_per_token(c, keys, head=False))
    by_position = sum(counts.forward_flops_per_token(c, k, head=False) for p in (1500, 2500) for k in range(1025, p + 1))
    assert got["forward_flops"] - 1000 * counts.forward_flops_per_token(c, 2000.0) == pytest.approx(by_position, rel=1e-12)
    # a window that admitted nothing, and a prompt with nothing cached
    assert serve_cell.forward_flops(c, 10, 100.0, [])["forward_flops"] == 10 * counts.forward_flops_per_token(c, 100.0)
    assert serve_cell.forward_flops(c, 0, 0.0, [(100, 0)])["prefill_keys_mean"] == 50.5


def test_a_prompts_cached_head_is_the_whole_blocks_a_prompt_ahead_of_it_began_with():
    head, other = list(range(100, 140)), list(range(500, 540))
    prompts = [head + [1, 2, 3], head + [4, 5, 6, 7, 8], other + [1], head[:32], head[:20] + [9] * 30, head + [1, 2, 3]]
    # the first finds nothing; 40 shared tokens are two blocks of 16; another head shares none; a prompt that is
    # all cached keeps its last block to prefill; a head that parts inside the second block shares one; the same
    # prompt again shares its two whole blocks (its tail fills no third)
    assert serve_cell.cached_prefix_lens(prompts, 16) == [0, 32, 0, 16, 16, 32]
    assert serve_cell.cached_prefix_lens([], 16) == [] and serve_cell.cached_prefix_lens([[1] * 16, [1] * 16], 16) == [0, 0]


def test_the_reader_reads_the_windows_counter_and_nothing_where_there_is_none():
    reader = bench_run.load_reader("model.serve_mfu_pct", spec.BENCH_DIR)
    entry = next(m for m in json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))["per_layer"]
                 if m["name"] == reader.NAME)
    assert entry["workloads"] == BACKLOG and (entry["moves"], entry["layer"], entry["unit"]) == (
        "serve_tokens_per_s", "model", "%")
    assert reader.read({"counters": {"serve_mfu_pct": 9.5}}) == 9.5
    assert reader.read({"counters": {"tokens_per_s": 14000.0}}) is None  # a training run, a run on the CPU


# what `serve: model.serve_mfu_pct ...` printed in chip runs of PR 39's final tree (TPU v5 lite; the context and the
# keys as printed, to a tenth of a token): cell -> (tokens out, mean context, prompt tokens prefilled, which the
# engine's own rounds counted to the token, their mean keys, window seconds, forward FLOPs, the reading)
RECORDED = {
    "xing4-serve-decode-long": (278144, 1382.5, 75034, 313.8, 47.530, 8.67852e14, 9.2685),
    "kimi-vl-a3b-serve-backlog": (116605, 2093.0, 309183, 1654.3, 45.022, 7.84662e14, 8.8469),
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_a_recorded_runs_reading_is_the_kinds_count_over_the_peak(cell):
    decoded, context, prefilled, keys, window_s, flops, reading = RECORDED[cell]
    c = spec.load_cell(cell).config
    mine = decoded * counts.forward_flops_per_token(c, context) + prefilled * counts.forward_flops_per_token(c, keys, head=False)
    assert mine == pytest.approx(flops, rel=2e-4)
    assert 100.0 * mine / (window_s * peaks.peak("TPU v5 lite", "bf16_flops_per_s")) == pytest.approx(reading, rel=1e-3)
    assert 0.0 < reading < 100.0
