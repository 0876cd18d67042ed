"""Percentile, spread, FLOP and byte arithmetic (the counts through each
configuration's model kind), and the table of peaks."""

import pytest

from benchmark.lib import counts, peaks, stats

MISTRAL_7B = dict(model="llama", hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
                  intermediate_size=14336, num_hidden_layers=32, vocab_size=32768)
MIXTRAL = dict(MISTRAL_7B, model="moe", vocab_size=32000, num_local_experts=8, num_experts_per_tok=2)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (100, 4.0), (95, 3.85)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_share_is_the_contracts_rule():
    # statistics.quantiles([1..6], n=4) -> 1.75, 3.5, 5.25
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def test_param_counts_match_the_published_models():
    assert counts.param_count(MISTRAL_7B) == 7_248_023_552  # Mistral-7B-v0.3
    assert counts.param_count(MIXTRAL) == 46_702_792_704  # Mixtral-8x7B-v0.1


def test_train_flops_count_causal_half_head_and_active_experts():
    c = dict(MISTRAL_7B, num_hidden_layers=5)
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    want = 6 * (5 * per_layer + 4096 * 32768) + 3 * 5 * 4 * 4096 * (4096 / 2)
    assert counts.train_flops_per_token(c, 4096) == want
    moe = counts.train_flops_per_token(dict(MIXTRAL, num_hidden_layers=1), 128)
    dense = counts.train_flops_per_token(dict(MISTRAL_7B, vocab_size=32000, num_hidden_layers=1), 128)
    assert moe - dense == 6 * (3 * 4096 * 14336 + 4096 * 8)  # one more expert, and the router


def test_decode_bytes_are_weights_once_plus_kv_held():
    c = dict(MISTRAL_7B, num_hidden_layers=16)
    base = counts.decode_step_bytes(c, 0, 0)
    assert base == 2 * (counts.param_count(c) - 32768 * 4096)  # the table is not read whole
    assert counts.kv_bytes_per_token(c) == 65536
    assert counts.decode_step_bytes(c, 16, 1000) == base + 16 * 4096 * 2 + 1000 * 65536


def test_unknown_device_is_an_error_not_a_default():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(ValueError):
        peaks.peak("TPU v9", "bf16_flops_per_s")
