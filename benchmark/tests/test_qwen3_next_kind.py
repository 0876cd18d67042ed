"""The model kind ``qwen3_next`` through the harness at the fixtures' widths: the program is
``correct`` against ``reference/qwen3_next.py`` (three Gated DeltaNet layers for every attention
layer, a share of the experts behind the published router), the fp8 control in its place is not,
and neither is a run whose state is dropped at the hand-over from a prompt's last chunk to decode;
the adapter's tree is the program's; the kind's counts are ISSUE 49's arithmetic from the
configuration file as it is run; ``--rehearse``'s traced run reads the kind's metrics; the new
files load."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import kinds, models, serve_cell, spec

from .conftest import FIXTURES

CELL = "tiny-qwen3-next-backlog"
REAL = "qwen3-next-serve-decode-long"
NEW_READERS = ("kernels.decode_gdn_hbm_pct", "kernels.decode_gdn_pct", "kernels.chunk_gdn_mxu_pct")


def _over(cell, numbers):
    return [k for k, v in numbers.items() if v > cell.check.get(f"{k}_limit", float("inf"))]


def test_the_program_is_correct_and_the_fp8_control_is_not():
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True, control="fp8")
    assert rec["verdict"].correct
    assert _over(cell, rec["control"])


def test_a_state_dropped_between_prompt_and_decode_is_not_correct(monkeypatch):
    """``scripts/calibrate_qwen3_next.py``'s broken run at test size: the rows of the store a
    prompt's last chunk left are zeroed before the slot's first decode step reads them."""
    from scripts import calibrate_qwen3_next

    monkeypatch.setattr(serve_cell_engine(), "_chunk_enqueued", calibrate_qwen3_next.dropping_the_state())
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True)
    assert not rec["verdict"].correct and not rec["verdict"].flags
    assert [name for name, value, limit in rec["verdict"].rows if value > limit]


def serve_cell_engine():
    from torchx_tpu.serve.engine import ServeEngine

    return ServeEngine


def test_the_rehearsals_traced_run_reads_the_kind():
    """What ``run.py --rehearse`` does with this cell: a traced run on the CPU, every reader asked.
    The CPU's trace has no device operations and no peak: the three new readers find nothing to read
    there, return nothing and do not raise (``tests/test_hot_spans.py`` holds the engine's
    ``state_bytes_per_slot`` on the spans)."""
    out = bench_run.run_cell(CELL, 7, 2.0, True, bench_dir=FIXTURES, allow_cpu=True)
    assert out["correct"] and out["metrics"] and out["device"]["platform"] == "cpu"
    assert "engine.step_ms" in out["metrics"] and not set(NEW_READERS) & set(out["metrics"])


def test_a_cell_without_linear_layers_reports_none_of_the_new_metrics():
    out = bench_run.run_cell("tiny-falcon-backlog", 7, 1.5, True, bench_dir=FIXTURES, allow_cpu=True)
    assert out["correct"] and not set(NEW_READERS) & set(out["metrics"])


def test_the_adapters_tree_is_the_programs():
    from torchx_tpu.models import llama

    for name, bench_dir in ((CELL, FIXTURES), (REAL, spec.BENCH_DIR)):
        c = spec.load_cell(name, bench_dir).config
        cfg = models.program_config(c, max_seq=256)
        theirs = jax.eval_shape(lambda cfg=cfg: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
        mine = jax.tree.map(lambda leaf: leaf[0], models.weight_shapes(c), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        assert jax.tree.map(lambda w: tuple(w.shape), theirs) == mine
        assert cfg.param_count() == kinds.of(c).param_count(c)


def test_counts_are_issue_49s_arithmetic():
    c = spec.load_cell(REAL).config
    k = kinds.of(c)
    # a DeltaNet mixer 33.72 M, an attention 27.26 M, one expert 3.146 M, router 1.05 M
    assert k.delta_net_params(c) + k._mixer_extras(c)[1] == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 64 + 128 + 4096 * 2048 == 33_718_464
    assert k.attention_params(c) + k._mixer_extras(c)[0] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512 == 27_263_488
    assert k.expert_params(c) == 3 * 2048 * 512 == 3_145_728
    common = 2 * 2048 + 128 * 3_145_728 + 3_145_728 + 2048 + 2048 * 512
    assert k.param_count(c) == 8 * common + 6 * 33_718_464 + 2 * 27_263_488 + 2 * 37_984 * 2048 + 2048 == 3_667_251_328  # 7.33 GB in bf16
    assert k.kv_bytes_per_token(c) == 2 * 2 * 2 * 256 * 2 == 4_096
    assert k.state_bytes_per_slot(c) == 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2) == 12_877_824
    assert k.decode_state_bytes(c, 128) == 2 * 128 * 12_877_824  # 3.30 GB a step
    assert 117.6 < k.held_experts_reached(c, 128) < 117.8
    assert 5.9e9 < k.decode_held_expert_bytes(c, 128) < 5.95e9
    step = k.decode_step_bytes(c, 128, 128 * 2000)
    assert 10.9e9 < step < 11.1e9  # experts 54%, states 30%, K/V 10%
    assert k.gdn_chunk_flops(c, 256) == 256 * 6 * 6 * 32 * 128 * 128
    active = 8 * (2.5 * 3_145_728 + 3_145_728 + 2048 + 2048 * 512) + 2 * (27_263_488 - 512) + 6 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
    assert k.forward_flops_per_token(c, 2000) == 2 * (active + 2048 * 37_984) + 2 * 4 * 4096 * 2000 + 6 * 6 * 32 * 128 * 128
    cfg = models.program_config(c, max_seq=4224)
    assert (cfg.gdn_heads, cfg.gdn_key_heads, cfg.gdn_head_dim, cfg.gdn_conv, cfg.gdn_conv_width) == (32, 16, 128, 4, 8192)
    assert (cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.rotary_dim, cfg.rope_dim, cfg.cache_row) == (256, 16, 2, 64, 64, (4, 128))
    assert (cfg.n_experts, cfg.n_experts_held, cfg.top_k, cfg.expert_width, cfg.n_shared_experts) == (512, 128, 10, 512, 1)
    assert cfg.layer_types == ("linear", "linear", "linear", "full") * 2 and cfg.cache_kinds.count("state") == 6
    tree = models.weight_shapes(c)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert n == k.param_count(c)


def test_the_file_holds_every_published_key_but_the_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    c = spec.load_cell(REAL).config
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"] if e["name"] == "qwen3-next-80b-a3b-l8e128")
    assert c["source"] == row["source_url"] == entry["source"] and len(c["source"]) <= 200
    cut = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {k for k, v in row["config"].items() if c.get(k, "missing") != v} == set(c["reduced"]) == set(entry["reduced"]) == cut
    assert (c["num_hidden_layers"], c["num_experts"], c["published_num_experts"], c["vocab_size"], c["published_vocab_size"]) == (8, 128, 512, 37_984, 151_936)
    dep = c["deployment"]
    assert (dep["max_slots"], dep["max_seq"], dep["block_size"], dep["max_prefill_batch"], dep["chips"]) == (128, 4224, 16, 2, 1)
    assert "four chips share each layer" in dep["stands_for"]


def test_the_cell_and_its_traffic_are_issue_49s():
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    cell = spec.load_cell(REAL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("qwen3-next-80b-a3b-l8e128", "batch-backlog-reasoning-1k", 1)
    assert cell.traffic["arrivals"] == {"process": "backlog", "count": 1024, "ramp_s": 12}
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    # a sparse model: a routing that falls the other way makes the widest gap heavy-tailed, so the mean and a share over a tolerance
    assert cell.check == {"sample_requests": 9, "served_logit_gap_mean_limit": 0.05, "served_gap_tolerance": 0.5, "served_gap_over_share_limit": 0.02}
    for name in (*NEW_READERS, "engine.state_bytes_per_slot", "kernels.decode_experts_held_hbm_pct", "kernels.decode_experts_pct",
                 "kernels.decode_moe_routing_pct", "model.serve_mfu_pct", "kernels.decode_hbm_pct", "kernels.decode_attention_pct",
                 "engine.chunk_fill_pct", "kernels.decode_chunk_cost_ms", "kernels.decode_chunk_hbm_pct", "device.idle_pct.serve"):  # fmt: skip
        assert name in cell.per_layer
    for name in NEW_READERS:  # read in the new cell alone
        assert next(m for m in manifest["per_layer"] if m["name"] == name)["workloads"] == [REAL]
    # not under the readers PR 40 left without a source, nor the Mamba mixer's, the latent's, the window's or the streams'
    for name in ("engine.admit_host_ms", "engine.prefill_device_pct", "device.idle_after_prefill_pct", "kernels.decode_ssm_pct",
                 "kernels.decode_ssm_hbm_pct", "kernels.decode_mla_hbm_pct", "kernels.decode_swa_hbm_pct", "kernels.decode_hc_pct"):  # fmt: skip
        assert name not in cell.per_layer
    dep = cell.config["deployment"]
    assert 1 + dep["max_slots"] * (dep["max_seq"] // dep["block_size"]) // 2 == 16_897


def test_the_unbuilt_is_refused_not_ignored():
    c = dict(spec.load_cell(CELL, FIXTURES).config)
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0]), ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
                       ("norm_topk_prob", False), ("linear_value_head_dim", 32), ("shared_expert_intermediate_size", 40)):  # fmt: skip
        with pytest.raises(ValueError):
            models.program_config(dict(c, **{key: value}))
