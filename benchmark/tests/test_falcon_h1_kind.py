"""The model kind ``falcon_h1`` through the harness at the fixtures' widths: the program is
``correct`` against ``reference/falcon_h1.py`` (a Mamba-2 mixer beside attention, the published
multipliers), the fp8 control in its place is not, and neither is a run whose state is dropped
at the hand-over from a prompt's last chunk to decode; the adapter's tree is the program's; the
kind's counts are ISSUE 41's arithmetic from the configuration file as it is run;
``--rehearse``'s traced run reads the kind's metrics; the new files load."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import kinds, models, serve_cell, spec

from .conftest import FIXTURES

CELL = "tiny-falcon-backlog"
REAL = "falcon-h1-serve-decode-long"
NEW_READERS = ("kernels.decode_ssm_hbm_pct", "kernels.decode_ssm_pct", "kernels.chunk_ssm_scan_mxu_pct",
               "engine.state_bytes_per_slot")  # fmt: skip


def _over(cell, numbers):
    return [k for k, v in numbers.items() if v > cell.check.get(f"{k}_limit", float("inf"))]


def test_the_program_is_correct_and_the_fp8_control_is_not():
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True, control="fp8")
    assert rec["verdict"].correct
    assert _over(cell, rec["control"])


def test_a_state_dropped_between_prompt_and_decode_is_not_correct(monkeypatch):
    """``scripts/calibrate_falcon_h1.py``'s broken run at test size: the rows of the store a
    prompt's last chunk left are zeroed before the slot's first decode step reads them."""
    from scripts import calibrate_falcon_h1

    monkeypatch.setattr(calibrate_falcon_h1.ServeEngine, "_chunk_enqueued", calibrate_falcon_h1.dropping_the_state())
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True)
    assert not rec["verdict"].correct and not rec["verdict"].flags
    assert [name for name, value, limit in rec["verdict"].rows if value > limit]


def test_the_rehearsals_traced_run_reads_the_kind():
    """What ``run.py --rehearse`` does with this cell: a traced run on the CPU, every reader
    asked. The CPU's trace has no device operations and no peak: the four new readers find
    nothing to read there, return nothing and do not raise (``tests/test_hot_spans.py`` holds
    the engine's ``state_bytes_per_slot`` on the spans the fourth reads)."""
    out = bench_run.run_cell(CELL, 7, 2.0, True, bench_dir=FIXTURES, allow_cpu=True)
    assert out["correct"] and out["metrics"] and out["device"]["platform"] == "cpu"
    assert "engine.step_ms" in out["metrics"] and not set(NEW_READERS) & set(out["metrics"])


def test_a_cell_without_a_mixer_reports_none_of_the_new_metrics():
    out = bench_run.run_cell("tiny-backlog", 7, 1.5, True, bench_dir=FIXTURES, allow_cpu=True)
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


def test_counts_are_issue_41s_arithmetic():
    c = spec.load_cell(REAL).config
    k = kinds.of(c)
    # attention 31.46 M; mixer W_in 47.35 + W_out 20.97 + the small leaves 0.03 M; SwiGLU 330.30 M; two norms
    assert k.mixer_params(c) == 5120 * 9248 + 4096 * 5120 + 5 * 5120 + 3 * 32 + 4096 == 68_351_072
    assert k.layer_matmul_params(c) + 2 * 5120 == 31_457_280 + 68_351_072 + 330_301_440 + 10_240 == 430_120_032
    assert k.param_count(c) == 6 * 430_120_032 + 2 * 261_120 * 5120 + 5120 == 5_254_594_112  # 10.51 GB in bf16
    assert k.kv_bytes_per_token(c) == 6 * 2 * 4 * 128 * 2 == 12_288
    assert k.state_bytes_per_slot(c) == 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2) == 25_350_144
    assert k.decode_state_bytes(c, 64) == 2 * 64 * 25_350_144  # 3.24 GB a step
    assert k.ssm_scan_flops(c) == 4 * 128 * 256 * 32
    step = k.decode_step_bytes(c, 64, 64 * 1800)
    weights = 2 * (6 * 430_120_032 + 5120 + 5120 * 261_120)
    assert step == weights + 64 * 5120 * 2 + 64 * 1800 * 12_288 + 2 * 64 * 25_350_144
    assert 7.8e9 < weights < 7.9e9 and 12.4e9 < step < 12.6e9  # the state 3.24 of 12.5 GB: 2.3 times the K/V's 1.42
    assert k.forward_flops_per_token(c, 1800) == 2 * (6 * 430_109_792 + 5120 * 261_120) + 6 * 4 * 2560 * 1800 + 6 * 4 * 128 * 256 * 32
    cfg = models.program_config(c, max_seq=4224)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_chunk) == (32, 128, 256, 2, 4, 128)
    assert (cfg.ssm_inner, cfg.ssm_conv_width, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (4096, 5120, 128, 20, 4)
    assert (cfg.embedding_multiplier, cfg.lm_head_multiplier, cfg.key_multiplier) == (5.656854249492381, 0.0078125, 0.011048543456039804)
    assert cfg.ssm_multipliers == tuple(c["ssm_multipliers"]) and cfg.mlp_multipliers == tuple(c["mlp_multipliers"])
    tree = models.weight_shapes(c)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert n == k.param_count(c)


def test_the_file_holds_every_published_key_but_the_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "Falcon-H1-34B-Instruct")
    c = spec.load_cell(REAL).config
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"] if e["name"] == "falcon-h1-34b-l6")
    assert c["source"] == row["source_url"] == entry["source"] and len(c["source"]) <= 200
    assert {k for k, v in row["config"].items() if c.get(k, "missing") != v} == set(c["reduced"]) == set(entry["reduced"]) == {"num_hidden_layers"}
    assert c["num_hidden_layers"] == 6
    dep = c["deployment"]
    assert (dep["max_slots"], dep["max_seq"], dep["block_size"], dep["max_prefill_batch"], dep["chips"]) == (64, 4224, 16, 2, 1)
    assert "one pipeline stage of twelve" in dep["stands_for"]


def test_the_cell_and_its_traffic_are_issue_41s():
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    cell = spec.load_cell(REAL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("falcon-h1-34b-l6", "batch-backlog-reasoning", 1)
    assert cell.traffic["arrivals"] == {"process": "backlog", "count": 512, "ramp_s": 12}
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    assert {"served_logit_gap_max_limit", "served_logit_gap_mean_limit"} <= set(cell.check)  # the model is dense
    for name in (*NEW_READERS, "model.serve_mfu_pct", "kernels.decode_hbm_pct", "kernels.decode_attention_pct",
                 "engine.chunk_fill_pct", "kernels.decode_chunk_cost_ms", "device.idle_pct.serve"):  # fmt: skip
        assert name in cell.per_layer
    for name in NEW_READERS:  # read in the new cell alone
        assert next(m for m in manifest["per_layer"] if m["name"] == name)["workloads"] == [REAL]
    # not under the readers PR 40 left without a source, nor the experts', the latent's, the window's or the streams'
    for name in ("engine.admit_host_ms", "engine.prefill_device_pct", "device.idle_after_prefill_pct", "kernels.decode_experts_pct",
                 "kernels.decode_mla_hbm_pct", "kernels.decode_swa_hbm_pct", "kernels.decode_hc_pct"):  # fmt: skip
        assert name not in cell.per_layer
    dep = cell.config["deployment"]
    assert 1 + dep["max_slots"] * (dep["max_seq"] // dep["block_size"]) // 2 == 8449


def test_the_unbuilt_is_refused_not_ignored():
    c = dict(spec.load_cell(CELL, FIXTURES).config)
    for key, value in (("mamba_proj_bias", True), ("attention_bias", True), ("mamba_norm_before_gate", True), ("mamba_rms_norm", False),
                       ("rope_scaling", {"type": "yarn"}), ("attn_layer_indices", [0, 2]), ("mamba_d_ssm", 40)):  # fmt: skip
        with pytest.raises(ValueError):
            models.program_config(dict(c, **{key: value}))
