"""The model kind ``mla_moe_hc`` through the harness at the fixtures' widths: the program is
``correct`` against ``reference/mla_moe_hc.py`` (four residual streams, the compressed
query, YaRN), the fp8 control in its place is not; the adapter's tree is the program's; the
kind's counts are ISSUE 33's arithmetic from the configuration file as it is run;
``--rehearse``'s traced run reads the kind's metrics; the new files load."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import kinds, models, serve_cell, spec

from .conftest import FIXTURES

CELL = "tiny-mla-hc-backlog"
REAL = "xing4-serve-decode-long"
NEW_READERS = ("kernels.decode_hc_pct", "kernels.decode_hc_hbm_pct", "kernels.prefill_hc_hbm_pct")


def test_the_program_is_correct_and_the_fp8_control_is_not():
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True, control="fp8")
    assert rec["verdict"].correct
    assert any(v > cell.check.get(f"{k}_limit", float("inf")) for k, v in rec["control"].items())


def test_the_rehearsals_traced_run_reads_the_kind():
    """What ``run.py --rehearse`` does with this cell: a traced run on the CPU, every reader
    asked. The CPU's trace has no device operations and no peak: the three new readers find
    nothing to read there, return nothing and do not raise."""
    out = bench_run.run_cell(CELL, 7, 2.0, True, bench_dir=FIXTURES, allow_cpu=True)
    assert out["correct"] and out["metrics"] and out["device"]["platform"] == "cpu"
    assert "engine.step_ms" in out["metrics"] and not set(NEW_READERS) & set(out["metrics"])


def test_a_cell_of_one_stream_reports_none_of_the_new_metrics():
    """A program without the scopes (the parent's) or a configuration without streams:
    the readers return nothing and do not raise."""
    out = bench_run.run_cell("tiny-mla-backlog", 7, 1.5, True, bench_dir=FIXTURES, allow_cpu=True)
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


def test_counts_are_issue_33s_arithmetic():
    c = spec.load_cell(REAL).config
    k = kinds.of(c)
    # W_qa 2.75 + W_qb 4.72 + W_kva 2.06 + W_kvb 4.19 + W_o 14.68 M
    assert k.attention_params(c) == 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584 == 28_409_856
    assert k.expert_params(c) == 11_010_048 and 3 * 3584 * 9216 == 99_090_432
    assert k.hc_params(c) == 2 * (14336 * 24 + 24 + 3)  # 0.69 M a layer
    assert abs(k.param_count(c) - 5_665.8e6) < 0.1e6  # 11.33 GB in bf16
    assert k.kv_bytes_per_token(c) == 8 * 1152 == 9216
    cfg = models.program_config(c, max_seq=4224)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (cfg.q_lora_rank, cfg.n_dense_layers, cfg.n_experts, cfg.top_k, cfg.n_shared_experts) == (768, 2, 64, 4, 1)
    assert (cfg.cache_width, cfg.capacity_factor, cfg.routed_scale) == (640, 0.0, 2.0)
    assert abs(cfg.attn_scale - 192**-0.5 * 1.4158883**2) < 1e-7
    assert cfg.rope_scaling.ramp_bounds(64, 10000.0) == (10, 23)
    # 128 slots of 4 picks reach every expert; one token reaches its 4
    assert 63.9 < k.distinct_experts(c, 128) < 64 and abs(k.distinct_experts(c, 1) - 4) < 1e-9
    assert k.prefill_expert_flops_per_token(c) == 6 * 2 * (4 + 1) * k.expert_params(c)
    # the residual path: 8 layers x (the stream read and written once, two sublayers' [rows, d] in and out, phi twice)
    assert k.hc_bytes(c, 128) == 8 * 2 * (2 * 128 * 4 * 3584 + 2 * (2 * 128 * 3584 + 14336 * 24))
    assert 95e6 < k.hc_bytes(c, 128) < 105e6  # 0.12 ms a decode step at the wire
    step = k.decode_step_bytes(c, 128, 128 * 1550)
    rows = 128 * 1550 * 9216
    assert 10.3e9 < step - rows < 10.5e9 and 1.7e9 < rows < 1.9e9  # 10.4 GB of weights + 1.83 GB of latent rows
    tree = models.weight_shapes(c)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert n == k.param_count(c)


def test_the_file_holds_every_published_key_but_the_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "Xing4.0-29B-A4B")
    c = spec.load_cell(REAL).config
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"] if e["name"] == "xing4.0-29b-a4b-l8")
    assert c["source"] == row["source_url"] == entry["source"] and len(c["source"]) <= 200
    assert {k for k, v in row["config"].items() if c.get(k, "missing") != v} == set(c["reduced"]) == set(entry["reduced"]) == {"num_hidden_layers"}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (8, 2)
    dep = c["deployment"]
    assert (dep["max_slots"], dep["max_seq"], dep["block_size"], dep["max_prefill_batch"], dep["chips"]) == (128, 4224, 16, 2, 1)
    assert "one pipeline stage of five" in dep["stands_for"] and "not built" in c["assumed"]["num_nextn_predict_layers"]


def test_the_cell_and_its_traffic_are_issue_33s_to_the_digit():
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    cell = spec.load_cell(REAL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("xing4.0-29b-a4b-l8", "batch-backlog-reasoning-1k", 1)
    t = cell.traffic
    assert t["arrivals"] == {"process": "backlog", "count": 1024, "ramp_s": 12}
    assert t["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.4, "min": 256, "max": 1024}
    assert t["output"] == {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}
    assert t["max_total_tokens"] == 4096 and t["sampling"] == "greedy"
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    for name in (*NEW_READERS, "kernels.decode_mla_hbm_pct", "kernels.decode_hbm_pct", "kernels.decode_moe_routing_pct",
                 "kernels.prefill_experts_mxu_pct", "engine.prefill_device_pct", "device.idle_pct.serve"):  # fmt: skip
        assert name in cell.per_layer
    for name in NEW_READERS:  # read in the new cell alone
        assert next(m for m in manifest["per_layer"] if m["name"] == name)["workloads"] == [REAL]
    # the engine's default pool at the deployment's sizes, as the configuration file says
    dep = cell.config["deployment"]
    assert 1 + dep["max_slots"] * (dep["max_seq"] // dep["block_size"]) // 2 == 16897


def test_the_unbuilt_is_refused_not_ignored():
    c = dict(spec.load_cell(CELL, FIXTURES).config)
    for key, value in (("n_group", 8), ("scoring_func", "softmax"), ("ep_size", 8), ("q_lora_rank", None), ("hc_mult", 0),
                       ("rope_scaling", dict(c["rope_scaling"], type="linear"))):  # fmt: skip
        with pytest.raises(ValueError):
            models.program_config(dict(c, **{key: value}))
    # and the kind this one builds on still refuses what it does not build
    with pytest.raises(ValueError, match="q_lora_rank"):
        kinds.load("mla_moe").program_config(c)
