"""The model kind ``exaone_moe`` through the harness at the fixtures' widths: the program
is ``correct`` against ``reference/exaone_moe.py`` with its requests decoding past the
window; the fp8 control in its place and a reference whose logits are scaled by -1 are
not; the adapter's tree is the program's; the kind's counts are those of the
configuration file and the least a step must read; ``--rehearse``'s traced run reads
the kind's metrics."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import kinds, models, serve_cell, spec

from .conftest import FIXTURES

CELL = "tiny-exaone-backlog"
REAL = "k-exaone-serve-decode-long"


def test_the_program_is_correct_and_the_fp8_control_is_not():
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True, control="fp8")
    assert rec["verdict"].correct
    assert any(v > cell.check.get(f"{k}_limit", float("inf")) for k, v in rec["control"].items())


@pytest.mark.parametrize("scale,correct", [(1.0, True), (-1.0, False)])
def test_the_kinds_reference_is_the_one_consulted(tmp_path, scale, correct):
    bench = tmp_path / "benchmark"
    shutil.copytree(FIXTURES, bench)
    os.makedirs(bench / "reference")
    src = open(os.path.join(spec.BENCH_DIR, "reference", "exaone_moe.py")).read()
    (bench / "reference" / "exaone_moe.py").write_text(
        src.replace("    return jnp.concatenate(\n        [matmul(x, w[:, i",  # the head's last statement
                    f"    return {scale} * jnp.concatenate(\n        [matmul(x, w[:, i"))
    out = bench_run.run_cell(CELL, 3, 1.5, False, bench_dir=str(bench), allow_cpu=True)
    assert out["correct"] is correct


def test_the_rehearsals_traced_run_reads_the_kind():
    """What ``run.py --rehearse`` does with this cell: a traced run on the CPU, every reader asked."""
    out = bench_run.run_cell(CELL, 7, 2.0, True, bench_dir=FIXTURES, allow_cpu=True)
    assert out["correct"] and out["metrics"]
    assert "engine.step_ms" in out["metrics"] and out["device"]["platform"] == "cpu"


def test_the_adapters_tree_is_the_programs():
    from torchx_tpu.models import llama

    for name, bench_dir in ((CELL, FIXTURES), (REAL, spec.BENCH_DIR)):
        c = spec.load_cell(name, bench_dir).config
        cfg = models.program_config(c, max_seq=256)
        theirs = jax.eval_shape(lambda cfg=cfg: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0)))
        mine = jax.tree.map(lambda leaf: leaf[0], models.weight_shapes(c), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        assert jax.tree.map(lambda w: tuple(w.shape), theirs) == mine
        assert cfg.param_count() == kinds.of(c).param_count(c)


def test_counts_are_the_configuration_files_and_the_least_a_step_must_read():
    """ISSUE 31's arithmetic, from the file as it is run; no roofline share can pass 100%:
    held experts reached, not held experts; window rows, not context rows, on sliding layers."""
    c = spec.load_cell(REAL).config
    k = kinds.of(c)
    assert k.attention_params(c) == 113_246_208  # W_q 50.33 + W_k 6.29 + W_v 6.29 + W_o 50.33 M
    assert k.expert_params(c) == 37_748_736 and 3 * 6144 * 18432 == 339_738_624
    assert abs(k.param_count(c) - 5.98e9) < 5e6 and abs(k.param_count(c) * 2 / 2**30 - 11.14) < 0.01
    cfg = models.program_config(c, max_seq=4224)
    assert (cfg.head_dim, cfg.n_experts, cfg.n_experts_held, cfg.top_k, cfg.sliding_window) == (128, 128, 16, 8, 128)
    assert cfg.cache_kinds.count("window") == 6 and cfg.cache_kinds.count("full") == 2 and cfg.layer_period == 4
    assert k.kv_bytes_per_token(c) == 8192  # what a further token of context costs: two full layers
    assert k.window_bytes_per_slot(c, 4096) == k.window_bytes_per_slot(c, 128) == 6 * 128 * 4096
    assert k.window_bytes_per_slot(c, 40) == 6 * 40 * 4096  # a slot shorter than the window reads what it has
    # 64 slots of 8 picks over 128 experts reach 15.7 of the 16 held; one token reaches one
    assert 15.6 < k.held_experts_reached(c, 64) < 16 and abs(k.held_experts_reached(c, 1) - 1.0) < 1e-9
    held_all = 7 * 16 * k.expert_params(c) * 2
    assert 0.97 * held_all < k.decode_held_expert_bytes(c, 64) < held_all  # 8.5 GB, never more than is held
    rows = 64 * 1400
    attention = k.decode_attention_bytes(c, 64, rows)
    assert attention == rows * 8192 + 64 * 6 * 128 * 4096  # ~0.75 GB of full rows + 0.2 GB of windows
    assert attention < rows * 8 * 4096 / 3  # a third of what every layer reading every row would be
    step = k.decode_step_bytes(c, 64, rows)
    assert 11.5e9 < step - attention < 11.9e9  # ~11.7 GB of weights a step
    # prefill: a token's 8 routings land here 1 time in 8, plus the shared expert
    assert k.prefill_expert_flops_per_token(c) == 7 * 2 * 2 * k.expert_params(c)
    # attention FLOPs: causal on 2 layers, at most 128 keys on 6
    cold = k.prefill_attention_flops(c, 0, 512)
    assert cold == 4 * 64 * 128 * (2 * 512 * 513 / 2 + 6 * (128 * 129 / 2 + 384 * 128))
    assert k.prefill_attention_flops(c, 1024, 16) == 4 * 64 * 128 * (2 * (16 * 1024 + 16 * 17 / 2) + 6 * 16 * 128)
    tree = models.weight_shapes(c)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert n == k.param_count(c)


def test_the_file_holds_every_published_key_but_the_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "K-EXAONE-236B-A23B")
    c = spec.load_cell(REAL).config
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"] if e["name"] == "k-exaone-236b-a23b-l8e16")
    assert c["source"] == row["source_url"] == entry["source"] and len(c["source"]) <= 200
    cut = {"num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows", "num_experts", "vocab_size"}
    assert {k for k, v in row["config"].items() if c.get(k, "missing") != v} == cut == set(entry["reduced"])
    assert cut <= set(c["reduced"])
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert c[key] == row["config"][key][:8]
    assert (c["published_num_experts"], c["published_vocab_size"]) == (row["config"]["num_experts"], row["config"]["vocab_size"])
    # every published width unchanged
    for key, width in (("hidden_size", 6144), ("num_attention_heads", 64), ("num_key_value_heads", 8), ("head_dim", 128),
                       ("moe_intermediate_size", 2048), ("intermediate_size", 18432), ("num_experts_per_tok", 8),
                       ("sliding_window", 128), ("routed_scaling_factor", 2.5)):  # fmt: skip
        assert c[key] == row["config"][key] == width
    assert "eight chips share each layer" in c["deployment"]["stands_for"] and "multi-token-prediction" in c["assumed"]["not built"]


def test_traffic_file_is_issue_31s_to_the_digit():
    t = spec.load_cell(REAL).traffic
    assert t["arrivals"] == {"process": "backlog", "count": 512, "ramp_s": 12}
    assert t["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.4, "min": 256, "max": 1024}
    assert t["output"] == {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}
    assert t["max_total_tokens"] == 4096 and t["sampling"] == "greedy"


def test_the_unbuilt_is_refused_not_ignored():
    c = dict(spec.load_cell(CELL, FIXTURES).config)
    for key, value in (("n_group", 8), ("scoring_func", "softmax"), ("tie_word_embeddings", True),
                       ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
                       ("mlp_layer_types", ["sparse"] * 8), ("sliding_windows", [20] * 8)):  # fmt: skip
        with pytest.raises(ValueError):
            models.program_config(dict(c, **{key: value}))
