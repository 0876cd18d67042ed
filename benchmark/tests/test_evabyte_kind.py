"""The model kind ``evabyte`` through the harness at the fixtures' widths: the program is
``correct`` against ``reference/evabyte.py`` (a window's exact rows and a pooled row a chunk of
every window before it, one softmax), the fp8 control in its place is not, and neither is a
run whose pooled rows are never read; the adapter's tree is the program's; the kind's counts
are ISSUE 44's arithmetic from the configuration file as it is run, and the counts that stand
in for a sum the harness does not hand over stay within a few percent of the exact sum;
``--rehearse``'s traced run reads the kind's metrics; the new files load."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import kinds, models, serve_cell, spec

from .conftest import FIXTURES

CELL = "tiny-evabyte-backlog"
REAL = "evabyte-serve-decode-long"
NEW_READERS = ("kernels.decode_eva_hbm_pct", "kernels.decode_eva_pct", "kernels.decode_eva_pool_pct",
               "engine.cache_rows_per_token")  # fmt: skip


def _over(cell, numbers):
    return [k for k, v in numbers.items() if v > cell.check.get(f"{k}_limit", float("inf"))]


def test_the_program_is_correct_and_the_fp8_control_is_not():
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True, control="fp8")
    assert rec["verdict"].correct
    assert _over(cell, rec["control"])


def test_pooled_rows_that_are_never_read_are_not_correct(monkeypatch):
    """``scripts/calibrate_evabyte.py --drop-pooled`` at test size: every window starts from
    nothing. The fault is set through ``monkeypatch`` so that it ends with the test."""
    from scripts import calibrate_evabyte
    from torchx_tpu.models import eva
    from torchx_tpu.serve.kv_pool import EvaTables

    for owner, name in ((eva, "cache_coord"), (EvaTables, "coord"), (EvaTables, "_lay")):
        monkeypatch.setattr(owner, name, getattr(owner, name))  # remembered, and put back
    calibrate_evabyte.drop_the_pooled_rows()
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.5, False, 0.0, allow_cpu=True)
    assert not rec["verdict"].correct and not rec["verdict"].flags
    assert [name for name, value, limit in rec["verdict"].rows if value > limit]


def test_the_rehearsals_traced_run_reads_the_kind():
    """What ``run.py --rehearse`` does with this cell: a traced run on the CPU, every reader
    asked. The CPU's trace has no device operations and no peak: the three kernel readers find
    nothing to read there, return nothing and do not raise; the engine's reader reads the spans."""
    out = bench_run.run_cell(CELL, 7, 2.0, True, bench_dir=FIXTURES, allow_cpu=True)
    assert out["correct"] and out["metrics"] and out["device"]["platform"] == "cpu"
    assert "engine.step_ms" in out["metrics"] and not set(NEW_READERS[:3]) & set(out["metrics"])


def test_a_cell_whose_rows_are_its_tokens_reports_none_of_the_new_metrics():
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


def test_counts_are_issue_44s_arithmetic():
    c = spec.load_cell(REAL).config
    k = kinds.of(c)
    assert k.layer_matmul_params(c) + 2 * 4096 == 4 * 4096**2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128 == 202_391_552
    assert k.param_count(c) == 8 * 202_391_552 + 320 * 4096 + 4096 + 4096 * 8 * 320 == 1_630_932_992  # 3.26 GB = 3.04 GiB in bf16
    assert k.row_bytes(c) == 8 * 2 * 32 * 128 * 2 == 131_072 and k.kv_bytes_per_token(c) == 8_192
    assert k.window_bytes_per_slot(c) == (2048 + 128) * 131_072  # 272 MiB: a whole window and its staging
    assert k.pool_op_bytes(c, 32) == 2 * 17 * 131_072
    # a block (16 rows, K and V, 8 layers) is 2 MiB; the engine's default pool 4,609 of them
    dep = c["deployment"]
    assert 16 * k.row_bytes(c) == 2 << 20 and 1 + dep["max_slots"] * (8 * 7 + 176 // 2) == 4609
    assert k.rows_attended(c, 100) == 101 and k.rows_attended(c, 6000) == 6000 / 16 + 15 / 16 * 1023.5 + 1
    step = k.decode_step_bytes(c, 32, 32 * 6000)
    weights = 2 * (8 * 202_391_552 + 4096 + 4096 * 320)
    assert step == weights + 32 * 4096 * 2 + 32 * k.rows_attended(c, 6000) * 131_072 + 2 * 17 * 131_072
    assert 3.2e9 < weights < 3.3e9 and 8.7e9 < step < 9.0e9  # the cache 5.6 of 8.9 GB a step
    assert k.forward_flops_per_token(c, 6000) == 2 * (8 * (202_391_552 - 8192) + 4096 * 320) + 8 * 4 * 4096 * k.rows_attended(c, 6000) + 8 * 6 * 4096
    cfg = models.program_config(c, max_seq=12416)
    assert (cfg.eva_window, cfg.eva_chunk, cfg.pred_heads, cfg.norm_unit_offset, cfg.fp32_skip_add) == (2048, 16, 8, True, True)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim, cfg.vocab_size, cfg.rope_theta) == (4096, 32, 32, 128, 11008, 320, 1e5)
    tree = models.weight_shapes(c)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert n == k.param_count(c)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_rows_of_the_mean_context_stay_near_the_exact_sum(seed):
    """The harness hands a kind the slots' summed tokens; a slot's rows are not a function of
    that sum. Over drawn sets of 32 contexts as the cell's mix holds them (a prompt of
    1,024-4,096 and what an answer of up to 8,192 has reached), the rows of the mean context at
    a uniform phase, against the exact sum of ``(W / C) (t // W) + t % W + 1``: within 3% in the
    mean over sets, which is what a window of thousands of steps sees (every slot passes every
    phase of its window in 2,048 steps), so the window's operations do not overshoot; one set
    alone, an instant's 32 phases, lies up to a quarter off, which is why the cell is on the
    list of no reader that divides this count by a few seconds' device time
    (``kernels.decode_hbm_pct``): the kernel's own roofline share reads the engine's exact count."""
    c = spec.load_cell(REAL).config
    k = kinds.of(c)
    rng = np.random.default_rng(seed)
    ratios = []
    for _ in range(200):
        t = rng.integers(1024, 4096, 32) + rng.integers(0, 8192, 32)
        exact = np.sum(128 * (t // 2048) + t % 2048 + 1)
        ratios.append(32 * k.rows_attended(c, t.mean()) / exact)
    assert 0.7 < min(ratios) and max(ratios) < 1.3 and abs(np.mean(ratios) - 1) < 0.03, (min(ratios), max(ratios), np.mean(ratios))
    under = [k.rows_attended(c, float(t)) / (t + 1) for t in (0, 100, 2047)]
    assert under == [1.0, 1.0, 1.0]  # under one window a row a byte


def test_the_file_holds_every_published_key_but_the_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "EvaByte")
    c = spec.load_cell(REAL).config
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"] if e["name"] == "evabyte-6.5b-l8")
    assert c["source"] == row["source_url"] == entry["source"] and len(c["source"]) <= 200
    assert {k for k, v in row["config"].items() if c.get(k, "missing") != v} == set(c["reduced"]) == set(entry["reduced"]) == {"num_hidden_layers"}
    assert c["num_hidden_layers"] == 8
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["intermediate_size"]) == (4096, 32, 32, 11008)
    assert (c["window_size"], c["chunk_size"], c["num_pred_heads"], c["vocab_size"]) == (2048, 16, 8, 320)
    dep = c["deployment"]
    assert (dep["max_slots"], dep["max_seq"], dep["block_size"], dep["max_prefill_batch"], dep["chips"]) == (32, 12416, 16, 2, 1)
    assert "one pipeline stage of four" in dep["stands_for"] and "4,609 blocks" in dep["num_blocks"] and "9.0 GiB" in dep["num_blocks"]
    assert "not built" in c["assumed"] and "self-speculation" in c["assumed"]["not built"]


def test_the_cell_and_its_traffic_are_issue_44s():
    manifest = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    cell = spec.load_cell(REAL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("evabyte-6.5b-l8", "batch-backlog-reasoning-bytes", 1)
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "backlog", "count": 256, "ramp_s": 12}
    assert mix["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.4, "min": 1024, "max": 4096}
    assert mix["output"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1536, "max": 8192}
    assert mix["max_total_tokens"] == 12288 and mix["sampling"] == "greedy"
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    assert {"served_logit_gap_max_limit", "served_logit_gap_mean_limit"} <= set(cell.check) and cell.check["sample_requests"] == 9
    for name in (*NEW_READERS, "model.serve_mfu_pct", "kernels.decode_attention_pct", "engine.chunk_steps_pct", "device.idle_pct.serve"):
        assert name in cell.per_layer
    for name in NEW_READERS:  # read in the new cell alone
        assert next(m for m in manifest["per_layer"] if m["name"] == name)["workloads"] == [REAL]
    # not under the readers PR 40 left without a source, nor the experts', the latent's, the window's, the streams' or the mixer's
    for name in ("engine.admit_host_ms", "engine.prefill_device_pct", "device.idle_after_prefill_pct", "kernels.decode_experts_pct",
                 "kernels.decode_mla_hbm_pct", "kernels.decode_swa_hbm_pct", "kernels.decode_hc_pct", "kernels.decode_ssm_pct",
                 "engine.kv_held_bytes_per_token", "engine.state_bytes_per_slot",
                 # nor those that divide decode_step_bytes, a count from the mean context, by a few seconds' device time
                 "kernels.decode_hbm_pct", "kernels.decode_chunk_hbm_pct",
                 # nor those that read nothing where the traced 4 s hold no step that carried a chunk: a request ends every ~2 s here
                 "engine.chunk_fill_pct", "kernels.decode_chunk_cost_ms"):  # fmt: skip
        assert name not in cell.per_layer


def test_the_unbuilt_is_refused_not_ignored():
    c = dict(spec.load_cell(CELL, FIXTURES).config)
    for key, value in (("attention_class", "mha"), ("attention_bias", True), ("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True), ("fp32_logits", False), ("num_chunks", 4), ("hidden_act", "gelu")):  # fmt: skip
        with pytest.raises(ValueError):
            models.program_config(dict(c, **{key: value}))
