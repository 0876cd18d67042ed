"""The model kind ``mla_moe`` through the harness at the fixtures' widths: the program
is ``correct`` against ``reference/mla_moe.py``; the fp8 control in its place and a
reference whose logits are scaled by -1 are not; the kind's counts are those of the
configuration file; the new scopes and span attributes are what the readers look for."""

import importlib
import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import kinds, models, serve_cell, spec

from .conftest import FIXTURES

CELL = "tiny-mla-backlog"


def test_the_program_is_correct_and_the_fp8_control_is_not():
    cell = spec.load_cell(CELL, FIXTURES)
    rec = serve_cell.run(cell, 13, 1.0, False, 0.0, allow_cpu=True, control="fp8")
    assert rec["verdict"].correct
    assert any(v > cell.check.get(f"{k}_limit", float("inf")) for k, v in rec["control"].items())
    assert rec["counters"]["prefix_hit_tokens"] > 0  # the shared heads were served from the cache


@pytest.mark.parametrize("scale,correct", [(1.0, True), (-1.0, False)])
def test_the_kinds_reference_is_the_one_consulted(tmp_path, scale, correct):
    bench = tmp_path / "benchmark"
    shutil.copytree(FIXTURES, bench)
    os.makedirs(bench / "reference")
    src = open(os.path.join(spec.BENCH_DIR, "reference", "mla_moe.py")).read()
    (bench / "reference" / "mla_moe.py").write_text(
        src.replace("    return jnp.concatenate(\n        [matmul(x, w[:, i",  # the head's last statement
                    f"    return {scale} * jnp.concatenate(\n        [matmul(x, w[:, i"))
    out = bench_run.run_cell(CELL, 3, 1.0, False, bench_dir=str(bench), allow_cpu=True)
    assert out["correct"] is correct


def test_counts_are_the_configuration_files():
    """ISSUE 27's arithmetic, from the file as it is run."""
    c = spec.load_cell("kimi-vl-a3b-serve-backlog").config
    k = kinds.of(c)
    assert k.attention_params(c) == 13_762_560  # W_q 6.29 + W_kva 1.18 + W_kvb 2.10 + W_o 4.19 M
    assert k.expert_params(c) * 64 == 553_648_128 and k.expert_params(c) * 2 == 17_301_504
    assert abs(k.param_count(c) - 5.433e9) < 1e6  # 10.87 GB in bf16
    assert k.kv_bytes_per_token(c) == 9 * 1152
    # the program's own count, from the config object the kind builds
    cfg = models.program_config(c, max_seq=4096)
    assert cfg.param_count() == k.param_count(c)
    assert cfg.cache_width == 640 and (cfg.n_dense_layers, cfg.capacity_factor) == (1, 0.0)
    # 64 slots of 6 picks reach nearly every expert; one token reaches its 6
    assert 63.8 < k.distinct_experts(c, 64) < 64 and abs(k.distinct_experts(c, 1) - 6) < 1e-9
    step = k.decode_step_bytes(c, 64, 64 * 2048)
    assert 11.0e9 < step < 12.0e9  # ~10.2 GB of weights + 1.36 GB of latent rows
    tree = models.weight_shapes(c)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert n == k.param_count(c)


def test_the_file_holds_every_published_key_but_the_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "Kimi-VL-A3B-Instruct")
    c = spec.load_cell("kimi-vl-a3b-serve-backlog").config
    assert c["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if c.get(k, "missing") != v} == set(c["reduced"]) == {"num_hidden_layers"}


def test_traffic_file_is_issue_27s_to_the_digit():
    t = spec.load_cell("kimi-vl-a3b-serve-backlog").traffic
    assert t["arrivals"] == {"process": "backlog", "count": 1024, "ramp_s": 12}
    assert t["prompt"] == {"dist": "lognormal", "median": 1792, "sigma": 0.4, "min": 1280, "max": 3072,
                           "shared_prefixes": 8, "shared_prefix_tokens": 1024}
    assert t["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64, "max": 1024}
    assert t["max_total_tokens"] == 4096 and t["sampling"] == "greedy"


def test_the_unbuilt_is_refused_not_ignored():
    c = dict(spec.load_cell(CELL, FIXTURES).config)
    for key, value in (("q_lora_rank", 1536), ("n_group", 8), ("rope_scaling", {"type": "yarn"}), ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            models.program_config(dict(c, **{key: value}))


def test_new_scopes_and_span_attributes_are_there_for_the_readers():
    """What this PR adds beside ``test_spans_and_scopes.py``'s recorded traces: the
    names the new readers ask ``obs/hot.py`` for, the scope paths of the lowered
    programs, and the admit span's and ``stats()``'s new fields."""
    from torchx_tpu.models import generate as gen
    from torchx_tpu.obs import hot
    attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name
    from torchx_tpu.serve.engine import ServeEngine

    for name in ("MLA_LATENT", "MLA_ABSORB", "APPEND_LATENT", "MOE_SHARED", "MOE_SORT"):
        assert getattr(hot, name) in hot.DEVICE_SCOPES
    cell = spec.load_cell(CELL, FIXTURES)
    cfg = models.program_config(cell.config, max_seq=64)
    params = models.make_weights(cell.config, 5)
    engine = ServeEngine(params, cfg, max_slots=2, block_size=16)
    stats = engine.stats()
    assert stats["preemptions"] == 0 and stats["kv_bytes_per_token"] == 3 * cfg.cache_width * 4

    def decode(params, pools):
        z = jnp.zeros((2,), jnp.int32)
        return gen.paged_decode_step(params, z, z, jnp.zeros((2, 4), jnp.int32), pools, cfg,
                                     jnp.zeros((2, 2), jnp.uint32), jnp.zeros((2,), jnp.float32))

    text = jax.jit(decode).lower(params, engine.pools).as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]+)"', text))  # an operation's scope path, from its scan body down
    for path in ("attn/mla_latent/", "attn/mla_absorb/", "attn/append_latent/append_kv/", "attn/paged_attention/scores/",
                 "moe_shared/", "moe_dispatch/moe_sort/", "moe_experts/", "moe_combine/", "moe_router/", "mlp/"):
        assert any(loc.startswith(path) or f"/{path}" in loc for loc in locs), path
    assert "paged_mla_xla" in attn_ops.traced("attention")
