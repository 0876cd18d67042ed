"""A later PR adds a configuration, a traffic mix, a cell, a per-layer metric
and a model kind as new files and edits none: a copy of the benchmark's data
directories gets the new files and a BENCHMARK.json entry, and the harness
runs them."""

import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import models, spec

from .conftest import DATA, FIXTURES


def _mtimes(root):
    return {os.path.join(r, p): os.path.getmtime(os.path.join(r, p)) for r, _, fs in os.walk(root) for p in fs}


def test_new_files_only(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(FIXTURES, bench)
    shutil.copytree(os.path.join(spec.BENCH_DIR, "layer_metrics"), bench / "layer_metrics")
    before = _mtimes(bench)

    cfg = json.load(open(bench / "configs" / "tiny-dense.json"))
    cfg.update(name="tiny-dense-l3", num_hidden_layers=3)
    json.dump(cfg, open(bench / "configs" / "tiny-dense-l3.json", "w"))
    mix = json.load(open(bench / "traffic" / "tiny-chat.json"))
    mix["arrivals"]["rate_per_s"] = 15.0
    json.dump(mix, open(bench / "traffic" / "tiny-chat-fast.json", "w"))
    json.dump({"config": "tiny-dense-l3", "traffic": "tiny-chat-fast", "chips": 1, "why": "new",
               "check": {"sample_requests": 3, "served_logit_gap_mean_limit": 1e-4}},
              open(bench / "workloads" / "new-cell.json", "w"))
    (bench / "layer_metrics" / "engine.queue_depth.py").write_text(
        'NAME = "engine.queue_depth"\nUNIT = "requests"\nLAYER = "serving engine"\n'
        'MOVES = "serve_tokens_per_s"\n\n\ndef read(run):\n    return run["counters"].get("queue_depth_mean")\n')
    manifest = {
        "end_to_end": [{"name": "serve_tokens_per_s"}, {"name": "setup_s"},
                       {"name": "ttft_p95_ms", "workloads": ["some-other-cell"]}],
        "per_layer": [{"name": "engine.queue_depth", "workloads": ["new-cell"]},
                      {"name": "trainer.step_ms", "workloads": ["new-cell"]}],
    }
    json.dump(manifest, open(tmp_path / "BENCHMARK.json", "w"))

    assert "new-cell" in spec.list_cells(str(bench))
    out = bench_run.run_cell("new-cell", 3, 1.0, False, bench_dir=str(bench), allow_cpu=True)
    assert out["correct"] and set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    out = bench_run.run_cell("new-cell", 3, 1.0, True, bench_dir=str(bench), allow_cpu=True)
    # the new reader reports; a reader that finds nothing to read is left out
    assert set(out["metrics"]) == {"engine.queue_depth"}
    after = _mtimes(bench)
    assert all(after[p] == t for p, t in before.items())


@pytest.mark.parametrize("cell", ["tiny-tied-chat", "tiny-tied-train"])
@pytest.mark.parametrize("scale,correct", [(1.0, True), (-1.0, False)])
def test_a_model_kind_is_new_files_only(tmp_path, cell, scale, correct):
    """``data/new_kind``: an adapter and a reference under keys no file of the
    benchmark reads, a configuration of that kind and two cells. With the
    kind's reference as written the cells are correct; with its logits
    scaled by -1 they are not, so that reference is the one consulted."""
    bench = tmp_path / "benchmark"
    shutil.copytree(FIXTURES, bench)
    before = _mtimes(bench)
    new = os.path.join(DATA, "new_kind")
    for sub in ("kinds", "reference", "configs", "workloads"):
        shutil.copytree(os.path.join(new, sub), bench / sub, dirs_exist_ok=True)
    ref = bench / "reference" / "tied.py"
    ref.write_text(ref.read_text().replace("SCALE = 1.0", f"SCALE = {scale}"))
    manifest = {
        "end_to_end": [{"name": "serve_tokens_per_s", "workloads": ["tiny-tied-chat"]},
                       {"name": "train_tokens_per_s_per_chip", "workloads": ["tiny-tied-train"]},
                       {"name": "setup_s"}],
        "per_layer": [],
    }
    json.dump(manifest, open(tmp_path / "BENCHMARK.json", "w"))

    out = bench_run.run_cell(cell, 3, 1.0, False, bench_dir=str(bench), allow_cpu=True)
    assert out["correct"] is correct and len(out["metrics"]) == 2 and out["metrics"]["setup_s"]
    after = _mtimes(bench)
    assert all(after[p] == t for p, t in before.items())
    assert not os.path.exists(os.path.join(spec.BENCH_DIR, "kinds", "tied.py"))


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(f"{'/'.join(str(k.key) for k in path)} {leaf.dtype} {leaf.shape}".encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,want", [
    ("tiny-dense", "73745da9499a3aab88f5bb06d5131e7b2c9bc5fd66735e9eddcb6051676ea81b"),
    ("tiny-moe", "63bfd0435d19bef580a7ccf7eaae11820531a0a2a0119f95fea63c4f83ec071e"),
])
def test_seeded_weights_are_the_parents(name, want):
    """Digests taken at the parent of the PR that moved the trees into
    ``kinds/``: the same seed still builds the same weights, leaf for leaf."""
    cell = spec.load_cell({"tiny-dense": "tiny-chat", "tiny-moe": "tiny-backlog"}[name], FIXTURES)
    assert _digest(models.make_weights(cell.config, 2147483659)) == want


def test_a_leaf_may_ask_for_zeros_or_a_stated_deviation():
    key = models.seed_key(1, "weights")
    assert not np.asarray(models._init_leaf(key, 0, (8,), "zeros", np.float32)).any()
    w = np.asarray(models._init_leaf(key, 1, (256, 256), ("normal", 0.02), np.float32))
    assert abs(w.std() - 0.02) < 1e-3
    with pytest.raises(ValueError):
        models._init_leaf(key, 2, (8,), "uniform", np.float32)
