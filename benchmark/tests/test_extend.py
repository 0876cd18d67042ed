"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files and edits none: a copy of the benchmark's data directories
gets four new files and a BENCHMARK.json entry, and the harness runs them."""

import json
import os
import shutil

from benchmark import run as bench_run
from benchmark.lib import spec

from .conftest import FIXTURES


def test_new_files_only(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(FIXTURES, bench)
    shutil.copytree(os.path.join(spec.BENCH_DIR, "layer_metrics"), bench / "layer_metrics")
    before = {p: os.path.getmtime(os.path.join(r, p)) for r, _, fs in os.walk(bench) for p in fs}

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
    after = {p: os.path.getmtime(os.path.join(r, p)) for r, _, fs in os.walk(bench) for p in fs}
    assert all(after[p] == t for p, t in before.items())
