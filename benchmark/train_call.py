"""Time the program's own ``train()`` on a training cell's job, once.

A training cell's window drives the program's step, state and feed in a loop
of the benchmark's own, two steps in flight (``lib/train_cell.py`` says why:
``train()`` hands back no state and takes no seed). ``train()`` itself fences
every step (``log_every=1``), logs, and builds its state through
``init_state``. This prints what it reaches on the cell's job, so that PERF.md
can record the gap between the two loops. No run of the benchmark calls it.

    python3 benchmark/train_call.py <train cell>
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = 34  # 4 of them are train()'s own untimed ones


def time_train(workload: str, steps: int = STEPS, bench_dir: str | None = None, allow_cpu: bool = False) -> dict:
    from benchmark.lib import device, models, spec, train_cell
    from torchx_tpu.examples import train_llama as tl
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec

    cell = spec.load_cell(workload, bench_dir or spec.BENCH_DIR)
    dev = device.require_chips(cell.chips, allow_cpu)
    job, dep = cell.traffic, cell.config["deployment"]
    seq = int(job["seq"])
    cfg = models.program_config(cell.config, max_seq=seq, remat_policy=dep["remat_policy"],
                                kernels="reference")
    tokens_path = os.path.join(spec.scratch_dir(cell), "tokens.bin")
    train_cell.write_tokens(tokens_path, 1, int(job["corpus_tokens"]), cell.config["vocab_size"])
    res = tl.train(cfg, parse_mesh_spec(dep["mesh"]), int(dep["batch"]), seq, steps,
                   lr=job["lr"], warmup=job["warmup"], data_path=tokens_path)
    keep = ("tokens_per_sec_per_chip", "step_time_s", "data_wait_frac", "launch_breakdown",
            "launch_to_first_step_s", "loss")
    return {"device": dev, "steps": steps, **{k: res[k] for k in keep}}


if __name__ == "__main__":
    print("train_call:", json.dumps(time_train(sys.argv[1])), flush=True)
