"""Compile a training cell's step for a described ``v5e:2x2`` chip from shapes alone and print what it moves
without computing on it, loop by loop, beside ``memory_analysis``. No chip time, nothing runs, never reported
as a chip run (15-45 s).

    JAX_PLATFORMS=cpu python3 scripts/rehearse_train_step.py [mistral7b-train-4k] [--at-least MiB] [--hlo FILE]

The step is the one ``benchmark/rehearse_compile.py::train_cell`` describes (the cell's configuration, splash
named since this process sees only the CPU, weights, optimizer state and batch as shapes on the cell's mesh).
Under its memory: every pure data movement of ``--at-least`` MiB or more (8: a layer's smallest attention
projection at Mistral's widths; ``torchx_tpu/obs/hlo.py::program_moves``: a copy to another layout, a slice out
of a stack, a transpose, a fusion of nothing but those) by the outermost loop that runs it
(``moves_by_loop``: the layers' forward, the loss's chunks, the layers' backward, and what runs in no loop), each
with the MiB it writes, the compiler's own ``estimated_cycles`` for it (a rank, not a time: PERF.md 7.14 c) and
the scope it came from; then MiB a turn and GiB a step for each loop, written and (as much again) read.
``--hlo FILE`` also writes ``compiled.as_text()`` there: the instruction names are the ones a traced run's
``XLA Ops`` carry (``scripts/trace_scopes.py <cell> --ops 'copy|slice'`` times them).
``tests/test_paged_attention_kernel.py`` holds the two layer loops to the list this prints.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark.lib import models, spec  # noqa: E402
from benchmark.rehearse_compile import report as report_memory  # noqa: E402

MIB, GIB = 2**20, 2**30


def compile_step(cell: spec.Cell, devices: list):  # noqa: ANN201
    """``cell``'s training step compiled for ``devices`` (described or real) from shapes: the state as
    ``train/step.py::init_state`` would make it, under ``jax.eval_shape``, whole on the cell's mesh."""
    from torchx_tpu.parallel.mesh import make_mesh
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec
    from torchx_tpu.train import step as tl

    dep, job = cell.config["deployment"], cell.traffic
    batch, seq = int(dep["batch"]), int(job["seq"])
    cfg = models.program_config(cell.config, max_seq=seq, remat_policy=dep["remat_policy"], kernels="reference", attn_impl="splash")  # fmt: skip
    mesh = make_mesh(parse_mesh_spec(dep["mesh"]), devices=devices[: cell.chips])
    whole = NamedSharding(mesh, P())
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole), tree)  # noqa: E731
    optimizer = tl.make_optimizer(lr=job["lr"], warmup=job["warmup"])
    state = on_chip(jax.eval_shape(lambda: tl.init_state(cfg, mesh, optimizer)))
    tokens = on_chip({"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)})
    step = tl.make_train_step(cfg, mesh, optimizer, state_shardings=jax.tree.map(lambda x: x.sharding, state))
    return step.lower(state, tokens).compile()


def print_moves(text: str, at_least: int) -> None:
    from torchx_tpu.obs.hlo import instruction_lines, moves_by_loop

    lines = instruction_lines(text)
    step_bytes = 0
    for loop, found in moves_by_loop(text, at_least).items():
        a_turn, turns = sum(found["moves"].values()), found["turns"]
        step_bytes += a_turn * (turns or 1)
        print(f"  {loop}: {a_turn / MIB:,.0f} MiB written a turn, {turns if turns else 'unknown'} turns,"
              f" {a_turn * (turns or 1) / GIB:.2f} GiB a step")  # fmt: skip
        for inst, size in found["moves"].items():
            line = lines.get(inst, "")
            cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
            scope = re.search(r'op_name="([^"]*)"', line)
            print(f"    {size / MIB:6.0f} MiB  {int(cycles.group(1)) if cycles else 0:>9,} cycles  {inst}"
                  f"  {line.split(' = ')[1].split(' ')[0] if line else ''}  {scope.group(1).split('while/body/')[-1] if scope else ''}")  # fmt: skip
    print(f"  in all: {step_bytes / GIB:.2f} GiB written a step and as much read, {2 * step_bytes / 819e9 * 1e3:.1f} ms at 819 GB/s"
          " if each cost what its bytes cost (not timed)")  # fmt: skip


def main() -> None:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell", nargs="?", default="mistral7b-train-4k")
    ap.add_argument("--at-least", type=float, default=8.0, help="MiB a move writes to be listed")
    ap.add_argument("--hlo", help="write the compiled program's text here")
    args = ap.parse_args()
    cell = spec.load_cell(args.cell)
    if cell.kind != "train":
        raise SystemExit(f"{cell.name} is no training cell: scripts/rehearse_serve_cell.py compiles the serving programs")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    compiled = compile_step(cell, list(topo.devices))
    report_memory(f"{cell.name}: program step", compiled)
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    print(f"  pure data movements of {args.at_least:g} MiB or more, by loop:")
    print_moves(text, int(args.at_least * MIB))


if __name__ == "__main__":
    main()
