"""Rehearsal 3: compile the cells' programs for a described ``v5e:2x2`` from
shapes alone and print ``memory_analysis`` of each. Costs no chip time, runs
nothing, and is never reported as a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [cell ...]

For a training cell: the program's step (weights, optimizer state and batch as
shapes) and the reference's float32 loss-and-gradient program. For a serving
cell: the decode step and every prefill program (rows x widths) of the plan.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402

from benchmark.lib import kinds, models, spec, traffic as traffic_lib  # noqa: E402

GIB = 2**30


def report(name: str, compiled) -> None:  # noqa: ANN001
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    print(
        f"{name}: args {m.argument_size_in_bytes / GIB:.2f} GiB, out {m.output_size_in_bytes / GIB:.2f},"
        f" temp {m.temp_size_in_bytes / GIB:.2f}, aliased {m.alias_size_in_bytes / GIB:.2f},"
        f" live {total / GIB:.2f} GiB per chip",
        flush=True,
    )


def shapes_of(config: dict, dtype, sharding_of) -> dict:  # noqa: ANN001
    tree = models.weight_shapes(config)
    return jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(leaf[0], dtype, sharding=s),
        tree, sharding_of, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple),
    )


def train_cell(cell: spec.Cell, topo) -> None:  # noqa: ANN001
    from torchx_tpu.examples import train_llama as tl
    from torchx_tpu.models import llama
    from torchx_tpu.parallel.mesh import BATCH_SPEC, make_mesh
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec

    config, job, dep = cell.config, cell.traffic, cell.config["deployment"]
    batch, seq = int(dep["batch"]), int(job["seq"])
    # the cell's own config (train_cell.job_setup), with the attention that
    # "auto" picks on the TPU named, since this process sees only the CPU
    cfg = models.program_config(config, max_seq=seq, remat_policy=dep["remat_policy"],
                                kernels="reference", attn_impl="splash")
    mesh = make_mesh(parse_mesh_spec(dep["mesh"]), devices=topo.devices[: cell.chips])
    optimizer = tl.make_optimizer(lr=job["lr"], warmup=job["warmup"])
    _, specs_fn = llama.model_fns(cfg)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs_fn(cfg, pp=False))
    dtype = jnp.bfloat16 if config["torch_dtype"] == "bfloat16" else jnp.float32
    params = shapes_of(config, dtype, shardings)
    opt_state = jax.eval_shape(optimizer.init, params)
    # moments follow their parameter's sharding, scalars are replicated
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    flat_p = {tuple(str(k) for k in p): l.sharding for p, l in jax.tree_util.tree_flatten_with_path(params)[0]}

    def place(path, leaf):  # noqa: ANN001
        tail = tuple(str(k) for k in path)
        for n in range(len(tail)):
            if tail[n:] in flat_p:
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=flat_p[tail[n:]])
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=rep)

    opt_state = jax.tree_util.tree_map_with_path(place, opt_state)
    state = tl.TrainState(params=params, opt_state=opt_state,
                          step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    batch_sds = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                                sharding=NamedSharding(mesh, BATCH_SPEC))}
    step = tl.make_train_step(cfg, mesh, optimizer, state_shardings=state_shardings)
    report(f"{cell.name}: program step (batch {batch} x seq {seq})", step.lower(state, batch_sds).compile())

    if cell.chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        p32 = shapes_of(config, jnp.float32, jax.tree.map(lambda _: one, shardings))
        toks = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=one)
        grad = jax.jit(jax.value_and_grad(lambda p, t: kinds.reference(config).mean_nll(p, t, config)))
        report(f"{cell.name}: reference loss and gradients (float32)", grad.lower(p32, toks).compile())


def serve_cell(cell: spec.Cell, topo) -> None:  # noqa: ANN001
    from torchx_tpu.models import generate as gen
    from torchx_tpu.serve import engine as eng

    config, mix, dep = cell.config, cell.traffic, cell.config["deployment"]
    one = SingleDeviceSharding(topo.devices[0])
    cfg = models.program_config(config, max_seq=int(dep["max_seq"]))
    slots, bs = int(dep["max_slots"]), int(dep["block_size"])
    per_slot = -(-cfg.max_seq // bs)
    n_blocks = 1 + slots * max(1, per_slot // 2)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    shard_tree = jax.tree.map(lambda _: one, models.weight_shapes(config),
                              is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    params = shapes_of(config, jnp.bfloat16, shard_tree)
    pool = sds((cfg.n_layers, n_blocks, bs, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    pools = {"k": pool, "v": pool}

    def decode(params, tokens, positions, tables, pools, seeds, temps):  # noqa: ANN001
        return gen.paged_decode_step(params, tokens, positions, tables, pools, cfg,
                                     eng._fold_keys(seeds, positions), temps)

    i32, f32 = jnp.int32, jnp.float32
    c = jax.jit(decode, donate_argnums=(4,)).lower(
        params, sds((slots,), i32), sds((slots,), i32), sds((slots, per_slot), i32), pools,
        sds((slots,), i32), sds((slots,), f32)).compile()
    report(f"{cell.name}: decode step ({slots} slots, {n_blocks} blocks)", c)

    plan = traffic_lib.build_schedule(mix, 1, 45, config["vocab_size"])
    widths = traffic_lib.prefill_widths(plan, mix, bs)
    for rows in (1, 2, 4):
        for w in widths:
            def prefill(params, tokens, pl, sl, tables, pools, seeds, temps):  # noqa: ANN001
                return gen.paged_prefill_chunk(params, tokens, pl, sl, tables, pools, cfg,
                                               eng._fold_keys(seeds, pl + sl - 1), temps)

            c = jax.jit(prefill, donate_argnums=(5,)).lower(
                params, sds((rows, w), i32), sds((rows,), i32), sds((rows,), i32),
                sds((rows, per_slot), i32), pools, sds((rows,), i32), sds((rows,), f32)).compile()
            report(f"{cell.name}: prefill rows {rows} x width {w}", c)


def main() -> None:
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in sys.argv[1:] or spec.list_cells():
        cell = spec.load_cell(name)
        (train_cell if cell.kind == "train" else serve_cell)(cell, topo)


if __name__ == "__main__":
    main()
