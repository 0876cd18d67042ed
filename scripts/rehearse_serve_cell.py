"""Compile a serving cell's programs for a described ``v5e:2x2`` chip from shapes alone,
whatever pools its model kind has, and print ``memory_analysis`` of each. No chip time,
nothing runs, never reported as a chip run.

    JAX_PLATFORMS=cpu python3 scripts/rehearse_serve_cell.py kimi-vl-a3b-serve-backlog [chunk width ...]

The two programs an engine has since PR 40: the decode step, and the decode step that
carries a chunk of a prompt (``ServeEngine``'s ``_decode`` and ``_decode_chunk``, written out
here as the engine writes them), the second at the engine's default ``chunk_width`` or at
each width given.

``benchmark/rehearse_compile.py::serve_cell`` builds K/V pools by hand and so cannot
describe a latent pool; this makes the engine's own cache of the cell's kind under ``jax.eval_shape``
(``serve/slot_cache.py``) and takes from it the pools' shapes and the ``tables`` the programs are handed: K/V pools,
a pool a cache kind (``k-exaone-serve-decode-long``), or a latent pool a layer group at 64 slots
(``kimi-vl-a3b-serve-backlog``) or 128 (``xing4-serve-decode-long``: 16,897 blocks x 8 layers x
1,280 B, 2.58 GiB beside 10.55 GiB of weights; decode 13.14 GiB live), and beside the K/V pools a
state-space mixer's store a slot (``falcon-h1-serve-decode-long``: 65 rows x 6 layers x 4.2 MB), a store beside pools
of fewer layers (``qwen3-next-serve-decode-long``: 129 rows x 6 linear layers x 2.1 MB beside the two attending layers'
16,897 blocks, the attention's weights under ``params["mixers"]`` and not in the layers' stack), or one K/V pool
whose rows are not tokens (``evabyte-serve-decode-long``: tables of 176 entries and 8 staging blocks a slot beside them).
Under each program it prints what its layer loop moves of a layer's pool size or more
(``torchx_tpu/obs/hlo.py::loop_moves``: nothing, since the pools ride the scan's carry),
and every pure data movement anywhere in the program of the size of a layer's smallest
attention projection or more (``program_moves``: since PR 32 no weight among them, the
projections are multiplied where they lie).
The process sees only the CPU, so the backend question every kernel's
``kernel_eligible`` asks is answered "tpu" here, as the chip would answer it.
"""

from __future__ import annotations

import inspect
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark.lib import models, spec  # noqa: E402
from benchmark.rehearse_compile import report as report_memory, shapes_of  # noqa: E402


KERNELS = ("paged_mla_decode", "paged_attention_decode", "gmm", "ssm_step", "gdn_step")


def main() -> None:
    from jax.experimental import topologies

    from torchx_tpu.models import generate as gen
    from torchx_tpu.models import llama
    from torchx_tpu.obs.hlo import loop_moves, program_moves
    from torchx_tpu.serve import engine as eng
    from torchx_tpu.serve.slot_cache import slot_cache

    cell = spec.load_cell(sys.argv[1])
    widths = [int(a) for a in sys.argv[2:]] or [inspect.signature(eng.ServeEngine).parameters["chunk_width"].default]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # what kernel_eligible will be told on the chip
    config, mix, dep = cell.config, cell.traffic, cell.config["deployment"]
    cfg = models.program_config(config, max_seq=int(dep["max_seq"]))
    slots, bs = int(dep["max_slots"]), int(dep["block_size"])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)  # noqa: E731
    params = shapes_of(config, jnp.bfloat16, jax.tree.map(lambda _: one, models.weight_shapes(config), is_leaf=is_leaf))
    # the engine's own cache of the cell's kind at its default pools, made abstractly: its geometry, the pools' shapes,
    # and the pytrees it hands the programs as ``tables`` (numpy on the host; only their shapes are used here)
    made = {}

    def cache_pools():  # noqa: ANN202
        made["cache"] = slot_cache(cfg, max_slots=slots, block_size=bs, num_blocks=None, num_window_blocks=None,
                                   max_prefill_batch=int(dep["max_prefill_batch"]), prefix_cache=True, prefix_cache_reserve=0.0)  # fmt: skip
        return made["cache"].pools

    pools = jax.tree.map(lambda p: sds(p.shape, p.dtype), jax.eval_shape(cache_pools))
    cache = made["cache"]
    n_blocks, n_window, ring = cache.num_blocks, cache.num_window_blocks, getattr(cache, "window_ring", 0)
    as_shapes = lambda tables: jax.tree.map(lambda t: sds(t.shape, t.dtype), tables)  # noqa: E731
    i32, f32 = jnp.int32, jnp.float32

    # a layer's smallest pool; a mixer's convolution tails (a few MB a layer) are no pool's size
    layer_bytes = min(p.size // p.shape[0] * p.dtype.itemsize
                      for name, p in jax.tree_util.tree_leaves_with_path(pools) if "conv" not in jax.tree_util.keystr(name))
    stacks = [params[group] for group in llama.layer_groups(params)] + list(params.get("mixers", {}).values())
    projection_bytes = min(w.size // w.shape[0] * w.dtype.itemsize for stack in stacks
                           for name, w in stack.items() if name in ("wq", "wk", "wv", "wo", "w_qa", "w_qb", "w_kva", "w_kvb", "w_uk", "w_uv"))  # fmt: skip

    def report(name, compiled):  # noqa: ANN001, ANN202
        report_memory(name, compiled)
        text = compiled.as_text()
        print(f"  moves of a layer's pool ({layer_bytes / 2**20:.0f} MiB) or more inside a loop:",
              loop_moves(text, layer_bytes) or "none", flush=True)  # fmt: skip
        moves = program_moves(text, projection_bytes)
        print(f"  pure data movements of a layer's smallest attention projection ({projection_bytes / 2**20:.1f} MiB) or more,"
              f" anywhere in the program: {len(moves) or 'none'}", *moves, sep="\n    ", flush=True)  # fmt: skip

    def decode(params, tokens, prev, positions, tables, pools, seeds, temps):  # noqa: ANN001
        tokens = jnp.where(tokens == eng._FROM_DEVICE, prev, tokens)  # as ServeEngine's _decode merges them
        return gen.paged_decode_step(params, tokens, positions, tables, pools, cfg,
                                     eng._fold_keys(seeds, positions), temps)

    def decode_chunk(params, tokens, prev, positions, tables, pools, seeds, temps, chunk, at, chunk_tables):  # noqa: ANN001
        tokens = jnp.where(tokens == eng._FROM_DEVICE, prev, tokens)  # as ServeEngine's _decode_chunk does
        keys = eng._fold_keys(seeds, jnp.append(positions, at[0] + at[1] - 1))
        sampled, pools = gen.paged_decode_chunk_step(params, tokens, positions, tables, chunk, at[0], at[1],
                                                     chunk_tables, pools, cfg, keys, temps)
        return jnp.where(jnp.arange(slots) == at[2], sampled[-1], sampled[:-1]), pools

    geometry = f"{slots} slots, {n_blocks} blocks" + (f", {n_window} window blocks in rings of {ring}" if n_window else "")
    slot_args = (sds((slots,), i32), sds((slots,), i32), sds((slots,), i32), as_shapes(cache.step_tables([], [])), pools)
    c = jax.jit(decode, donate_argnums=(5,)).lower(params, *slot_args, sds((slots,), i32), sds((slots,), f32)).compile()
    report(f"{cell.name}: decode step ({geometry})", c)
    print("  kernels:", sorted({n for n in KERNELS if n in c.as_text()}))
    for width in widths:
        c = jax.jit(decode_chunk, donate_argnums=(5,)).lower(
            params, *slot_args, sds((slots + 1,), i32), sds((slots + 1,), f32),
            sds((width,), i32), sds((3,), i32), as_shapes(cache.chunk_tables(0))).compile()
        report(f"{cell.name}: decode step carrying a chunk of {width} ({geometry})", c)
        print("  kernels:", sorted({n for n in KERNELS if n in c.as_text()}))


if __name__ == "__main__":
    main()
