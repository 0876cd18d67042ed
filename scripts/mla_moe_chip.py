"""The latent decode kernel and the experts' grouped matmul alone on the chip, and the
served path with and without them.

A one-off measurement (PR 27; the latent kernel's part rewritten by PR 36), not a tool of
the benchmark: no cell runs it. On one TPU

* ``--cell kimi-vl-a3b-serve-backlog | xing4-serve-decode-long`` runs ``paged_mla_pallas``
  and ``paged_mla_attention_xla`` at that cell's shapes, read from its configuration through
  ``benchmark/lib`` (slots, heads, block, table, row, pool blocks, the larger layer group's
  depth), into the group's pool stack with a traced ``layer`` (the last: block ids reach the
  stack's end). Both are held to the same attention in float64 on the host over four sets of
  lengths: every edge of the kernel's bookkeeping (0, 1, 16, 17, 512, 513, 1,024, 1,025, one
  row short of the table, the table), the same in reverse, every slot at the table's end, and
  a ragged draw round the cell's mean. The ragged draw is timed as a loop of calls inside one
  program (a call's output feeds the next call's query, so nothing is hoisted): microseconds a
  call, nanoseconds a 20 KB block, and the rows' bytes over that time as a share of 819 GB/s by
  both counts (1,152 B a row needed, 1,280 B as it lies);
* ``--protocol`` makes 200 calls in one program and 200 programs of one call at the ragged
  draw with the edges among its slots: none may differ from the first, and ``--watchdog-s``
  ends a run that hangs;
* ``--grouped`` compares the grouped matmul the program calls (``ops/grouped_matmul.py``: since PR 46
  the Pallas call ``grouped_matmul_walk``, the layer read out of the stack), ``megablox`` (imported
  from jax for the comparison only, tiled and called as the program did until PR 46) and
  ``jax.lax.ragged_dot`` with float64 and times each: microseconds a call and the bytes of the experts
  that have rows over that time as a share of 819 GB/s (``weights_hbm_pct``), at the rows of a decode
  step and of a step that carries a chunk of a prompt: 384 and 1,920 at ``kimi``'s widths, 512 and 2,560
  with 16 of 128 experts held at ``k-exaone``'s; ``--grouped-only`` skips the latent kernel;
* ``--parity`` serves one prompt through ``ServeEngine`` twice, with the kernels and with
  ``kernel_eligible`` answering no (the XLA functions), and prints for each how far the served
  tokens' logits lie below the reference's best (the numbers the cell's ``correct`` compares).

    chiprun -- python3 scripts/mla_moe_chip.py --cell xing4-serve-decode-long [--protocol]

What each lever of PR 36 gave, nanoseconds a block at ``kimi``'s / ``xing4``'s shapes (my chip
runs, PR 36; 25 is the wire): the parent 56.0 / 59.6; the compiler's bounds check in front of
every copy taken out 41.9 / 44.8; eight copies a group started unrolled and waited for as one
38.9 / 41.7; 1,024 rows a buffer, multiplied 512 at a time over the live parts 33.7 / 37.3. The
copies alone, nothing multiplied, take 31.5 / 32.7; the products alone 23.6 / 26.4. Products
cut into 128-row pieces with the starts between them were slower (59.5 / 70.1): PERF.md section
6, PR 36.

It needs a TPU: a time from the CPU's interpreter says nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CALLS = 32
HBM_BYTES_PER_S = 819e9  # one v5e (benchmark/lib/peaks.py)
CELLS = ("kimi-vl-a3b-serve-backlog", "xing4-serve-decode-long")


def timed(fn, *args) -> float:  # noqa: ANN001
    """Seconds of one call of the jitted ``fn`` after a warm-up."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def cell_shapes(cell: str) -> dict:
    """The latent kernel's shapes in ``cell``, read from the cell's configuration: slots,
    heads, block, table, row, the pool's blocks and the larger layer group's depth."""
    from benchmark.lib import models, spec
    from torchx_tpu.models import generate as gen

    loaded = spec.load_cell(cell)
    config, mix, dep = loaded.config, loaded.traffic, loaded.config["deployment"]
    cfg = models.program_config(config, max_seq=int(dep["max_seq"]))
    slots, bs = int(dep["max_slots"]), int(dep["block_size"])
    bpr = -(-cfg.max_seq // bs)
    return dict(
        cell=cell, slots=slots, h=cfg.n_heads, bs=bs, bpr=bpr, rank=cfg.kv_lora_rank, rope=cfg.qk_rope_dim, width=cfg.cache_width,
        nb=1 + slots * (bpr // 2),  # ServeEngine's default pool, which both cells take
        layers=max(gen.layer_group_sizes(cfg).values()), scale=float(cfg.attn_scale),
        # a slot midway through its answer: the prompt's median and half the answer's
        mean_rows=int(mix["prompt"]["median"] + mix["output"]["median"] // 2), min_rows=int(mix["prompt"]["min"]),
    )  # fmt: skip


def latent_inputs(seed: int, sh: dict):  # noqa: ANN201
    """A layer group's pool stack and the slots' queries, made on the device; the lanes
    behind ``rank + rope`` hold zeros as the program leaves them."""
    import jax
    import jax.numpy as jnp

    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    lanes = jnp.arange(sh["width"]) < sh["rank"] + sh["rope"]
    stack = jax.jit(lambda: jnp.where(lanes, jax.random.normal(
        kp, (sh["layers"], sh["nb"], sh["bs"], sh["width"]), jnp.bfloat16), 0).astype(jnp.bfloat16))()  # fmt: skip
    q = jnp.where(lanes, jax.random.normal(kq, (sh["slots"], sh["h"], sh["width"]), jnp.float32) * 0.5, 0)
    return q.astype(jnp.bfloat16), stack


def tables_for(rng, sh: dict, lengths) -> "np.ndarray":  # noqa: ANN001, F821
    """Block tables in shuffled physical order. Slots share blocks when they hold more than
    the pool has (every slot at ``max_seq``): the kernel only reads. The pool's last block
    is always somebody's, so with the last layer the copies reach the stack's end."""
    import numpy as np

    live = [-(-int(n) // sh["bs"]) for n in lengths]
    ids = rng.permutation(np.arange(1, sh["nb"]))
    ids = np.resize(ids, max(sum(live), 1))
    ids[rng.integers(0, len(ids))] = sh["nb"] - 1
    tables = np.zeros((sh["slots"], sh["bpr"]), np.int32)
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = ids[at : at + n]
        at += n
    return tables


def float64_attention(q, pool, tables, lengths, sh: dict) -> "np.ndarray":  # noqa: ANN001, F821
    """The same attention in float64 on the host over one layer's pool (a numpy array)."""
    import numpy as np

    q64 = np.asarray(q, np.float64)
    want = np.zeros((sh["slots"], sh["h"], sh["rank"]))
    for i, n in enumerate(lengths):
        n = max(int(n), 1)  # a slot of no length attends its first row: nobody reads the answer
        rows = pool[tables[i, : -(-n // sh["bs"])]].astype(np.float64).reshape(-1, sh["width"])[:n]
        s = q64[i] @ rows.T * sh["scale"]
        p = np.exp(s - s.max(-1, keepdims=True))
        want[i] = (p / p.sum(-1, keepdims=True)) @ rows[:, : sh["rank"]]
    return want


def edge_lengths(rng, sh: dict) -> dict:  # noqa: ANN001
    """The length sets the kernel is held to float64 over: every edge of its bookkeeping
    (a slot of no length and of one row, a block's, a chunk's and the table's edges), every
    slot at the table's end, and the cell's own ragged draw."""
    import numpy as np

    top = sh["bpr"] * sh["bs"]
    edges = np.minimum([0, 1, 16, 17, 512, 513, 1024, 1025, top - 1, top], top)
    lo, hi = sh["min_rows"], 2 * sh["mean_rows"] - sh["min_rows"]
    return {
        "edges": np.resize(edges, sh["slots"]),
        "edges_reversed": np.resize(edges[::-1], sh["slots"]),
        "all_at_the_tables_end": np.full(sh["slots"], top),
        "ragged": rng.integers(lo, hi, sh["slots"]),
    }


def attention(rng, sh: dict, qd, stack, calls: int = CALLS) -> dict:  # noqa: ANN001
    """``paged_mla_pallas`` alone at a cell's shapes (``cell_shapes``), into the stack of the
    cell's larger layer group (``latent_inputs``) with a traced ``layer``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import paged_mla as pm
    from torchx_tpu.ops import paged_mla_kernel as pmk

    rank, scale, row = sh["rank"], sh["scale"], sh["rank"] + sh["rope"]
    layer = jnp.int32(sh["layers"] - 1)
    pool = np.asarray(stack[sh["layers"] - 1].astype(jnp.float32))
    fns = {"pallas": pmk.paged_mla_pallas, "xla": pm.paged_mla_attention_xla}
    one = {name: jax.jit(lambda q, p, t, n, i, fn=fn: fn(q, p, t, n, rank, scale, layer=i)) for name, fn in fns.items()}
    out = {k: sh[k] for k in ("cell", "slots", "h", "bpr", "nb", "layers")}
    for case, lengths in edge_lengths(rng, sh).items():
        tables = tables_for(rng, sh, lengths)
        want = float64_attention(qd, pool, tables, lengths, sh)
        td, ld = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
        keep = np.asarray(lengths) > 0
        for name in fns:
            got = np.asarray(one[name](qd, stack, td, ld, layer), np.float64)
            out[f"{case}.{name}_max_err"] = float(np.abs(got - want)[keep].max())
            out[f"{case}.{name}_finite"] = bool(np.isfinite(got).all())
    # the last case is the cell's ragged draw: time that one
    blocks = int(sum(-(-int(n) // sh["bs"]) for n in lengths))
    out.update(rows_held=int(lengths.sum()), blocks_held=blocks)
    for name, fn in fns.items():

        def loop(q, p, t, n, i, fn=fn):  # noqa: ANN001, ANN202
            def body(_, q):  # noqa: ANN001, ANN202
                o = fn(q, p, t, n, rank, scale, layer=i)
                return q.at[..., :rank].set(o)

            return jax.lax.fori_loop(0, calls, body, q)

        s = timed(jax.jit(loop), qd, stack, td, ld, layer) / calls
        out[f"{name}_us"] = s * 1e6
        if name == "pallas":
            out["pallas_ns_a_block"] = s * 1e9 / blocks
        for label, width in (("needed", row), ("as_laid_out", sh["width"])):
            out[f"{name}_hbm_pct_{label}"] = 100.0 * lengths.sum() * width * 2 / HBM_BYTES_PER_S / s
    return out


def protocol(rng, sh: dict, qd, stack, calls: int = 200) -> dict:  # noqa: ANN001
    """``calls`` calls in one program and ``calls`` programs of one call at the cell's
    ragged draw: none may differ from the first (or hang: the caller's watchdog)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import paged_mla_kernel as pmk

    rank, scale = sh["rank"], sh["scale"]
    lengths = edge_lengths(rng, sh)["ragged"]
    top = sh["bpr"] * sh["bs"]
    every = lengths[:: max(1, sh["slots"] // 8)]  # the edges among them, at a few slots
    every[:] = np.minimum(np.resize([0, 1, 17, 513, top, 512, 16, 1025], len(every)), top)
    td, ld = jnp.asarray(tables_for(rng, sh, lengths)), jnp.asarray(lengths, jnp.int32)
    layer = jnp.int32(sh["layers"] - 1)
    one = jax.jit(lambda q, p, t, n, i: pmk.paged_mla_pallas(q, p, t, n, rank, scale, layer=i))
    first = np.asarray(one(qd, stack, td, ld, layer), np.float32)

    def many(q, p, t, n, i):  # noqa: ANN001, ANN202
        def body(_, same):  # noqa: ANN001, ANN202
            o = pmk.paged_mla_pallas(q, p, t, n, rank, scale, layer=i)
            return same & jnp.array_equal(o.astype(jnp.float32), first)

        return jax.lax.fori_loop(0, calls, body, jnp.bool_(True))

    in_one_program = bool(jax.jit(many)(qd, stack, td, ld, layer))
    one_a_program = all(np.array_equal(np.asarray(one(qd, stack, td, ld, layer), np.float32), first) for _ in range(calls))
    return {"cell": sh["cell"], "calls": calls, "same_in_one_program": in_one_program, "same_one_a_program": one_a_program,
            "finite": bool(np.isfinite(first).all())}  # fmt: skip


#: the grouped matmul's shapes in the two cells whose steps differ most: (k, n) of the gate / up projection (the
#: down projection is its transpose), experts held of experts published, rows of a decode step and of a step
#: that carries a chunk of 256 (``(slots + 256) x picks``)
GROUPED_SHAPES = {
    "kimi": dict(k=2048, n=1408, held=64, experts=64, rows=(384, 1920)),
    "k-exaone": dict(k=6144, n=2048, held=16, experts=128, rows=(512, 2560)),
}


def _megablox_tiling(k: int, n: int) -> tuple[int, int, int]:
    """What ``ops/grouped_matmul.py::_tiling`` gave megablox until PR 46 at these rows: a row tile of
    128 and the largest ``[tk, tn]`` under 3 MiB, the wider of two of one size."""
    tiles = lambda x: [t for t in range(128, x + 1, 128) if x % t == 0]  # noqa: E731
    _, tn, tk = max((tk * tn, tn, tk) for tk in tiles(k) for tn in tiles(n) if tk * tn * 2 <= 3 * 1024 * 1024)
    return 128, tk, tn


def grouped(rng, name: str, m: int, k: int, n: int, held: int, experts: int) -> dict:  # noqa: ANN001
    """The kernel the program calls, megablox (imported from jax for this comparison only) and
    ``jax.lax.ragged_dot`` at one shape: each against float64 and timed, with the bytes of the
    experts that have rows over the time as a share of the wire."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from torchx_tpu.ops import grouped_matmul as gm
    from torchx_tpu.ops.attention import traced

    L = 2
    w = jnp.asarray(rng.standard_normal((L, held, k, n), dtype=np.float32) * k**-0.5, jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((m, k), dtype=np.float32), jnp.bfloat16)
    picks = np.sort(rng.integers(0, experts, m))  # the rows of experts held elsewhere lie behind the last group
    sizes = jnp.asarray(np.bincount(picks, minlength=experts)[:held], jnp.int32)
    here = int(np.asarray(sizes).sum())
    x64, w64 = np.asarray(x, np.float64), np.asarray(w[1], np.float64)
    rows = min(here, 512)  # float64 on the host: the first rows are enough
    want = np.stack([x64[r] @ w64[picks[r]] for r in range(rows)])
    layer = jnp.int32(1)
    reached = int((np.asarray(sizes) > 0).sum())
    out = {"shape": name, "rows": m, "rows_here": here, "groups_with_rows": reached, "tiling": gm._tiling(m, k, n, 2, experts)}
    every = jnp.zeros((L * held,), jnp.int32)  # megablox as the program called it: every layer's groups, all empty but one's
    fns = {
        "kernel": lambda x, w, s: gm.grouped_matmul(x, w, s, layer, spread_over=experts),
        "megablox": lambda x, w, s: gmm(
            x, w.reshape(L * held, k, n), jax.lax.dynamic_update_slice(every, s, (layer * held,)),
            preferred_element_type=x.dtype, tiling=_megablox_tiling(k, n),
        ),
        "ragged_dot": lambda x, w, s: jax.lax.ragged_dot(x, w[1], s),
    }  # fmt: skip
    valid = (jnp.arange(m) < here)[:, None]  # a row of no group is whatever the kernel's buffer held
    for impl, fn in fns.items():
        got = np.asarray(jax.jit(fn)(x, w, sizes), np.float64)[:rows]
        out[f"{impl}_max_err"] = float(np.abs(got - want).max())

        def loop(x, w, s, fn=fn):  # noqa: ANN001, ANN202
            return jax.lax.fori_loop(0, CALLS, lambda _, x: x + jnp.where(valid, fn(x, w, s)[:, :1], 0) * 1e-6, x)

        us = timed(jax.jit(loop), x, w, sizes) / CALLS * 1e6
        out[f"{impl}_us"] = us
        out[f"{impl}_weights_hbm_pct"] = 100.0 * reached * k * n * 2 / HBM_BYTES_PER_S / (us * 1e-6)
        out[f"{impl}_mxu_pct"] = 100.0 * 2 * here * k * n / 197e12 / (us * 1e-6)
    out["kernel_is"] = traced("grouped_matmul")
    return out


def parity(seed: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import kinds, models, spec
    from torchx_tpu.ops import grouped_matmul as gm
    from torchx_tpu.ops import paged_mla as pm
    from torchx_tpu.serve.engine import ServeEngine

    config = spec.load_cell(CELLS[0]).config
    cfg = models.program_config(config, max_seq=1024)
    params = models.make_weights(config, seed)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, config["vocab_size"], 700).tolist()
    ref = kinds.reference(config)
    out = {}
    for name in ("kernels", "xla"):
        if name == "xla":
            pm.kernel_eligible = lambda *a, **k: False
            gm.kernel_eligible = lambda *a, **k: False
        engine = ServeEngine(params, cfg, max_slots=8, block_size=16, max_prefill_batch=2).start()
        try:
            req = engine.generate(prompt, 200, timeout=1200)
        finally:
            engine.stop()
        del engine
        seq = prompt + req.generated
        toks = jnp.asarray([seq + [0] * (1024 - len(seq))], jnp.int32)
        lg = ref.logits(params, toks, config)[0, len(prompt) - 1 : len(prompt) - 1 + 200]
        got = jnp.take_along_axis(lg, jnp.asarray(req.generated)[:, None], axis=-1)[:, 0]
        gaps = np.asarray(lg.max(-1) - got)
        first = int(np.argmax(gaps > 0)) if (gaps > 0).any() else -1
        out[name] = {"gap_mean": float(gaps.mean()), "gap_max": float(gaps.max()), "differ": int((gaps > 0).sum()),
                     "first_differing_position": first, "gap_mean_before_it": float(gaps[: max(first, 0)].mean()) if first > 0 else 0.0}  # fmt: skip
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=27)
    ap.add_argument("--cell", choices=CELLS, default=CELLS[0], help="whose shapes the latent kernel is run at")
    ap.add_argument("--protocol", action="store_true", help="200 calls in one program and 200 programs of one call")
    ap.add_argument("--grouped", action="store_true", help="the grouped matmul too (kimi's and k-exaone's widths)")
    ap.add_argument("--grouped-only", action="store_true", help="the grouped matmul and nothing else")
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--watchdog-s", type=int, default=1500, help="exit if the whole run takes longer: a kernel that hangs")
    args = ap.parse_args()
    import faulthandler

    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}")
        return 1
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    setup_compilation_cache()
    faulthandler.dump_traceback_later(args.watchdog_s, exit=True)
    rng = np.random.default_rng(args.seed)
    if not args.grouped_only:
        sh = cell_shapes(args.cell)
        qd, stack = latent_inputs(args.seed, sh)
        print(json.dumps({"device_kind": dev.device_kind, "attention": attention(rng, sh, qd, stack)}), flush=True)
        if args.protocol:
            print(json.dumps({"protocol": protocol(rng, sh, qd, stack)}), flush=True)
        del qd, stack
    if args.grouped or args.grouped_only:
        for name, sh in GROUPED_SHAPES.items():
            for m in sh["rows"]:
                for k, n in ((sh["k"], sh["n"]), (sh["n"], sh["k"])):  # gate / up, then down
                    print(json.dumps({"grouped": grouped(rng, name, m, k, n, sh["held"], sh["experts"])}), flush=True)
    if args.parity:
        print(json.dumps({"parity": parity(args.seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
