"""The decode attention kernel alone on the chip: is it right, and how fast.

A one-off measurement (PR 25), not a tool of the benchmark. On one TPU it

* compares ``paged_attention_pallas`` and ``paged_attention_xla`` with the
  same attention in float64 on the host, on random pools at several shapes
  (heads, block size, dtype, ragged lengths, an inactive slot): the largest
  absolute error of each;
* times both at the shapes the serving cells lower (chat and Mixtral: 16
  slots x 32 heads over 8; ``falcon-h1``: 64 slots x 20 heads over 4 cache
  heads at ~1.45 k tokens a slot; ``k-exaone``: 64 slots x 64 heads over 8,
  its full layers' tables of 264 blocks and its sliding layers' rings of 10
  under a window of 128; ``evabyte``: 32 slots x 32 heads over 32 cache heads,
  tables of 176 blocks, ~1.4 k rows a slot), and at a window held full, each as a loop of calls
  inside one program (the output of a call is the next call's query, so
  nothing is hoisted), and prints the live K/V bytes a call must read over
  its time as a share of the device's published HBM bandwidth, and the
  nanoseconds a live block's pair of copies (one of K, one of V) costs;
* with ``--chunk-kib``, ``--part-rows`` or ``--group-kib``, times the kernel
  at other geometries (``--shapes`` picks which shapes).

    chiprun -- python3 scripts/paged_attention_chip.py

To time another checkout's kernel with this script (a parent commit unpacked
under ``.scratch/``): ``--repo .scratch/parent``.

It needs a TPU: a time from the CPU's interpreter says nothing.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CALLS = 64  # kernel calls inside one timed program
HBM_BYTES_PER_S = 819e9  # one v5e, Google Cloud's "TPU v5e" page (benchmark/lib/peaks.py)


def make(rng, slots, h, kvh, hd, bs, bpr, lengths, dtype):  # noqa: ANN001, ANN201
    """Random query, pools and disjoint block tables for ``lengths``; table
    entries past a slot's live blocks are the trash block, as the engine's are."""
    import jax.numpy as jnp
    import numpy as np

    live = [-(-int(n) // bs) for n in lengths]
    nb = 1 + sum(live)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((slots, bpr), np.int32)
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = perm[at : at + n]
        at += n
    k = rng.standard_normal((nb, bs, kvh, hd), dtype=np.float32)
    v = rng.standard_normal((nb, bs, kvh, hd), dtype=np.float32)
    q = rng.standard_normal((slots, h, hd), dtype=np.float32)
    as_dev = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    return as_dev(q), as_dev(k), as_dev(v), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--chunk-kib", type=int, nargs="*", default=[], help="other chunk sizes to time")
    ap.add_argument("--part-rows", type=int, nargs="*", default=[], help="other part sizes to time")
    ap.add_argument("--group-kib", type=int, nargs="*", default=[], help="other group sizes to time")
    ap.add_argument("--shapes", nargs="*", default=None, help="the shapes to time (default: all)")
    ap.add_argument("--repo", default=REPO, help="the checkout whose kernel is checked and timed")
    ap.add_argument("--no-check", action="store_true", help="skip the comparison with float64")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import paged_attention as pa
    from torchx_tpu.ops import paged_attention_kernel as pk

    attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"paged_attention_chip: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}), flush=True)
    rng = np.random.default_rng(args.seed)

    def exact(q, k, v, tables, lengths):  # noqa: ANN001, ANN202
        """The same attention in float64 on the host, a slot and a head at a time."""
        q, k, v = (np.asarray(x.astype(jnp.float32), np.float64) for x in (q, k, v))
        out = np.zeros_like(q)
        group = q.shape[1] // k.shape[2]
        for i, n in enumerate(np.asarray(lengths)):
            ks = k[np.asarray(tables)[i]].reshape(-1, *k.shape[2:])[:n]  # [n, kvh, hd]
            vs = v[np.asarray(tables)[i]].reshape(-1, *v.shape[2:])[:n]
            for head in range(q.shape[1]):
                s = ks[:, head // group] @ q[i, head] * q.shape[2] ** -0.5
                p = np.exp(s - s.max())
                out[i, head] = (p / p.sum()) @ vs[:, head // group]
        return out

    def difference(name, slots, h, kvh, hd, bs, bpr, lengths, dtype):  # noqa: ANN001, ANN202
        a = make(rng, slots, h, kvh, hd, bs, bpr, lengths, dtype)
        assert pa.kernel_eligible(a[0].shape, a[1].shape, a[0].dtype, a[1].dtype, "tpu"), name
        want = exact(*a)
        err = lambda fn: float(np.abs(np.asarray(jax.jit(fn)(*a).astype(jnp.float32), np.float64) - want).max())  # noqa: E731
        row = {"check": name, "dtype": jnp.dtype(dtype).name, "pallas_max_abs_err": err(pk.paged_attention_pallas),
               "xla_max_abs_err": err(pa.paged_attention_xla), "exact_abs_max": float(np.abs(want).max())}  # fmt: skip
        print(json.dumps(row), flush=True)

    ragged = [1, 15, 16, 17, 255, 256, 257, 700, 1024, 4096, 1, 33, 512, 513, 2047, 3000]
    for dtype in () if args.no_check else (jnp.bfloat16, jnp.float32):
        difference("h32.kvh8.bs16", 16, 32, 8, 128, 16, 256, ragged, dtype)
        difference("h8.kvh8.bs16", 16, 8, 8, 128, 16, 256, ragged, dtype)
        difference("h20.kvh4.bs16", 16, 20, 4, 128, 16, 264, ragged, dtype)
        difference("h64.kvh8.bs32", 16, 64, 8, 128, 32, 128, ragged, dtype)
        difference("h32.kvh16.bs8", 4, 32, 16, 128, 8, 64, [1, 9, 100, 512], dtype)
        difference("h32.kvh32.bs16", 8, 32, 32, 128, 16, 176, [1, 15, 16, 17, 255, 700, 2047, 2816], dtype)

    def timed(fn, a):  # noqa: ANN001, ANN202
        @jax.jit
        def loop(q, *pools_and_tables):  # noqa: ANN001, ANN202
            return jax.lax.fori_loop(0, CALLS, lambda _, q: fn(q, *pools_and_tables), q)

        loop(*a).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            loop(*a).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / CALLS

    def ring_of(a, window, ring, bs):  # noqa: ANN001, ANN202
        """``a`` with each slot's table cut to the ring a sliding layer keeps: block ``b`` at entry ``b % ring``."""
        q, k, v, tables, lengths = a
        rings = np.zeros((tables.shape[0], ring), np.int32)
        for i, n in enumerate(np.asarray(lengths)):
            for b in range(max(0, int(n) - window) // bs, -(-int(n) // bs)):
                rings[i, b % ring] = np.asarray(tables)[i, b]
        return q, k, v, jnp.asarray(rings), lengths

    def speed(name, slots, h, kvh, bpr, lengths, window=0, geometry=None, xla=True):  # noqa: ANN001, ANN202
        bs, hd = 16, 128
        a = make(rng, slots, h, kvh, hd, bs, bpr, lengths, jnp.bfloat16)
        pairs = sum(-(-int(n) // bs) for n in lengths)
        kernel, reference = pk.paged_attention_pallas, pa.paged_attention_xla
        if window:
            a = ring_of(a, window, 10, bs)
            pairs = sum(-(-int(n) // bs) - max(0, int(n) - window) // bs for n in lengths)
            kernel = lambda *x: pk.paged_attention_pallas(*x, window=window)  # noqa: E731
            reference = lambda *x: pa.paged_attention_xla(*x, None, window)  # noqa: E731
        live_bytes = pairs * bs * kvh * hd * 2 * 2
        row = {"shape": name, "slots": slots, "heads": f"{h}/{kvh}", "table_blocks": int(a[3].shape[1]),
               "tokens_held": int(sum(lengths)), "block_pairs": pairs, "live_kv_bytes": live_bytes}  # fmt: skip
        for knob, size in (geometry or {}).items():
            setattr(pk, knob, size)
            row[knob] = size
        t = timed(kernel, a)
        row["pallas_us"] = t * 1e6
        row["pallas_ns_per_block_pair"] = t * 1e9 / pairs
        row["pallas_hbm_share_pct"] = 100.0 * live_bytes / t / HBM_BYTES_PER_S
        if said := attn_ops.traced("paged_geometry"):  # nothing from a kernel older than PR 42
            row["geometry"] = said
            attn_ops.TRACED.pop("paged_geometry")
        if xla:
            row["xla_us"] = timed(reference, a) * 1e6
        print(json.dumps(row), flush=True)

    # chat: 16 slots x 4096, about 11 held by prompts of 288-896 plus answers; backlog: 16 x 2048, all held;
    # falcon-h1 and k-exaone: 64 slots of the reasoning mix, ~1.45 k and ~1.6 k tokens a slot as their traces read
    chat = [int(x) for x in rng.integers(300, 1100, 11)] + [1] * 5
    backlog = [int(x) for x in rng.integers(100, 760, 16)]
    reasoning = [int(x) for x in rng.integers(300, 2600, 64)]
    # evabyte: 32 slots whose rows are a window's (1-2,048) behind 128 pooled rows a finished window (1-5 of them)
    eva_rows = [int(128 * w + r) for w, r in zip(rng.integers(1, 6, 32), rng.integers(1, 2049, 32))]
    shapes = {
        "chat": (16, 32, 8, 256, chat, 0),
        "backlog": (16, 32, 8, 128, backlog, 0),
        "chat.full_window": (16, 32, 8, 256, [4096] * 16, 0),
        "falcon-h1": (64, 20, 4, 264, reasoning, 0),
        "k-exaone.full": (64, 64, 8, 264, reasoning, 0),
        "k-exaone.ring": (64, 64, 8, 264, reasoning, 128),
        "evabyte": (32, 32, 32, 176, eva_rows, 0),  # one query head a cache head: blocks of 128 KiB of K
    }
    shapes = {k: v for k, v in shapes.items() if args.shapes is None or k in args.shapes}
    for name, shape in shapes.items():
        speed(name, *shape, xla=shape[0] == 16)  # at 64 slots the XLA function gathers gigabytes: not timed
    sweeps = {"_CHUNK_BYTES": [k * 1024 for k in args.chunk_kib], "_PART_ROWS": args.part_rows,
              "_GROUP_BYTES": [k * 1024 for k in args.group_kib]}  # fmt: skip
    for knob, sizes in sweeps.items():
        default = getattr(pk, knob)
        for size in sizes:
            for name, shape in shapes.items():
                speed(name, *shape, geometry={knob: size}, xla=False)
        setattr(pk, knob, default)
    return 0


if __name__ == "__main__":
    sys.exit(main())
