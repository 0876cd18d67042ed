"""The two new kernels alone on the chip, and the served path with and without them.

A one-off measurement (PR 27), not a tool of the benchmark. On one TPU, at the widths
of ``kimi-vl-a3b-serve-backlog``, it

* compares ``paged_mla_pallas`` and ``paged_mla_attention_xla`` with the same
  attention in float64 on the host (64 slots of ragged lengths near 2 k rows), and
  times both as a loop of calls inside one program (a call's output feeds the next
  call's query, so nothing is hoisted): microseconds a call, and the latent bytes
  the slots hold (1,152 B a row) over that time as a share of 819 GB/s;
* compares the Pallas grouped matmul (``megablox``, as ``ops/grouped_matmul.py``
  tiles it, the layer read out of the stack) and ``jax.lax.ragged_dot`` with float64
  at decode's 384 rows and at a prefill's 12,288, and times both;
* with ``--parity``, serves one prompt through ``ServeEngine`` twice, with the kernels
  and with ``kernel_eligible`` answering no (the XLA functions), and prints for each
  how far the served tokens' logits lie below the reference's best (the numbers the
  cell's ``correct`` compares), so that a fault in a kernel shows as a difference
  between the two.

    chiprun -- python3 scripts/mla_moe_chip.py [--parity]

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
CELL = "kimi-vl-a3b-serve-backlog"


def timed(fn, *args) -> float:  # noqa: ANN001
    """Seconds of one call of the jitted ``fn`` after a warm-up."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def attention(rng) -> dict:  # noqa: ANN001
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import paged_mla as pm
    from torchx_tpu.ops import paged_mla_kernel as pmk

    slots, h, bs, bpr, rank, rope, width = 64, 16, 16, 264, 512, 64, 640
    lengths = rng.integers(1300, 3400, slots)
    live = [-(-int(n) // bs) for n in lengths]
    nb = 1 + sum(live)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((slots, bpr), np.int32)
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = perm[at : at + n]
        at += n
    pool = rng.standard_normal((nb, bs, width), dtype=np.float32)
    q = rng.standard_normal((slots, h, width), dtype=np.float32) * 0.5
    pool[..., rank + rope :] = 0.0
    q[..., rank + rope :] = 0.0
    scale = 192**-0.5
    dev = lambda x: jnp.asarray(x, jnp.bfloat16)  # noqa: E731
    qd, pd, td, ld = dev(q), dev(pool), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    q64, p64 = np.asarray(qd, np.float64), np.asarray(pd, np.float64)
    want = np.zeros((slots, h, rank))
    for i, n in enumerate(lengths):
        rows = p64[tables[i, : live[i]]].reshape(-1, width)[:n]
        s = q64[i] @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        want[i] = (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]
    out = {"rows_held": int(lengths.sum()), "blocks": nb}
    for name, fn in (("pallas", pmk.paged_mla_pallas), ("xla", pm.paged_mla_attention_xla)):
        one = jax.jit(lambda q, p, t, n, fn=fn: fn(q, p, t, n, rank, scale))
        out[f"{name}_max_err"] = float(np.abs(np.asarray(one(qd, pd, td, ld), np.float64) - want).max())

        def loop(q, p, t, n, fn=fn):  # noqa: ANN001, ANN202
            def body(_, q):  # noqa: ANN001, ANN202
                o = fn(q, p, t, n, rank, scale)
                return q.at[..., :rank].set(o)

            return jax.lax.fori_loop(0, CALLS, body, q)

        us = timed(jax.jit(loop), qd, pd, td, ld) / CALLS * 1e6
        out[f"{name}_us"] = us
        out[f"{name}_hbm_pct"] = 100.0 * lengths.sum() * (rank + rope) * 2 / HBM_BYTES_PER_S / (us * 1e-6)
    return out


def grouped(rng, m: int) -> dict:  # noqa: ANN001
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import grouped_matmul as gm

    L, E, k, n = 2, 64, 2048, 1408
    w = jnp.asarray(rng.standard_normal((L, E, k, n), dtype=np.float32) * k**-0.5, jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((m, k), dtype=np.float32), jnp.bfloat16)
    picks = np.sort(rng.integers(0, E, m))
    sizes = jnp.asarray(np.bincount(picks, minlength=E), jnp.int32)
    x64, w64 = np.asarray(x, np.float64), np.asarray(w[1], np.float64)
    rows = min(m, 512)  # float64 on the host: the first rows are enough
    want = np.stack([x64[r] @ w64[picks[r]] for r in range(rows)])
    layer = jnp.int32(1)
    out = {"rows": m, "tiling": gm._tiling(m, k, n, 2, E)}
    fns = {
        "megablox": lambda x, w, s: gm.grouped_matmul(x, w, s, layer),
        "ragged_dot": lambda x, w, s: jax.lax.ragged_dot(x, w[1], s),
    }
    for name, fn in fns.items():
        got = np.asarray(jax.jit(fn)(x, w, sizes), np.float64)[:rows]
        out[f"{name}_max_err"] = float(np.abs(got - want).max())

        def loop(x, w, s, fn=fn):  # noqa: ANN001, ANN202
            return jax.lax.fori_loop(0, CALLS, lambda _, x: x + fn(x, w, s)[:, :1] * 1e-6, x)

        us = timed(jax.jit(loop), x, w, sizes) / CALLS * 1e6
        out[f"{name}_us"] = us
        out[f"{name}_weights_hbm_pct"] = 100.0 * min(E, m) * k * n * 2 / HBM_BYTES_PER_S / (us * 1e-6)
        out[f"{name}_mxu_pct"] = 100.0 * 2 * m * k * n / 197e12 / (us * 1e-6)
    return out


def parity(seed: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import kinds, models, spec
    from torchx_tpu.ops import grouped_matmul as gm
    from torchx_tpu.ops import paged_mla as pm
    from torchx_tpu.serve.engine import ServeEngine

    config = spec.load_cell(CELL).config
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
    ap.add_argument("--parity", action="store_true")
    args = ap.parse_args()
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}")
        return 1
    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    setup_compilation_cache()
    rng = np.random.default_rng(args.seed)
    out = {"device_kind": dev.device_kind, "attention": attention(rng),
           "grouped_decode": grouped(rng, 384), "grouped_prefill": grouped(rng, 12288)}  # fmt: skip
    print(json.dumps(out), flush=True)
    if args.parity:
        print(json.dumps({"parity": parity(args.seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
