#!/usr/bin/env python
"""Measure the int8-training crossover on the batch (row) axis.

End-to-end int8 training at batch 2 is net-negative on v5e (the dynamic
quant/dequant elementwise passes outweigh the 1.94x int8 MXU speedup —
docs/performance.md); larger batches of the full model are not measured.
This measures the per-layer matmul itself across the row axis: it
slope-times the llama3_1b FFN dot ([M, 2048] x [2048, 8192]) as bf16 vs
the AQT int8 training dot (dynamic per-tensor scales, the exact
configuration ``LlamaConfig.int8_matmuls`` uses) for growing M = the
batch x seq rows a training step feeds it.

Timing protocol: chained data dependence (each iteration consumes the
previous output, so repeat dispatches cannot be elided) and slope timing
(t(long) - t(short) cancels the fixed dispatch/fetch overhead).

Prints one JSON line per M with the bf16/int8 ratio; ratio > 1 means
int8 wins at that shape.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp


def _chain(matmul, x0, w, n):  # noqa: ANN001
    """n dependent matmuls; EVERY output column feeds the carry (a slice
    would let XLA dead-code-eliminate the unused columns — observed as a
    7x-over-peak bf16 "measurement" with the naive y = out[:, :k] chain).
    """

    def body(_, y):  # noqa: ANN001
        out = matmul(y, w)
        m, k = y.shape
        folded = out.reshape(m, out.shape[1] // k, k).sum(axis=1)
        # renormalize to a data-dependent O(1) fixed point so the chain
        # neither underflows to zeros (which would hand AQT a degenerate
        # abs-max=0 scale and un-time the real quant cost) nor overflows;
        # the reduction's cost is identical for both candidates so the
        # slope difference still isolates the matmul
        norm = jnp.maximum(jnp.mean(jnp.abs(folded)), 1e-6)
        return (folded / norm).astype(y.dtype)

    return jax.lax.fori_loop(0, n, body, x0)


def time_chain(matmul, m: int, k: int, n: int, peak: float = 190e12) -> float:
    """-> seconds per matmul via slope timing; chain lengths scale with
    the shape so the slope dwarfs the fixed fetch round trip."""
    x = jnp.ones((m, k), jnp.bfloat16)
    w = jnp.ones((k, n), jnp.bfloat16) * 0.01
    t_est = 2 * m * k * n / peak
    short = 8
    long = short + min(400, max(40, int(0.2 / t_est)))
    fn = jax.jit(lambda x0, w, steps: _chain(matmul, x0, w, steps), static_argnums=2)
    jax.device_get(fn(x, w, short)[0, 0])  # compile + warm both lengths
    jax.device_get(fn(x, w, long)[0, 0])

    def run(steps: int) -> float:
        t0 = time.monotonic()
        jax.device_get(fn(x, w, steps)[0, 0])
        return time.monotonic() - t0

    best = min((run(long) - run(short)) for _ in range(2))
    return max(best, 1e-9) / (long - short)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--rows", default="2048,4096,8192,16384,32768")
    args = ap.parse_args()

    from torchx_tpu.ops.quant import aqt_dot_general

    dims = (((1,), (0,)), ((), ()))

    def bf16_mm(x, w):  # noqa: ANN001
        return jax.lax.dot_general(x, w, dims, preferred_element_type=jnp.float32)

    aqt = aqt_dot_general()

    def int8_mm(x, w):  # noqa: ANN001
        return aqt(x, w, dims)

    for m in [int(r) for r in args.rows.split(",")]:
        t_bf16 = time_chain(bf16_mm, m, args.k, args.n)
        t_int8 = time_chain(int8_mm, m, args.k, args.n)
        flops = 2 * m * args.k * args.n
        print(
            json.dumps(
                {
                    "rows": m,
                    "bf16_us": round(t_bf16 * 1e6, 1),
                    "int8_us": round(t_int8 * 1e6, 1),
                    "bf16_tflops": round(flops / t_bf16 / 1e12, 1),
                    "int8_tops": round(flops / t_int8 / 1e12, 1),
                    "int8_speedup": round(t_bf16 / t_int8, 3),
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
