"""The Gated DeltaNet step kernel alone on the chip: is it right, and how fast.

A one-off measurement (PR 49), not a tool of the benchmark. On one TPU it

* compares ``ops/gdn_step_kernel.py::gdn_step_pallas`` with the delta rule in float64 on the
  host, at ``qwen3-next-serve-decode-long``'s shapes (32 value heads over 16 key heads of 128 x
  128, a stack of layers, slots on their own rows and some on the trash row): the largest
  absolute error of the read-out and of the rows written, and that no other row moved;
* times it there as a loop of calls inside one program over a donated store of 128 slots (the
  layer index walks the stack, so every call moves other rows), and prints the bytes a call
  must move (each slot's state read once and written once) over its time as a share of the
  device's published HBM bandwidth; with ``--heads``, at other numbers of value heads a grid
  step (multiples of 8: a block is whole tiles).

    chiprun -- python3 scripts/gdn_step_chip.py --heads 16 32

It needs a TPU: a time from the CPU's interpreter says nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 48  # kernel calls inside one timed program
HBM_BYTES_PER_S = 819e9  # one v5e, Google Cloud's "TPU v5e" page (benchmark/lib/peaks.py)
H, HK, D = 32, 16, 128


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=49)
    ap.add_argument("--heads", type=int, nargs="*", default=[], help="other numbers of value heads a grid step to time")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import gdn_step_kernel as gk

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"gdn_step_chip: needs a TPU, found {dev.platform}")
        return 2
    rng = np.random.default_rng(args.seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731

    def inputs(slots: int):  # noqa: ANN202 - decay, beta, q, k, v as gdn._step_inputs makes them
        uniform = lambda: rng.uniform(0, 1, (slots, H)).astype(np.float32)  # noqa: E731
        return uniform(), uniform(), unit(normal(slots, HK, D)) * D**-0.5, unit(normal(slots, HK, D)), normal(slots, H, D)

    # -- right ----------------------------------------------------------------------------------
    layers, slots = 2, 6
    store = normal(layers, 1 + slots, H, D, D)
    rows = np.asarray([1, 0, 3, 4, 0, 6], np.int32)
    decay, beta, q, k, v = inputs(slots)
    o, new = jax.jit(lambda *a: gk.gdn_step_pallas(*a, layer=jnp.int32(1)))(*(jnp.asarray(a) for a in (store, rows, decay, beta, q, k, v)))
    o, new = np.asarray(o, np.float64), np.asarray(new, np.float64)
    by_head = lambda x: np.repeat(x.astype(np.float64), H // HK, axis=1)  # noqa: E731
    s = store[1, rows].astype(np.float64) * decay[:, :, None, None]
    u = beta[:, :, None] * (v - np.einsum("shkv,shk->shv", s, by_head(k)))
    want = s + by_head(k)[:, :, :, None] * u[:, :, None, :]
    want_o = np.einsum("shkv,shk->shv", want, by_head(q))
    moved = rows != 0
    untouched = np.array_equal(new[0], store[0]) and np.array_equal(new[1, [2, 5]], store[1, [2, 5]])
    print(json.dumps({
        "read_out_max_abs_err": float(np.abs(o - want_o)[moved].max()), "read_out_rms": float(np.sqrt((want_o**2).mean())),
        "rows_max_abs_err": float(np.abs(new[1, rows[moved]] - want[moved]).max()), "other_rows_and_layers_untouched": bool(untouched),
    }), flush=True)  # fmt: skip

    # -- fast -----------------------------------------------------------------------------------
    layers, slots = 6, 128
    rows = jnp.arange(1, slots + 1, dtype=jnp.int32)
    decay, beta, q, k, v = (jnp.asarray(a) for a in inputs(slots))
    need = 2 * slots * H * D * D * 4
    for heads in [gk._HEADS, *args.heads]:
        gk._HEADS = heads

        def many(store, v):  # noqa: ANN001, ANN202
            def call(i, carry):  # noqa: ANN001, ANN202
                store, v = carry
                o, store = gk.gdn_step_pallas(store, rows, decay, beta, q, k, v, layer=i % layers)
                return store, v + 1e-6 * o  # the next call's input: nothing is hoisted

            return jax.lax.fori_loop(0, CALLS, call, (store, v))

        timed = jax.jit(many, donate_argnums=(0,))
        store = jnp.zeros((layers, 1 + slots, H, D, D), jnp.float32)
        store, _ = timed(store, v)  # compile, warm
        jax.block_until_ready(store)
        t0 = time.perf_counter()
        store, out = timed(store, v)
        jax.block_until_ready((store, out))
        per_call = (time.perf_counter() - t0) / CALLS
        print(json.dumps({"heads_a_grid_step": heads, "ms_a_call": per_call * 1e3, "bytes_a_call": need,
                          "share_of_hbm_pct": 100.0 * need / HBM_BYTES_PER_S / per_call}), flush=True)  # fmt: skip
        del store, out
    return 0


if __name__ == "__main__":
    sys.exit(main())
