"""The recurrent-state step kernel alone on the chip: is it right, and how fast.

A one-off measurement (PR 41), not a tool of the benchmark. On one TPU it

* compares ``ops/ssm_step_kernel.py::ssm_step_pallas`` with the same update and read-out in
  float64 on the host, at ``falcon-h1-serve-decode-long``'s shapes (32 heads x 256 x 128, two
  groups, a stack of layers, slots on their own rows and some on the trash row): the largest
  absolute error of the read-out and of the rows written, and that no other row moved;
* times it there as a loop of calls inside one program over a donated store of 64 slots (the
  layer index walks the stack, so every call moves other rows), and prints the bytes a call
  must move (each slot's state read once and written once) over its time as a share of the
  device's published HBM bandwidth; with ``--heads``, at other numbers of heads a grid step (multiples of 8: a block is whole tiles).

    chiprun -- python3 scripts/ssm_step_chip.py --heads 8 16

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
H, N, P, G = 32, 256, 128, 2


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--heads", type=int, nargs="*", default=[], help="other numbers of heads a grid step to time")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchx_tpu.ops import ssm_step_kernel as sk

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"ssm_step_chip: needs a TPU, found {dev.platform}")
        return 2
    rng = np.random.default_rng(args.seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731

    def inputs(slots: int):  # noqa: ANN202
        return rng.uniform(0, 1, (slots, H)).astype(np.float32), normal(slots, H, P), normal(slots, G, N), normal(slots, G, N)

    # -- right ----------------------------------------------------------------------------------
    layers, slots = 2, 6
    store = normal(layers, 1 + slots, H, N, P)
    rows = np.asarray([1, 0, 3, 4, 0, 6], np.int32)
    decay, fed, b, c = inputs(slots)
    y, new = jax.jit(lambda *a: sk.ssm_step_pallas(*a, layer=jnp.int32(1)))(*(jnp.asarray(a) for a in (store, rows, decay, fed, b, c)))
    y, new = np.asarray(y, np.float64), np.asarray(new, np.float64)
    by_head = lambda v: np.repeat(v.astype(np.float64), H // G, axis=1)  # noqa: E731
    want = store[1, rows].astype(np.float64) * decay[:, :, None, None] + by_head(b)[:, :, :, None] * fed[:, :, None, :]
    want_y = np.einsum("shnp,shn->shp", want, by_head(c))
    moved = rows != 0
    untouched = np.array_equal(new[0], store[0]) and np.array_equal(new[1, [2, 5]], store[1, [2, 5]])
    print(json.dumps({
        "read_out_max_abs_err": float(np.abs(y - want_y)[moved].max()), "read_out_rms": float(np.sqrt((want_y**2).mean())),
        "rows_max_abs_err": float(np.abs(new[1, rows[moved]] - want[moved]).max()), "other_rows_and_layers_untouched": bool(untouched),
    }), flush=True)  # fmt: skip

    # -- fast -----------------------------------------------------------------------------------
    layers, slots = 6, 64
    rows = jnp.arange(1, slots + 1, dtype=jnp.int32)
    decay, fed, b, c = (jnp.asarray(a) for a in inputs(slots))
    need = 2 * slots * H * N * P * 4
    for heads in [sk._HEADS, *args.heads]:
        sk._HEADS = heads

        def many(store, fed):  # noqa: ANN001, ANN202
            def call(i, carry):  # noqa: ANN001, ANN202
                store, fed = carry
                y, store = sk.ssm_step_pallas(store, rows, decay, fed, b, c, layer=i % layers)
                return store, fed + 1e-6 * y  # the next call's input: nothing is hoisted

            return jax.lax.fori_loop(0, CALLS, call, (store, fed))

        timed = jax.jit(many, donate_argnums=(0,))
        store = jnp.zeros((layers, 1 + slots, H, N, P), jnp.float32)
        store, _ = timed(store, fed)  # compile, warm
        jax.block_until_ready(store)
        t0 = time.perf_counter()
        store, out = timed(store, fed)
        jax.block_until_ready((store, out))
        per_call = (time.perf_counter() - t0) / CALLS
        print(json.dumps({"heads_a_grid_step": heads, "ms_a_call": per_call * 1e3, "bytes_a_call": need,
                          "share_of_hbm_pct": 100.0 * need / HBM_BYTES_PER_S / per_call}), flush=True)  # fmt: skip
        del store, out
    return 0


if __name__ == "__main__":
    sys.exit(main())
