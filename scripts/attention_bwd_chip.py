"""Splash attention's backward alone on the chip: two kernels against the fused one, how fast and how right.

A one-off measurement (PR 50), not a tool of the benchmark. On one TPU, at ``mistral7b-train-4k``'s
shape (``q [2, 4096, 32, 128]``, ``k``, ``v`` ``[2, 4096, 8, 128]``, causal, bf16), it runs the backward
of ``ops/attention.py::splash_attention`` (the transposes, the scale folded into ``q``, ``di``, the
kernels, the partials' sum: what the step's ``attn_kernel`` scope holds behind the recomputed forward)

* in the form the function chooses from the shapes (``chosen``), as two kernels (``split``: a ``dkv`` walk
  and a ``dq`` walk, each making ``S``, ``P`` and ``dP`` again), and fused (``dk``, ``dv`` and a ``dq``
  partial a kv block from one walk) over ``block_q_dkv`` x ``block_kv_dkv`` x ``block_kv_dkv_compute``;
* times each as a loop of calls inside one program (every call's cotangent depends on the call before, so
  nothing is hoisted; the loop adds one pass over ``do`` and ``dq`` a call to every form alike), best of
  ``--repeats``, and prints ms a call beside the forward kernel's (with its residuals);
* compares ``dq``, ``dk``, ``dv`` of batch row 0 with float64 on the host: the largest absolute gap over
  the largest absolute value of the float64 gradient.

    chiprun -- python3 scripts/attention_bwd_chip.py

``--describe`` compiles every form for a described ``v5e:2x2`` instead (no chip, nothing run, nothing
timed) and prints what Mosaic accepts. Without it the script needs a TPU: a time from the CPU's
interpreter says nothing.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 8  # backward calls inside one timed program
B, S, H, KV_H, D = 2, 4096, 32, 8, 128
BQ, BKV = 512, 1024  # the forward's blocks, splash_attention's defaults


def forms(chosen: dict) -> dict[str, dict]:
    """name -> what ``_backward_blocks`` returns for that form."""
    split = lambda bq, bkv: dict(block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv, block_q_dq=bq, block_kv_dq=bkv)  # noqa: E731
    fused = lambda bq, bkv, c: dict(block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=c, use_fused_bwd_kernel=True)  # noqa: E731
    out = {"chosen": chosen, "split q512 kv1024": split(512, 1024), "split q1024 kv1024": split(1024, 1024), "split q512 kv2048": split(512, 2048)}
    for bq in (512, 1024):
        for bkv in (1024, 2048, 4096):
            for c in (512, 1024, 2048):
                if c <= bkv:
                    out[f"fused q{bq} kv{bkv} compute{c}"] = fused(bq, bkv, c)
    return out


def reference_grads(q, k, v, do):  # noqa: ANN001, ANN201 - float64 on the host, one batch row, a cache head at a time
    import numpy as np

    q, k, v, do = (np.asarray(a, np.float64) for a in (q, k, v, do))
    s, h, d = q.shape
    rep = h // k.shape[1]
    mask = np.tril(np.ones((s, s), bool))
    dq, dk, dv = np.zeros_like(q), np.zeros_like(k), np.zeros_like(v)
    for j in range(h):
        g = j // rep
        sc = q[:, j] @ k[:, g].T * d**-0.5
        sc = np.where(mask, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        dv[:, g] += p.T @ do[:, j]
        dp = do[:, j] @ v[:, g].T
        ds = p * (dp - (dp * p).sum(-1, keepdims=True))
        dq[:, j] = ds @ k[:, g] * d**-0.5
        dk[:, g] += ds.T @ q[:, j] * d**-0.5
    return dq, dk, dv


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--describe", action="store_true", help="compile for a described v5e:2x2 and stop: no chip, nothing timed")
    ap.add_argument("--only", nargs="*", default=[], help="substrings of the forms to run (default: all)")
    args = ap.parse_args()
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name

    chosen = attn_ops._backward_blocks(S, S, D, BQ, BKV)
    todo = {n: f for n, f in forms(chosen).items() if not args.only or any(o in n for o in args.only)}

    def attn(q, k, v):  # noqa: ANN001, ANN202
        return attn_ops.splash_attention(q, k, v, causal=True)

    def use(form: dict) -> None:
        attn_ops._backward_blocks = lambda *_: form  # read as splash_attention is next traced

    def many(pullback, do):  # noqa: ANN001, ANN202 - a loop of backward calls over one forward's residuals
        def call(_, carry):  # noqa: ANN001, ANN202
            do, dk, dv = carry
            dq, dk_, dv_ = pullback(do)
            return do + 1e-3 * dq, dk + dk_, dv + dv_

        kv = jnp.zeros((B, S, KV_H, D), do.dtype)
        return jax.lax.fori_loop(0, CALLS, call, (do, kv, kv))

    many, once = jax.jit(many), jax.jit(lambda pullback, do: pullback(do))  # a form's pullback is a pytree of its own: traced anew

    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name

    chosen = attn_ops._backward_blocks(S, S, D, BQ, BKV)
    todo = {n: f for n, f in forms(chosen).items() if not args.only or any(o in n for o in args.only)}

    def attn(q, k, v):  # noqa: ANN001, ANN202
        return attn_ops.splash_attention(q, k, v, causal=True)

    def backward_of(form: dict):  # noqa: ANN202 - (residuals program, a loop of backward calls over them)
        attn_ops._backward_blocks = lambda *_: form  # read as splash_attention is traced: the first call below

        def many(pullback, do):  # noqa: ANN001, ANN202
            def call(_, carry):  # noqa: ANN001, ANN202
                do, dk, dv = carry
                dq, dk_, dv_ = pullback(do)
                return do + 1e-3 * dq, dk + dk_, dv + dv_

            kv = jnp.zeros((B, S, KV_H, D), do.dtype)
            return jax.lax.fori_loop(0, CALLS, call, (do, kv, kv))

        return jax.jit(lambda q, k, v: jax.vjp(attn, q, k, v)), jax.jit(many), jax.jit(lambda pullback, do: pullback(do))

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        shape = lambda heads: jax.ShapeDtypeStruct((B, S, heads, D), jnp.bfloat16, sharding=one_chip)  # noqa: E731
        for name, form in todo.items():
            use(form)
            try:
                grad = jax.jit(jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
                compiled = grad.lower(shape(H), shape(KV_H), shape(KV_H)).compile()
                m = compiled.memory_analysis()
                print(json.dumps({"form": name, "compiles": True, "temp_mib": m.temp_size_in_bytes / 2**20,
                                  "splash_calls": compiled.as_text().count("custom_call_target=\"tpu_custom_call\"")}), flush=True)  # fmt: skip
            except Exception as e:  # noqa: BLE001 - Mosaic's refusal is the finding
                print(json.dumps({"form": name, "compiles": False, "why": str(e).strip().splitlines()[-1][:300]}), flush=True)
        return 0

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"attention_bwd_chip: needs a TPU, found {dev.platform}")
        return 2
    rng = np.random.default_rng(args.seed)
    draw = lambda heads: jnp.asarray(rng.standard_normal((B, S, heads, D), np.float32), jnp.bfloat16)  # noqa: E731
    q, k, v, do = draw(H), draw(KV_H), draw(KV_H), draw(H)
    want = reference_grads(*(np.asarray(a[0], np.float32) for a in (q, k, v, do)))
    print(json.dumps({"device_kind": dev.device_kind, "shape": [B, S, H, KV_H, D], "calls_a_program": CALLS, "chosen": chosen}), flush=True)

    def best(fn, *a):  # noqa: ANN001, ANN202
        jax.block_until_ready(fn(*a))  # compile, warm
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return min(times)

    forward = jax.jit(lambda q, k, v: jax.lax.fori_loop(0, CALLS, lambda _, qq: qq + 1e-3 * jax.vjp(attn, qq, k, v)[0], q))
    print(json.dumps({"form": "forward with residuals", "ms_a_call": best(forward, q, k, v) / CALLS * 1e3}), flush=True)
    for name, form in todo.items():
        use(form)
        try:
            _, pullback = jax.jit(lambda q, k, v: jax.vjp(attn, q, k, v))(q, k, v)  # a new function a form: traced under it
            gaps = {
                n: float(np.abs(np.asarray(g[0], np.float64) - w).max() / np.abs(w).max())
                for n, g, w in zip(("dq", "dk", "dv"), once(pullback, do), want)
            }
            print(json.dumps({"form": name, "ms_a_call": best(many, pullback, do) / CALLS * 1e3, "gap_to_float64": gaps}), flush=True)
        except Exception as e:  # noqa: BLE001 - a form the chip's compiler refuses is a row of the table
            print(json.dumps({"form": name, "refused": str(e).strip().splitlines()[-1][:300]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
