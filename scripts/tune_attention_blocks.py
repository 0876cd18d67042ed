#!/usr/bin/env python
"""Sweep flash-attention tile sizes on real hardware.

Profiling (docs/performance.md) showed the pallas flash kernels consume
~57% of llama3_1b step time at head_dim 64 with the default 128-blocks.
This sweeps (attn_block_q, attn_block_kv) candidates through the full
trainer and prints a ranked table — run on a healthy TPU (the pallas
kernels this tunes do not lower on CPU):

    python scripts/tune_attention_blocks.py --config llama3_1b --batch 2

The winner feeds LlamaConfig.attn_block_q/attn_block_kv (and the bench
candidate list in bench.py).
"""

from __future__ import annotations

import argparse
import itertools


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default="llama3_1b")
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument(
        "--blocks",
        default="0,128,256,512",
        help="comma list of candidate block sizes (0 = kernel default)",
    )
    parser.add_argument("--remat-policy", default="dots")
    parser.add_argument(
        "--impl",
        default="splash",
        choices=["pallas", "splash"],
        help="attention kernel to sweep (splash won the v5e sweep)",
    )
    args = parser.parse_args()

    from torchx_tpu.models import all_configs
    from torchx_tpu.train.run import train
    from torchx_tpu.parallel.mesh import MeshConfig

    candidates = [int(b) for b in args.blocks.split(",")]
    mesh = MeshConfig(dp=1, fsdp=-1, tp=1, sp=1)
    results = []
    for bq, bkv in itertools.product(candidates, candidates):
        cfg = all_configs()[args.config](
            remat_policy=args.remat_policy,
            attn_impl=args.impl,
            attn_block_q=bq,
            attn_block_kv=bkv,
        )
        try:
            m = train(
                cfg,
                mesh,
                batch=args.batch,
                seq=args.seq,
                steps=args.steps,
                log_every=args.steps,
            )
            results.append((m["mfu"], bq, bkv, m["tokens_per_sec_per_chip"]))
            print(
                f"block_q={bq or 'def'} block_kv={bkv or 'def'}:"
                f" MFU={m['mfu']:.1%} tps/chip={m['tokens_per_sec_per_chip']:,.0f}"
            )
        except Exception as e:  # noqa: BLE001 - a bad tiling must not end the sweep
            print(f"block_q={bq} block_kv={bkv}: FAILED {str(e)[:90]}")

    if results:
        results.sort(reverse=True)
        print("\nbest configurations:")
        for mfu, bq, bkv, tps in results[:5]:
            print(
                f"  attn_block_q={bq} attn_block_kv={bkv}:"
                f" MFU={mfu:.1%} tokens/sec/chip={tps:,.0f}"
            )


if __name__ == "__main__":
    main()
