"""AOT-compile the flagship train step for a v5p slice and prove the HBM fit.

The north-star deliverable (BASELINE.md) is Llama-3-8B at >= 45% MFU on a
TPU v5p-32 slice (16 chips, 95 GB HBM each). No v5p hardware is needed to
know whether a config *fits*: this compiles the exact training step for
the v5p topology and prints the compiler's per-device memory table —
see torchx_tpu/parallel/aot_fit.py for the machinery and
tests/test_aot_fit.py for the CI gate (CPU-backend upper bound).

Run::

    python scripts/aot_memory_fit.py                        # v5p-32 table
    python scripts/aot_memory_fit.py --topology v5p:2x4x4   # v5p-64
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

from torchx_tpu.parallel.aot_fit import (
    DEFAULT_HEADROOM,
    GIB,
    V5P_HBM_BYTES,
    compile_fit,
    north_star_cfg,
    tpu_topology_mesh,
)


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--topology", default="v5p:2x2x4", help="TPU topology (v5p-32 default)"
    )
    parser.add_argument(
        "--mesh", default="fsdp=8,tp=2", help="axis sizes, e.g. fsdp=8,tp=2"
    )
    parser.add_argument("--config", default="llama3_8b")
    parser.add_argument(
        "--cases",
        default="8:8192:dots,16:8192:dots,32:8192:dots,16:8192:full,8:32768:dots",
        help="comma list of batch:seq:remat_policy",
    )
    parser.add_argument("--headroom", type=float, default=DEFAULT_HEADROOM)
    args = parser.parse_args(argv)

    from torchx_tpu.examples.train_llama import parse_mesh_arg

    mesh = tpu_topology_mesh(args.topology, parse_mesh_arg(args.mesh))
    n = mesh.devices.size
    print(
        f"topology {args.topology}: {n} devices"
        f" ({getattr(mesh.devices.flat[0], 'device_kind', '?')}),"
        f" mesh {dict(mesh.shape)}"
    )
    print(f"HBM budget: {V5P_HBM_BYTES / GIB:.0f} GiB x {args.headroom} headroom")

    base = north_star_cfg()
    if args.config != "llama3_8b":
        from torchx_tpu.models import all_configs

        base = all_configs()[args.config]()

    print(
        "\n| batch | seq | remat | args GiB/dev | temps GiB/dev |"
        " peak GiB/dev | fits |"
    )
    print("|---|---|---|---|---|---|---|")
    ok = True
    for case in args.cases.split(","):
        b, s, pol = case.strip().split(":")
        cfg = dataclasses.replace(base, remat_policy=pol)
        try:
            r = compile_fit(cfg, mesh, int(b), int(s), headroom=args.headroom)
        except Exception as e:  # XLA OOM-at-compile raises ResourceExhausted
            print(f"| {b} | {s} | {pol} | - | - | compile failed: {e} | NO |")
            ok = False
            continue
        print(r.row())
        ok = ok and r.fits
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
