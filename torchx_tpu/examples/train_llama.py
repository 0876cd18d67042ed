"""Llama pretraining example/benchmark — the flagship launched job.

The analog of the reference's ``lightning`` example trainer
(torchx/examples/apps/lightning) re-imagined for TPU SPMD: a pjit-style
training step (AdamW, remat, bf16) over the 4-axis dp/fsdp/tp/sp mesh,
launched via::

    tpx run -s gke dist.spmd --tpu v5p-32 -m torchx_tpu.examples.train_llama -- \
        --config llama3_8b --mesh fsdp=-1 --batch 16 --seq 8192

Prints per-step tokens/sec and model FLOPs utilization (MFU); the
launch-to-first-step latency (the BASELINE.md north-star metric) is
reported as the time from process start to the end of step 1.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

import jax

from torchx_tpu.models import all_configs
from torchx_tpu.parallel.mesh import MeshConfig
from torchx_tpu.train.run import train
from torchx_tpu.train.step import (
    TrainState,
    make_optimizer,
    make_train_step,
    normalize_state_shardings,
)

# for benchmark/lib/train_cell.py, benchmark/train_call.py and
# benchmark/rehearse_compile.py, which read these five names here
__all__ = [
    "TrainState",
    "make_optimizer",
    "make_train_step",
    "normalize_state_shardings",
    "train",
]


def parse_mesh_arg(spec: str) -> MeshConfig:
    """``dp=2,fsdp=-1,tp=4`` -> MeshConfig."""
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec

    return parse_mesh_spec(spec)


def main(argv: Optional[list[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default="tiny", choices=sorted(all_configs()))
    parser.add_argument(
        "--mesh",
        default="fsdp=-1",
        help="axis sizes pp/dp/fsdp/ep/tp/sp, e.g. dp=2,fsdp=-1,tp=4"
        " (ep shards MoE experts independently of tp, e.g. ep=8,tp=1)",
    )
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--ring-attention", action="store_true")
    parser.add_argument(
        "--remat-policy",
        default=None,
        choices=["full", "dots", "dots_attn", "auto"],
        help="rematerialization policy (default: the config's own);"
        " 'auto' AOT-compiles candidates and picks the cheapest-recompute"
        " policy that fits device HBM",
    )
    parser.add_argument(
        "--prefetch",
        type=int,
        default=2,
        help="device input prefetch depth (batches staged ahead of the"
        " step; 0 = synchronous)",
    )
    parser.add_argument(
        "--int8",
        action="store_true",
        help="AQT int8 training matmuls (see docs/performance.md for the"
        " measured v5e guidance before enabling)",
    )
    parser.add_argument(
        "--int8-scope",
        default=None,
        choices=["all", "ffn"],
        help="which projections to quantize (implies --int8)",
    )
    parser.add_argument("--lr", type=float, default=None)
    parser.add_argument(
        "--grad-bucket-mb",
        default="0",
        help="bucket the gradient sync so per-bucket reduces overlap the"
        " backward pass: a size cap in MiB, 'auto' (remat_auto-style"
        " candidate ladder), or 0 to keep the single fused sync."
        " Gradients are bitwise identical either way",
    )
    parser.add_argument(
        "--kernels",
        default=None,
        choices=["reference", "pallas", "interpret"],
        help="attention/norm kernel implementation: 'pallas' selects the"
        " fused Mosaic kernels on TPU (reference fallback elsewhere);"
        " 'interpret' runs the same kernels in the Pallas interpreter"
        " (parity testing); default reference XLA ops",
    )
    parser.add_argument(
        "--log-every", type=int, default=None,
        help="steps between log lines, >= 1 (each is a device fence;"
        " 8+ on TPU)",
    )
    parser.add_argument(
        "--data", default=None, help="packed uint32 token file (see datapreproc); synthetic data when unset"
    )
    parser.add_argument(
        "--profile-dir", default=None, help="write an xprof trace of the timed steps here"
    )
    parser.add_argument(
        "--profile",
        action="store_true",
        help="per-step phase attribution (data_wait / forward_backward /"
        " grad_sync / optimizer / checkpoint / host) appended to the obs"
        " session's profile.jsonl — view with `tpx profile`; also"
        " enabled by TPX_PROFILE=1. Fences every step: use for"
        " attribution runs, not headline numbers",
    )
    parser.add_argument(
        "--ckpt-dir", default=None, help="checkpoint directory (enables save+resume)"
    )
    parser.add_argument(
        "--ckpt-every", type=int, default=0, help="save every N steps (default 100 when --ckpt-dir is set)"
    )
    args = parser.parse_args(argv)

    cfg = all_configs()[args.config]()
    if args.ring_attention:
        cfg = dataclasses.replace(cfg, use_ring_attention=True)
    if args.remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=args.remat_policy)
    if args.int8 or args.int8_scope:
        cfg = dataclasses.replace(
            cfg, int8_matmuls=True, int8_scope=args.int8_scope or "all"
        )
    if args.log_every is not None and args.log_every < 1:
        parser.error("--log-every must be >= 1")
    # None = keep train()'s own defaults (single source of truth)
    overrides = {
        k: v
        for k, v in {"log_every": args.log_every, "lr": args.lr}.items()
        if v is not None
    }
    import os

    from torchx_tpu import settings

    # an elastic reshape overrides --mesh: the supervisor injects the
    # degraded shape for resubmitted attempts as $TPX_MESH, so the job
    # comes up on the surviving capacity without anyone editing flags
    mesh_spec = os.environ.get(settings.ENV_TPX_MESH) or args.mesh
    metrics = train(
        cfg,
        parse_mesh_arg(mesh_spec),
        args.batch,
        args.seq,
        args.steps,
        **overrides,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        data_path=args.data,
        profile_dir=args.profile_dir,
        prefetch=args.prefetch,
        profile=args.profile,
        grad_bucket_mb=args.grad_bucket_mb,
        kernels=args.kernels or "reference",
    )
    if jax.process_index() == 0:
        print("final:", metrics, flush=True)


if __name__ == "__main__":
    main()
