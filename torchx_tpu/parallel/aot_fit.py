"""AOT memory-fit analysis: does a training config fit the target HBM?

PJRT topology descriptions let the flagship train step — splash attention,
dots remat, chunked CE, AdamW, real fsdp/tp shardings — be compiled for a
TPU slice with no hardware attached; the compiler's buffer assignment
(``compiled.memory_analysis()``) then answers the only question that
matters before renting a pod: *does the north-star config fit per-device
HBM?* The same entry points compile on the CPU backend (CI has no libtpu),
where the xla-attention fallback materializes [b, h, s, s] logits — CPU
numbers are therefore a conservative upper bound of the TPU ones.

Used by ``scripts/aot_memory_fit.py`` (the operator CLI that prints the
fit table in docs/performance.md) and ``tests/test_aot_fit.py`` (CI gate).

Reference analog: none — meta-pytorch/torchx has no model/perf stack; this
validates the BASELINE.json north-star (Llama-3-8B >= 45% MFU on v5p-32).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from torchx_tpu.models import all_configs
from torchx_tpu.train.step import (
    abstract_train_state,
    make_optimizer,
    make_train_step,
)

GIB = 1024**3

# v5p HBM per chip; the fit leaves headroom for runtime scratch + infeed
# buffers the buffer assignment does not cover
V5P_HBM_BYTES = 95 * GIB
DEFAULT_HEADROOM = 0.9


def tpu_topology_mesh(topology: str, mesh_axes: Any) -> Mesh:
    """Mesh over the compile-only devices of a TPU slice description.

    ``topology`` is a PJRT topology string like ``v5p:2x2x4`` (the 16-chip
    v5p-32 slice) or ``v5e:4x4``; requires a TPU-capable PJRT plugin.
    """
    from jax.experimental import topologies

    from torchx_tpu.parallel.mesh import make_mesh

    topo = topologies.get_topology_desc(topology, "tpu")
    return make_mesh(mesh_axes, devices=topo.devices)


@dataclasses.dataclass
class FitResult:
    batch: int
    seq: int
    remat_policy: str
    args_bytes: int  # per-device params + opt state + batch
    temp_bytes: int  # per-device activations / workspace
    peak_bytes: int  # per-device worst case (see compile_fit)
    fits: bool
    generated_code_bytes: int = 0

    def row(self) -> str:
        """This result as one markdown fit-table row."""
        return (
            f"| {self.batch} | {self.seq} | {self.remat_policy} "
            f"| {self.args_bytes / GIB:.1f} | {self.temp_bytes / GIB:.1f} "
            f"| {self.peak_bytes / GIB:.1f} | "
            f"{'yes' if self.fits else 'NO'} |"
        )


def compile_fit(
    cfg: Any,
    mesh: Mesh,
    batch: int,
    seq: int,
    hbm_bytes: int = V5P_HBM_BYTES,
    headroom: float = DEFAULT_HEADROOM,
) -> FitResult:
    """AOT-compile one (config, mesh, batch, seq) and read the memory fit."""
    from torchx_tpu.parallel.mesh import BATCH_SPEC

    cfg = dataclasses.replace(cfg, max_seq=seq)
    optimizer = make_optimizer(warmup=100)
    state_sds = abstract_train_state(cfg, mesh, optimizer)
    batch_sds = {
        "tokens": jax.ShapeDtypeStruct(
            (batch, seq + 1),
            jnp.int32,
            sharding=NamedSharding(mesh, BATCH_SPEC),
        )
    }
    step = make_train_step(cfg, mesh, optimizer)
    compiled = step.lower(state_sds, batch_sds).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        raise RuntimeError("backend returned no memory analysis")
    peak = getattr(ma, "peak_memory_in_bytes", 0)
    # arguments (params/opt state) are resident for the whole step whether
    # or not the peak_memory accounting includes them, so the fit test uses
    # the conservative max(live-buffer peak, args + temps)
    resident = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    worst = max(peak, resident)
    return FitResult(
        batch=batch,
        seq=seq,
        remat_policy=cfg.remat_policy,
        args_bytes=ma.argument_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        peak_bytes=worst,
        fits=worst <= hbm_bytes * headroom,
        generated_code_bytes=ma.generated_code_size_in_bytes,
    )


def north_star_cfg(attn_impl: str = "splash") -> Any:
    """llama3_8b exactly as the 45%-MFU claim trains it: bf16, dots remat,
    splash attention at the measured 512/512 tiles, chunked logsumexp CE
    with bf16 logits (docs/performance.md round-4 levers)."""
    from torchx_tpu.models import llama

    return llama.llama3_8b(
        remat=True,
        remat_policy="dots",
        attn_impl=attn_impl,
        attn_block_q=512,
        attn_block_kv=512,
        loss_chunk=2048,
    )


def model_state_bytes_per_device(cfg: Any, n_devices: int) -> int:
    """Analytic params + Adam moments bytes per device (all fsdp/tp-sharded
    at scale): 3x the bf16 param bytes spread over the mesh."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return 3 * cfg.param_count() * itemsize // n_devices


def probe_fits(requests: list[dict[str, Any]]) -> list[dict[str, Any]]:
    """Batch :func:`compile_fit` for the ``tpx tune`` AOT prune stage.

    One jax process serves the whole candidate batch (the tune driver is
    jax-free; spawning one interpreter per candidate would pay the jax
    import tax N times). Each request dict carries ``config`` (builtin
    name), ``mesh_spec``, ``batch``, ``seq`` and optionally
    ``remat_policy``, ``int8_scope``, ``hbm_bytes``, ``headroom``; each
    result mirrors :class:`FitResult` plus the echoed request, or carries
    ``error`` — per-candidate failures never kill the batch.
    """
    from torchx_tpu.parallel.mesh import make_mesh
    from torchx_tpu.parallel.mesh_config import MeshConfig, parse_mesh_spec

    configs = all_configs()
    out: list[dict[str, Any]] = []
    for req in requests:
        result: dict[str, Any] = {"request": req}
        try:
            overrides: dict[str, Any] = {}
            if req.get("remat_policy"):
                overrides["remat_policy"] = req["remat_policy"]
            scope = req.get("int8_scope") or "none"
            if scope != "none":
                overrides["int8_matmuls"] = True
                overrides["int8_scope"] = scope
            cfg = configs[req["config"]](**overrides)
            mesh_cfg = (
                parse_mesh_spec(req["mesh_spec"])
                if req.get("mesh_spec")
                else MeshConfig()
            )
            mesh = make_mesh(mesh_cfg)
            r = compile_fit(
                cfg,
                mesh,
                int(req["batch"]),
                int(req["seq"]),
                hbm_bytes=int(req.get("hbm_bytes") or V5P_HBM_BYTES),
                headroom=float(req.get("headroom") or DEFAULT_HEADROOM),
            )
            result.update(
                {
                    "fits": r.fits,
                    "args_bytes": int(r.args_bytes),
                    "temp_bytes": int(r.temp_bytes),
                    "peak_bytes": int(r.peak_bytes),
                    "remat_policy": r.remat_policy,
                }
            )
        except Exception as e:  # noqa: BLE001 - advisory batch probe
            result["error"] = f"{type(e).__name__}: {e}"
        out.append(result)
    return out


def _probe_main() -> int:
    """``python -m torchx_tpu.parallel.aot_fit``: JSON requests on stdin,
    one JSON results line on stdout (the tune driver's subprocess ABI)."""
    import json
    import sys

    from torchx_tpu.parallel.xla_cache import setup_compilation_cache

    requests = json.load(sys.stdin)
    if not isinstance(requests, list):
        raise SystemExit("expected a JSON list of probe requests on stdin")
    # a candidate that fits is compiled again by its measured trial: the
    # probe's compile is that trial's cache hit
    setup_compilation_cache()
    print(json.dumps(probe_fits(requests)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(_probe_main())
