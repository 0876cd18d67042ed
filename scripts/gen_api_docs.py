#!/usr/bin/env python
"""Generate the per-symbol API reference under docs/api/.

Reference analog: the sphinx autodoc pages meta-pytorch/torchx ships
(docs/source/api_reference.rst, specs.rst, runner.rst, schedulers.rst,
tracker.rst, workspace.rst). This repo's docs are plain markdown with
machine-owned generated content (same pattern as gen_scheduler_docs.py),
so the API reference is generated by introspection: every public symbol's
signature + docstring, straight from the live modules — the pages cannot
drift from the code, and CI fails when a public symbol is missing a
docstring (``--check``).

Usage:
    python scripts/gen_api_docs.py           # rewrite docs/api/ in place
    python scripts/gen_api_docs.py --check   # exit 1 on drift / missing docs
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import inspect
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
OUT = REPO / "docs" / "api"

# (module, page slug, one-line scope note). Order = index order.
MODULES = [
    ("torchx_tpu.specs", "specs", "the core data model every layer builds on"),
    ("torchx_tpu.specs.overlays", "specs_overlays", "raw-request patching (PUT/JOIN/DEL deep-merge)"),
    ("torchx_tpu.runner.api", "runner", "the session facade: run / dryrun / status / logs"),
    ("torchx_tpu.runner.config", "runner_config", ".tpxconfig loading and precedence"),
    ("torchx_tpu.runner.events", "runner_events", "API telemetry events"),
    ("torchx_tpu.obs", "observability", "structured tracing, metrics, durable sinks + `tpx trace` timelines"),
    ("torchx_tpu.obs.telemetry", "obs_telemetry", "fleet telemetry plane: /metricz scraping, ring-buffer series store, query reducers"),
    ("torchx_tpu.obs.slo", "obs_slo", "declarative SLOs evaluated as multi-window burn rates + journaled alerts"),
    ("torchx_tpu.obs.stitch", "obs_stitch", "cross-process trace stitching (`tpx trace --stitch`)"),
    ("torchx_tpu.obs.hot", "obs_hot", "hot-path plane: the loops' spans on the profiler's clock and the programs' device scopes, by name"),
    ("torchx_tpu.obs.profile", "obs_profile", "per-step phase attribution: MFU/roofline accounting + measured-overlap calibration feedback (`tpx profile`)"),
    ("torchx_tpu.supervisor", "supervisor", "preemption-aware auto-resubmit with retry budgets + checkpoint resume"),
    ("torchx_tpu.supervisor.gang", "gang", "gang health: hang/straggler/partial-loss detection from heartbeats + leases"),
    ("torchx_tpu.analyze", "analyze", "preflight static analysis: coded diagnostics + `tpx lint`"),
    ("torchx_tpu.analyze.explain", "analyze_explain", "deep preflight: static sharding propagation + HBM/collective cost model (`tpx explain`, TPX7xx)"),
    ("torchx_tpu.analyze.selfcheck", "analyze_selfcheck", "whole-program invariant analyzer over the launcher's own tree: transitive jax-free proof, clock/lock/journal discipline (`tpx selfcheck`, TPX9xx)"),
    ("torchx_tpu.util.jsonl", "util_jsonl", "crash-safe JSONL + JSON-state helpers: fsync'd appends, atomic rewrites, torn-line holdback readers"),
    ("torchx_tpu.resilience", "resilience", "control-plane resilience: classified retries, breakers, fault injection"),
    ("torchx_tpu.control", "control", "fleet control plane: watch streams, one reconciler, the `tpx control` daemon"),
    ("torchx_tpu.control.events", "control_events", "StateEvent: the watch-stream event record + JSONL serde"),
    ("torchx_tpu.control.watch", "control_watch", "per-backend watch adapters: sidecar mtime, kubectl -w shim, poll fallback"),
    ("torchx_tpu.control.store", "control_store", "sharded on-disk job-state journal (JSONL + fsync'd meta)"),
    ("torchx_tpu.control.reconciler", "control_reconciler", "the single event loop: watch streams -> store/cache -> waiter wakeups"),
    ("torchx_tpu.control.daemon", "control_daemon", "the `tpx control` multi-tenant localhost HTTP daemon"),
    ("torchx_tpu.control.client", "control_client", "HTTP client + transparent CLI proxy (`$TPX_CONTROL_ADDR`)"),
    ("torchx_tpu.tune", "tune", "closed-loop config autotuner: static prune -> measure top-k -> pinned plan artifact + cost-model recalibration (`tpx tune`)"),
    ("torchx_tpu.fleet", "fleet", "fleet scheduler: priority classes, gang placement, the elastic preemption market (`tpx queue`)"),
    ("torchx_tpu.pipelines", "pipelines", "train→eval→promote DAG engine: journaled stages, eval gates, canary promotion with SLO-gated rollback (`tpx pipeline`)"),
    ("torchx_tpu.sim", "sim", "virtual-time fleet simulator: the production control plane driven deterministically at fleet scale (`tpx sim`)"),
    ("torchx_tpu.federation", "federation", "multi-cell federation: SLO-burn + prefix-affinity global routing, drain/uncordon lifecycle, region-by-region promotion waves (`tpx cell`)"),
    ("torchx_tpu.schedulers.api", "schedulers", "the Scheduler ABC + typed opts + responses"),
    ("torchx_tpu.schedulers", "schedulers_registry", "backend registry / factories"),
    ("torchx_tpu.workspace.api", "workspace", "workspace mixin contract + ignore rules"),
    ("torchx_tpu.workspace.docker_workspace", "workspace_docker", "docker image build/push workspaces"),
    ("torchx_tpu.workspace.dir_workspace", "workspace_dir", "directory-snapshot workspaces"),
    ("torchx_tpu.components.dist", "components_dist", "distributed app components (dist.spmd / dist.ddp)"),
    ("torchx_tpu.components.utils", "components_utils", "utility components (echo/sh/python/copy/booth)"),
    ("torchx_tpu.components.serve", "components_serve", "serving components"),
    ("torchx_tpu.components.metrics", "components_metrics", "observability components"),
    ("torchx_tpu.components.structured_arg", "components_structured_arg", "structured CLI argument parsing"),
    ("torchx_tpu.tracker.api", "tracker", "experiment tracking API + in-job AppRun"),
    ("torchx_tpu.tracker.backend.fsspec", "tracker_fsspec", "fsspec-backed tracker"),
    ("torchx_tpu.tracker.mlflow", "tracker_mlflow", "MLflow-backed tracker"),
    ("torchx_tpu.plugins", "plugins", "extension registry + registration decorators"),
    ("torchx_tpu.distributed", "distributed", "in-job helpers: gang info, jax.distributed bootstrap"),
    ("torchx_tpu.parallel.mesh", "parallel_mesh", "device mesh construction + canonical shardings"),
    ("torchx_tpu.parallel.mesh_config", "parallel_mesh_config", "jax-free mesh shape model: specs + elastic shrink arithmetic"),
    ("torchx_tpu.parallel.pipeline", "parallel_pipeline", "GPipe pipeline parallelism"),
    ("torchx_tpu.parallel.checkpoint", "parallel_checkpoint", "async checkpointing: snapshot-then-write with a background writer"),
    ("torchx_tpu.parallel.overlap", "parallel_overlap", "bucketed gradient-sync overlap (--grad-bucket-mb)"),
    ("torchx_tpu.parallel.aot_fit", "parallel_aot_fit", "AOT HBM-fit analysis for TPU topologies"),
    ("torchx_tpu.parallel.prefetch", "parallel_prefetch", "double-buffered device input prefetch"),
    ("torchx_tpu.parallel.remat_auto", "parallel_remat_auto", "auto remat policy from AOT memory analysis"),
    ("torchx_tpu.train.step", "train_step", "what a training step is: optimizer, TrainState, the jitted SPMD step, the state's shapes and shardings"),
    ("torchx_tpu.train.run", "train_run", "`train()` as stages that return values: resolve, open, compile, join, first step, loop, summarize"),
    ("torchx_tpu.train.report", "train_report", "what a training job says about itself: launch spans, heartbeats and leases, the step profiler, peak FLOP/s"),
    ("torchx_tpu.train.data", "train_data", "token-memmap input pipeline: per-process shards, seeded resumable batches"),
    ("torchx_tpu.models.llama", "models_llama", "the Llama model family"),
    ("torchx_tpu.models.moe", "models_moe", "mixture-of-experts family"),
    ("torchx_tpu.models.generate", "models_generate", "KV-cache generation"),
    ("torchx_tpu.serve.kv_pool", "serve_kv_pool", "paged KV cache: HBM-budget pool planning + block allocation"),
    ("torchx_tpu.serve.engine", "serve_engine", "continuous-batching decode engine over a fixed slot array"),
    ("torchx_tpu.serve.slot_cache", "serve_slot_cache", "what a slot holds in the cache: one object a kind of cache behind the engine"),
    ("torchx_tpu.serve.prefix_cache", "serve_prefix_cache", "refcounted radix prefix cache over the paged KV pool"),
    ("torchx_tpu.serve.kv_transfer", "serve_kv_transfer", "prefill->decode KV-block transfer seam (local/http/file)"),
    ("torchx_tpu.serve.pool", "serve_pool", "autoscaling serve pool: cache-aware routing + disaggregated gangs"),
    ("torchx_tpu.ops.attention", "ops_attention", "attention kernels (splash/flash/xla)"),
    ("torchx_tpu.ops.paged_attention", "ops_paged_attention", "single-token decode attention over the paged KV pool"),
    ("torchx_tpu.ops.paged_attention_kernel", "ops_paged_attention_kernel", "the ragged Pallas TPU kernel behind paged decode attention"),
    ("torchx_tpu.ops.ring_attention", "ops_ring_attention", "ring attention over the sp axis"),
    ("torchx_tpu.ops.norms", "ops_norms", "RMSNorm (+ fused backward kernel)"),
    ("torchx_tpu.ops.fused", "ops_fused", "fused Pallas step kernels: flash attention + residual RMSNorm (--kernels)"),
    ("torchx_tpu.ops.rope", "ops_rope", "rotary position embeddings"),
    ("torchx_tpu.ops.quant", "ops_quant", "int8 weight-only + AQT training quantization"),
    ("torchx_tpu.deprecations", "deprecations", "deprecation warning helpers"),
]

HEADER = "<!-- generated by scripts/gen_api_docs.py; do not edit -->"


def _public_names(mod) -> list[str]:
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    names = []
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        if inspect.ismodule(obj):
            continue
        defined_in = getattr(obj, "__module__", None)
        if defined_in != mod.__name__:
            continue  # re-export without __all__: not this page's symbol
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        names.append(name)
    return names


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return doc.strip() if doc else ""


def _render_class(name: str, cls, missing: list[str], where: str) -> list[str]:
    lines = [f"### class `{name}{_signature(cls)}`", ""]
    doc = _doc(cls)
    if not doc:
        missing.append(f"{where}.{name}")
    else:
        lines += [doc, ""]
    if dataclasses.is_dataclass(cls):
        rows = ["| field | type | default |", "|---|---|---|"]
        for f in dataclasses.fields(cls):
            default = (
                "—"
                if f.default is dataclasses.MISSING
                and f.default_factory is dataclasses.MISSING
                else (
                    "*(factory)*"
                    if f.default is dataclasses.MISSING
                    else f"`{f.default!r}`"
                )
            )
            ftype = getattr(f.type, "__name__", str(f.type))
            rows.append(f"| `{f.name}` | `{ftype}` | {default} |")
        lines += rows + [""]
    for mname, meth in sorted(vars(cls).items()):
        if mname.startswith("_"):
            continue
        func = meth.__func__ if isinstance(meth, (classmethod, staticmethod)) else meth
        if not (inspect.isfunction(func) or isinstance(meth, property)):
            continue
        if isinstance(meth, property):
            lines += [f"#### property `{mname}`", ""]
            mdoc = _doc(meth.fget) if meth.fget else ""
        else:
            lines += [f"#### `{mname}{_signature(func)}`", ""]
            mdoc = _doc(func)
        if not mdoc:
            # an override documents itself through the contract it
            # implements: inherit the base class's docstring
            for base in cls.__mro__[1:]:
                base_m = base.__dict__.get(mname)
                if base_m is None:
                    continue
                if isinstance(base_m, (classmethod, staticmethod)):
                    base_m = base_m.__func__
                if isinstance(base_m, property):
                    base_m = base_m.fget
                bdoc = _doc(base_m)
                if bdoc:
                    mdoc = f"{bdoc}\n\n*(contract: `{base.__name__}.{mname}`)*"
                    break
        if not mdoc:
            missing.append(f"{where}.{name}.{mname}")
        else:
            lines += [mdoc, ""]
    return lines


def render_module(modname: str, slug: str, note: str, missing: list[str]) -> str:
    mod = importlib.import_module(modname)
    lines = [HEADER, f"# `{modname}`", ""]
    mdoc = _doc(mod)
    if not mdoc:
        missing.append(modname)
    else:
        lines += [mdoc, ""]
    for name in _public_names(mod):
        obj = getattr(mod, name, None)
        if obj is None:
            continue
        if inspect.isclass(obj):
            lines += _render_class(name, obj, missing, modname)
        elif inspect.isfunction(obj):
            lines += [f"### `{name}{_signature(obj)}`", ""]
            doc = _doc(obj)
            if not doc:
                missing.append(f"{modname}.{name}")
            else:
                lines += [doc, ""]
        else:
            lines += [f"### `{name}`", "", f"`{_stable_repr(obj)}`", ""]
    return "\n".join(lines).rstrip() + "\n"


def _stable_repr(obj) -> str:
    """repr without memory addresses (function-valued registries would
    otherwise make every docs run dirty)."""
    if inspect.isroutine(obj):
        return f"<function {obj.__module__}.{obj.__qualname__}>"
    # exact builtin containers only — subclasses (PartitionSpec, ...)
    # have meaningful reprs of their own
    if type(obj) is dict:
        inner = ", ".join(
            f"{k!r}: {_stable_repr(v)}" for k, v in obj.items()
        )
        return "{" + inner + "}"
    if type(obj) in (list, tuple):
        inner = ", ".join(_stable_repr(v) for v in obj)
        if type(obj) is tuple:
            return f"({inner},)" if len(obj) == 1 else f"({inner})"
        return f"[{inner}]"
    return repr(obj)


def render_index() -> str:
    lines = [
        HEADER,
        "# API reference",
        "",
        "Per-symbol reference for the public API, generated from the live",
        "modules (signatures + docstrings; see scripts/gen_api_docs.py).",
        "Start with the [library guide](../runner.md) for narrative docs.",
        "",
        "| module | scope |",
        "|---|---|",
    ]
    for modname, slug, note in MODULES:
        lines.append(f"| [`{modname}`]({slug}.md) | {note} |")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--check", action="store_true")
    args = parser.parse_args(argv)

    missing: list[str] = []
    pages = {"index.md": render_index()}
    for modname, slug, note in MODULES:
        pages[f"{slug}.md"] = render_module(modname, slug, note, missing)

    if missing:
        print(
            "public symbols missing docstrings (the API reference renders"
            " empty for these):",
            file=sys.stderr,
        )
        for m in missing:
            print(f"  {m}", file=sys.stderr)
        return 1

    if args.check:
        stale = []
        for fname, content in pages.items():
            path = OUT / fname
            if not path.exists() or path.read_text() != content:
                stale.append(fname)
        extra = {p.name for p in OUT.glob("*.md")} - set(pages)
        if stale or extra:
            for f in stale:
                print(f"docs/api/{f} is stale", file=sys.stderr)
            for f in sorted(extra):
                print(f"docs/api/{f} is orphaned", file=sys.stderr)
            print("run: python scripts/gen_api_docs.py", file=sys.stderr)
            return 1
        print(f"api docs current: {len(pages)} pages")
        return 0

    OUT.mkdir(parents=True, exist_ok=True)
    for p in OUT.glob("*.md"):
        if p.name not in pages:
            p.unlink()
    for fname, content in pages.items():
        (OUT / fname).write_text(content)
    print(f"wrote {len(pages)} pages to docs/api/")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
