"""Production serving runtime: continuous batching over a paged KV cache.

Three layers, bottom-up:

* :mod:`torchx_tpu.serve.kv_pool` — host-side paged KV-cache planning and
  block allocation (the device-side gather/scatter lives in
  :mod:`torchx_tpu.ops.paged_attention`);
* :mod:`torchx_tpu.serve.engine` — the continuous-batching decode engine:
  a fixed slot array XLA compiles once, per-step admission and eviction,
  prompts fed in chunks that ride the decode steps;
* :mod:`torchx_tpu.serve.slot_cache` — what a slot holds in the cache, one
  object a kind of cache (one paged pool; beside a ring; beside a mixer's
  state; a window's rows and the pooled rows behind it), picked once from
  the configuration: the engine's loop knows no kind by name;
* :mod:`torchx_tpu.serve.prefix_cache` — refcounted radix prefix cache
  over the pool: shared prompt prefixes resolve to shared physical
  blocks instead of recomputing (LRU-evicted under pool pressure);
* :mod:`torchx_tpu.serve.kv_transfer` — the prefill->decode KV-block
  transfer seam for disaggregated serving (local/HTTP/file transports;
  the ``TransferConfig`` shape AppDef roles carry);
* :mod:`torchx_tpu.serve.pool` — the launcher-driven serve pool:
  ``tpx serve-pool`` submits N ``generate_server`` replicas through the
  Runner, routes requests least-loaded (with a longest-cached-prefix
  bonus), and autoscales via ``Runner.resize`` on queue-depth/p99
  targets — one gang, or disaggregated prefill + decode gangs with
  independent policies.
"""

# Lazy re-exports (PEP 562): kv_pool pulls in the jax-backed paged
# attention op, but jax-free consumers (the fleet simulator runs the
# production Autoscaler from serve.pool) must be able to import their
# submodule without paying for — or even having — jax.
_EXPORTS = {
    "BlockAllocator": "torchx_tpu.serve.kv_pool",
    "PoolPlan": "torchx_tpu.serve.kv_pool",
    "plan_pool": "torchx_tpu.serve.kv_pool",
    "PrefixCache": "torchx_tpu.serve.prefix_cache",
    "prefix_chain": "torchx_tpu.serve.prefix_cache",
    "TransferConfig": "torchx_tpu.serve.kv_transfer",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
