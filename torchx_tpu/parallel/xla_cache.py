"""Persistent XLA compilation cache setup.

Compilation dominates launch-to-first-step, and every relaunch —
preemption recovery, elastic resize, a server restart, a sweep over the
same shapes — recompiles programs an earlier process already built. Every
entry point that compiles for the device (trainer, server, ``tune``
measurement, the ``aot_fit`` probe, the benchmarks) calls
:func:`setup_compilation_cache` once before its first compile.

One knob, jax's own: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads
it itself and this module sets no directory in code. Where it is not set
the cache goes to one fixed path inside the checkout (``<repo>/.jax_cache``)
— the directory is part of the cache key, so a path that moves between
launches (``~`` of another user, a tempdir, a pid) never hits.
"""

from __future__ import annotations

import logging
import os
import re

logger = logging.getLogger(__name__)

ENV_JAX_COMPILATION_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root, resolved from this package's location
#: (torchx_tpu/parallel/xla_cache.py -> three levels up)
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CACHE_DIRNAME = ".jax_cache"


def setup_compilation_cache() -> str:
    """Enable the persistent compilation cache; returns the directory.

    Idempotent (re-applying the same config values is a no-op). Variant
    configs of one model (a remat-policy trial, a prefill bucket) lower to
    distinct programs, each with its own entry, so every one must be
    allowed to persist: the entry-size floor is zeroed and any compile over
    one second qualifies.
    """
    import jax

    cache_dir = os.environ.get(ENV_JAX_COMPILATION_CACHE_DIR)
    if not cache_dir:
        cache_dir = os.path.join(REPO_ROOT, CACHE_DIRNAME)
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the scope names of obs/hot.py are metadata of the compiled program,
    # and the key leaves metadata out by default: a program compiled before
    # a scope was added or renamed would be loaded back with its old names,
    # and a profile of it would name its operations wrongly. With metadata
    # in the key a relaunch of the same code still hits; an edit that moves
    # traced lines compiles once more.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # ... and the metadata names source files: without the checkout's own
    # path in them the key is the same wherever the checkout lies
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        re.escape(REPO_ROOT + os.sep),
    )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    logger.info("persistent XLA compilation cache at %s", cache_dir)
    return cache_dir
