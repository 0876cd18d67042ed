"""In-job distributed helpers (the analog of torchx.distributed).

Reference analog: torchx/distributed/__init__.py (303 LoC) — rank/world-size
helpers, ``init_pg``, rank0-first barriers over torch.distributed. Here the
substrate is ``jax.distributed`` + the launcher-injected gang env
(TPX_REPLICA_ID / TPX_NUM_REPLICAS / TPX_COORDINATOR_HOST): user code calls
:func:`init_from_env` once (or relies on ``dist.spmd``'s bootstrap which
does it automatically) and then uses plain jax collectives.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

from torchx_tpu import settings

_initialized = False


def is_tpx_job() -> bool:
    """True when running inside a tpx-launched replica."""
    return settings.ENV_TPX_APP_ID in os.environ


def gang_info() -> tuple[int, int, str]:
    """(process_id, num_processes, coordinator_host) from the injected env,
    falling back to GKE's TPU_WORKER_* variables when the launcher vars are
    absent (e.g. hand-rolled podslice jobs). The single source of truth —
    the spmd bootstrap uses this same parser."""
    raw = os.environ.get(settings.ENV_TPX_REPLICA_ID)
    if raw is None:
        # multi-slice backends that can't do arithmetic at pod start inject
        # the (slice_id, host_id, hosts_per_slice) decomposition instead
        slice_id = os.environ.get(settings.ENV_TPX_SLICE_ID)
        host_id = os.environ.get(settings.ENV_TPX_HOST_ID)
        per_slice = os.environ.get(settings.ENV_TPX_HOSTS_PER_SLICE)
        if slice_id is not None and host_id is not None and per_slice is not None:
            raw = str(int(slice_id) * int(per_slice) + int(host_id))
    process_id = int(raw or os.environ.get(settings.ENV_TPU_WORKER_ID) or 0)
    num = int(os.environ.get(settings.ENV_TPX_NUM_REPLICAS) or 0)
    coordinator = os.environ.get(settings.ENV_TPX_COORDINATOR_HOST, "")
    if not coordinator:
        hostnames = os.environ.get(settings.ENV_TPU_WORKER_HOSTNAMES, "")
        hosts = [h.strip() for h in hostnames.split(",") if h.strip()]
        if hosts:
            coordinator = hosts[0]
            num = num or len(hosts)
    return process_id, num or 1, coordinator or "localhost"


def replica_id() -> int:
    """This process's global id within the role's gang (0-based)."""
    return gang_info()[0]


def num_replicas() -> int:
    """Total processes in the role's gang."""
    return gang_info()[1]


def coordinator_address(port: Optional[int] = None) -> str:
    """``host:port`` of replica 0 — the jax.distributed coordinator."""
    host = gang_info()[2]
    return f"{host}:{port or settings.TPX_COORDINATOR_PORT}"


def init_from_env(port: Optional[int] = None) -> None:
    """Initialize jax.distributed from the launcher-injected env. Safe to
    call multiple times, outside a tpx job (no-op for single process), and
    after the ``dist.spmd`` bootstrap already initialized the world.

    The analog of ``torchx.distributed.init_pg(backend="auto")``
    (reference distributed/__init__.py:164-227).
    """
    global _initialized
    if _initialized:
        return
    process_id, n, host = gang_info()
    if n > 1:
        import jax

        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=f"{host}:{port or settings.TPX_COORDINATOR_PORT}",
                num_processes=n,
                process_id=process_id,
            )
    _initialized = True


def local_device_count() -> int:
    """Accelerator devices attached to THIS process."""
    import jax

    return jax.local_device_count()


def world_device_count() -> int:
    """Accelerator devices across the whole gang."""
    import jax

    return jax.device_count()


def is_process_zero() -> bool:
    """True on the gang's coordinator process (logging/checkpoint guard)."""
    import jax

    return jax.process_index() == 0


@contextlib.contextmanager
def on_process_zero_first() -> Iterator[None]:
    """Process 0 runs the body before everyone else (download-once pattern;
    analog of ``on_rank0_first``, reference distributed/__init__.py:230-303).

    Uses a jax collective as the barrier, so call only after device init.
    """
    import jax
    import jax.numpy as jnp

    def barrier() -> None:
        if jax.process_count() > 1:
            # tiny global psum = cross-process barrier
            jax.block_until_ready(
                jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
                    jnp.ones((jax.local_device_count(),))
                )
            )

    if is_process_zero():
        yield
        barrier()
    else:
        barrier()
        yield
