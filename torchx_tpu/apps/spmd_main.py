"""SPMD bootstrap: initialize jax.distributed on every host, then run user code.

This is the TPU analog of the reference's torchrun invocation
(torchx/components/dist.py:261-287): where torchrun rendezvouses N agents
via a c10d TCPStore and forks workers, a TPU slice runs ONE JAX process per
host and `jax.distributed.initialize` connects them through the coordinator
service. The launcher injects the gang identity (TPX_REPLICA_ID /
TPX_NUM_REPLICAS / TPX_COORDINATOR_HOST); this module turns it into a live
`jax.distributed` world and then execs the user script/module in-process.

Usage (as built by components.dist.spmd):

    python -m torchx_tpu.apps.spmd_main [--port P] (--script S | -m MOD) [args...]

Structured errors are written to $TPX_ERROR_FILE on failure so the
launcher's status surface shows root cause (reference analog: torchelastic
error files, local_scheduler.py:996-1001).
"""

from __future__ import annotations

import argparse
import os
import runpy
import socket
import sys
import time
import traceback
from contextlib import nullcontext
from typing import Any, ContextManager

from torchx_tpu import settings

_PROCESS_START = time.monotonic()


def _job_span(name: str, **attrs: Any) -> ContextManager[Any]:
    """A span joining the client's trace via the injected $TPX_TRACE_ID /
    $TPX_PARENT_SPAN context, or a no-op when this process was not
    launched under tracing (keeps bare `python -m spmd_main` runs from
    minting orphan traces)."""
    if not os.environ.get(settings.ENV_TPX_TRACE_ID):
        return nullcontext()
    from torchx_tpu.obs import trace as obs_trace

    return obs_trace.span(name, **attrs)


def _gang() -> tuple[int, int, str]:
    """(process_id, num_processes, coordinator_host) — shared parser in
    torchx_tpu.distributed so user code and the bootstrap agree."""
    from torchx_tpu.distributed import gang_info

    return gang_info()


def _wait_for_coordinator(host: str, port: int, timeout: float = 300.0) -> None:
    """Non-coordinator hosts wait for the coordinator socket so slow pod
    starts don't fail the gang (launch-latency critical path)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, port), timeout=2):
                return
        except OSError:
            time.sleep(0.25)
    raise TimeoutError(f"coordinator {host}:{port} unreachable after {timeout}s")


def initialize_distributed(port: int) -> None:
    process_id, num_processes, coordinator = _gang()
    # surface the resolved GLOBAL id to user code even when the backend
    # injected only the (slice, host) decomposition (e.g. GKE multi-slice)
    os.environ.setdefault(settings.ENV_TPX_REPLICA_ID, str(process_id))
    if num_processes <= 1:
        return  # single process: jax works without a coordinator
    from torchx_tpu import distributed as tpx_dist

    if process_id != 0:
        _wait_for_coordinator(coordinator, port)
    # init through the shared helper so a user script that also calls
    # init_from_env() sees the world as already initialized
    tpx_dist.init_from_env(port)


def write_error_file(exc: BaseException) -> None:
    error_file = os.environ.get(settings.ENV_TPX_ERROR_FILE)
    if not error_file:
        return
    try:
        os.makedirs(os.path.dirname(error_file), exist_ok=True)
        from torchx_tpu.specs.api import make_structured_error

        payload = make_structured_error(
            f"{type(exc).__name__}: {exc}\n{traceback.format_exc()}", exitcode=1
        )
        with open(error_file, "w") as f:
            f.write(payload)
    except OSError:
        pass


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(prog="spmd_main", description=__doc__)
    parser.add_argument("--port", type=int, default=settings.TPX_COORDINATOR_PORT)
    parser.add_argument("--no-init", action="store_true", help="skip jax.distributed")
    group = parser.add_mutually_exclusive_group(required=True)
    group.add_argument("--script", help="path to user python script")
    group.add_argument("-m", dest="module", help="user python module")
    args, rest = parser.parse_known_args(argv)
    if rest and rest[0] == "--":
        rest = rest[1:]

    try:
        with _job_span(
            "job.bootstrap",
            replica=os.environ.get(settings.ENV_TPX_REPLICA_ID),
            no_init=args.no_init or None,
            # interpreter+import time already paid before bootstrap began
            # (the "import" slice of the launch.breakdown family)
            import_s=round(time.monotonic() - _PROCESS_START, 3),
        ):
            if not args.no_init:
                initialize_distributed(args.port)
        sys.argv = [args.script or args.module, *rest]
        if os.environ.get(settings.ENV_TPX_TRACE_ID):
            # instantaneous marker: distributed init is done, user code
            # starts now — the in-job half of launch latency
            from torchx_tpu.obs import trace as obs_trace

            obs_trace.heartbeat(
                "job.exec",
                replica=os.environ.get(settings.ENV_TPX_REPLICA_ID),
                target=args.script or args.module,
            )
        if args.script:
            runpy.run_path(args.script, run_name="__main__")
        else:
            runpy.run_module(args.module, run_name="__main__", alter_sys=True)
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (0 if e.code is None else 1)
        if code != 0:
            write_error_file(e)
        raise
    except BaseException as e:
        write_error_file(e)
        raise


if __name__ == "__main__":
    main()
