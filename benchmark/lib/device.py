"""The device as JAX reports it, and the guards every run keeps."""

from __future__ import annotations

import threading
import time

import jax


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int, allow_cpu: bool = False) -> dict:
    """``platform``, ``kind`` and ``count`` of the devices in use; exits with
    a code other than 0 where there is no TPU or too few chips."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not allow_cpu:
        raise NoChip(f"benchmark: no TPU here (platform={d.platform}); nothing measured")
    if len(devs) < chips:
        raise NoChip(f"benchmark: cell asks for {chips} chips, JAX finds {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip, 0 where the backend reports none."""
    return memory_stats(chips)["peak_bytes_in_use"]


def memory_stats(chips: int = 1) -> dict:
    """``bytes_in_use``, ``peak_bytes_in_use`` and ``bytes_limit`` of the chip
    whose peak is highest; each 0 where the backend reports none."""
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    all_stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    fullest = max(all_stats, key=lambda st: st.get("peak_bytes_in_use", 0), default={})
    return {k: int(fullest.get(k, 0)) for k in keys}


class CompileCounter:
    """Counts XLA compilations and compilation-cache loads, with their times,
    so that a run can show none fell inside its measured window."""

    _EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: object) -> None:
        if event in self._EVENTS:
            with self._lock:
                self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for t in self.times if t0 <= t <= t1)
