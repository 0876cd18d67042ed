"""Double-buffered device input prefetch for the training loop.

The step-time budget of an SPMD trainer has exactly two host-visible
pieces: time the device spends computing, and time the host spends
producing the next batch (memmap reads, crop stacking, the host->device
transfer) while the device sits idle. :class:`Prefetcher` moves the second
piece off the critical path: a producer thread stays up to ``depth``
batches ahead of the consumer, running batch assembly AND the sharded
``device_put`` of batch N+1 concurrently with the device computing batch
N. The consumer's ``next()`` then usually finds a finished device array
waiting in the queue — and every microsecond it *does* block is accounted
in :attr:`Prefetcher.data_wait_s`, so the trainer can report the
data-wait vs compute split instead of guessing (bench.py surfaces it as
``data_wait_frac``). Under a ``jax.profiler`` session the blocking ``get``
is the span ``train.data_wait`` on the consumer's line and each placement
``train.h2d`` on the producer's (:mod:`torchx_tpu.obs.hot`).

Depth semantics:

* ``depth >= 1`` — a daemon producer thread plus a FIFO queue of that
  size; ordering is preserved (one producer, one queue), so seeded,
  resumable data streams stay deterministic.
* ``depth == 0`` — synchronous passthrough: no thread, ``next()`` runs
  the source and placement inline (the pre-prefetch behavior), still
  timed as data wait.

Errors raised by the source or placement propagate to the consumer's
``next()`` call — a data error fails the job loudly rather than hanging
the loop. :meth:`Prefetcher.close` (also the context-manager exit) drains
and joins the producer so early loop exits never leak a thread blocked on
a full queue.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from torchx_tpu.obs import hot
from torchx_tpu.parallel.mesh import BATCH_SPEC

_DONE = object()  # source exhausted


class _Failure:
    """Exception crossing the thread boundary (kept distinct from batch
    values so an iterator of exception *objects* would still round-trip)."""

    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


class Prefetcher:
    """Iterator staying up to ``depth`` placed batches ahead of its consumer.

    ``source`` is any iterable of batches; ``place`` (optional) maps each
    raw batch to its device-resident form — e.g. a sharded ``device_put``
    (see :func:`device_prefetch`) — and runs ON THE PRODUCER THREAD, so
    transfers overlap compute. With ``depth=0`` everything runs inline in
    ``next()`` (passthrough mode).
    """

    def __init__(
        self,
        source: Iterable[Any],
        *,
        depth: int = 2,
        place: Optional[Callable[[Any], Any]] = None,
        name: str = "tpx-prefetch",
    ) -> None:
        if depth < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {depth}")
        self._source = iter(source)
        self._place = place if place is not None else (lambda x: x)
        self._depth = depth
        self._wait_s = 0.0
        self._wait_observer: Optional[Callable[[float], None]] = None
        self._served = 0
        self._closed = False
        self._exhausted = False
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        if depth > 0:
            self._queue = queue.Queue(maxsize=depth)
            self._thread = threading.Thread(
                target=self._produce, daemon=True, name=name
            )
            self._thread.start()

    # -- producer side -----------------------------------------------------

    def _offer(self, item: Any) -> None:
        # bounded put that stays responsive to close(): never block forever
        # on a queue the consumer stopped draining
        assert self._queue is not None
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.2)
                return
            except queue.Full:
                continue

    def _produce(self) -> None:
        try:
            for raw in self._source:
                with hot.span(hot.TRAIN_H2D):
                    placed = self._place(raw)
                self._offer(placed)
                if self._stop.is_set():
                    return
            self._offer(_DONE)
        except BaseException as e:  # noqa: BLE001 - re-raised on the consumer side
            self._offer(_Failure(e))

    # -- consumer side -----------------------------------------------------

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self._closed or self._exhausted:
            raise StopIteration
        t0 = time.monotonic()
        try:
            if self._queue is None:  # depth=0 passthrough
                try:
                    with hot.span(hot.TRAIN_DATA_WAIT):
                        return self._place(next(self._source))
                except StopIteration:
                    self._exhausted = True
                    raise
            with hot.span(hot.TRAIN_DATA_WAIT):
                item = self._queue.get()
            if item is _DONE:
                self._exhausted = True
                raise StopIteration
            if isinstance(item, _Failure):
                self._exhausted = True
                raise item.exc
            self._served += 1
            return item
        finally:
            dt = time.monotonic() - t0
            self._wait_s += dt
            if self._wait_observer is not None:
                try:
                    self._wait_observer(dt)
                except Exception:  # noqa: BLE001 - observers never break the loop
                    pass

    def set_wait_observer(
        self, observer: Optional[Callable[[float], None]]
    ) -> None:
        """Install a per-``next()`` wait callback (seconds blocked).

        Runs on the CONSUMER thread inside ``next()`` — the step
        profiler's ``observe_wait`` hook, which credits each blocked
        interval to the current step's ``data_wait`` phase instead of
        only the run-total :attr:`data_wait_s`. Best-effort: observer
        exceptions are swallowed. Pass None to uninstall.
        """
        self._wait_observer = observer

    @property
    def data_wait_s(self) -> float:
        """Cumulative seconds the consumer spent blocked waiting for data
        (queue waits, or inline production time in passthrough mode)."""
        return self._wait_s

    @property
    def batches_served(self) -> int:
        """Batches handed to the consumer so far (excludes queued ones)."""
        return self._served

    def close(self) -> None:
        """Stop the producer and join its thread (idempotent).

        Safe at any point — including mid-stream early exit with the
        producer blocked on a full queue: the stop event breaks its
        bounded put, the queue is drained, and the thread is joined.
        """
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._queue is not None:
            while True:  # unblock a producer waiting on a full queue
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc_info: Any) -> None:
        self.close()


def sharded_put(mesh: Mesh, spec: PartitionSpec = BATCH_SPEC) -> Callable[[Any], Any]:
    """A ``place`` callable moving host batches onto ``mesh`` under ``spec``.

    Dict batches place each leaf; host numpy arrays go through
    ``make_array_from_process_local_data`` (each process contributes only
    its local rows — same multi-host contract as examples/data.py);
    already-committed ``jax.Array`` leaves pass through untouched.
    """
    sharding = NamedSharding(mesh, spec)

    def put_leaf(x: Any) -> Any:
        if isinstance(x, jax.Array) and getattr(x, "sharding", None) == sharding:
            return x
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    def place(batch: Any) -> Any:
        if isinstance(batch, dict):
            return {k: put_leaf(v) for k, v in batch.items()}
        return put_leaf(batch)

    return place


def device_prefetch(
    source: Iterable[Any],
    mesh: Mesh,
    *,
    depth: int = 2,
    spec: PartitionSpec = BATCH_SPEC,
    name: str = "tpx-prefetch",
) -> Prefetcher:
    """:class:`Prefetcher` over host batches with sharded placement onto
    ``mesh`` — the one-call spelling the trainer uses."""
    return Prefetcher(source, depth=depth, place=sharded_put(mesh, spec), name=name)
