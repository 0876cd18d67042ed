"""Token-memmap input pipeline for the trainer.

Loads the packed uint32 binary that :mod:`datapreproc` writes, slices it
into per-process shards (each JAX process reads only its contiguous range
and materializes only its own rows of the global batch), and yields
device-resident batches with one host->device copy in flight (simple
double-buffer prefetch; XLA overlaps the copy with the previous step).

Batch sampling is seeded per (seed, process, step), so a job resumed from
checkpoint step N continues the stream at step N instead of replaying
steps 1..N (pass ``start_step``).
"""

from __future__ import annotations

import itertools
from typing import Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from torchx_tpu.parallel.mesh import BATCH_SPEC


class TokenDataset:
    """Random-crop batches of ``seq+1`` tokens from a memmapped corpus.

    ``batch`` is the GLOBAL batch size; each process yields its
    ``batch / process_count`` local rows.
    """

    def __init__(
        self,
        path: str,
        seq: int,
        batch: int,
        seed: int = 0,
        start_step: int = 0,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
    ) -> None:
        data = np.memmap(path, dtype=np.uint32, mode="r")
        pi = process_index if process_index is not None else jax.process_index()
        pc = process_count if process_count is not None else jax.process_count()
        if batch % pc:
            raise ValueError(f"global batch {batch} not divisible by {pc} processes")
        shard_len = len(data) // pc
        if shard_len < seq + 1:
            raise ValueError(
                f"corpus shard ({shard_len} tokens) smaller than seq+1={seq + 1}"
            )
        self._data = data[pi * shard_len : (pi + 1) * shard_len]
        self._seq = seq
        self._local_batch = batch // pc
        self._seed = seed
        self._start_step = start_step
        self._pi = pi

    def __iter__(self) -> Iterator[np.ndarray]:
        # valid crop starts are [0, len - (seq+1)]; integers() high is
        # exclusive, so the bound is len - seq
        n = len(self._data) - self._seq
        for step in itertools.count(self._start_step):
            rng = np.random.default_rng((self._seed, self._pi, step))
            starts = rng.integers(0, n, size=self._local_batch)
            yield np.stack(
                [self._data[s : s + self._seq + 1] for s in starts]
            ).astype(np.int32)


def device_batches(
    dataset: TokenDataset, mesh: Mesh, prefetch: int = 2
) -> Iterator[dict[str, jax.Array]]:
    """Yield sharded device batches with host production AND the
    host->device transfer running ahead of the consumer.

    A daemon thread assembles up to ``prefetch`` host batches (memmap
    reads + crop stacking) while the device runs the current step; the
    consumer side additionally keeps one async device transfer in flight.
    Each process contributes only its local rows
    (``jax.make_array_from_process_local_data``) — no duplicated host IO
    across the slice. Ordering (and therefore the seeded, resumable
    stream) is preserved: one producer, FIFO queue.
    """
    import queue
    import threading

    sharding = NamedSharding(mesh, BATCH_SPEC)

    def put(local_rows: np.ndarray) -> jax.Array:
        return jax.make_array_from_process_local_data(sharding, local_rows)

    q: "queue.Queue[object]" = queue.Queue(maxsize=max(1, prefetch))
    stop = threading.Event()
    done = object()  # exhaustion sentinel (TokenDataset is infinite, but
    # the helper accepts any iterable — ending must not hang the consumer)

    def _offer(item: object) -> None:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.5)
                return
            except queue.Full:
                continue

    def producer() -> None:
        try:
            for rows in dataset:
                _offer(rows)
                if stop.is_set():
                    return
            _offer(done)
        except BaseException as e:  # noqa: BLE001 - re-raised on the consumer side
            _offer(e)

    threading.Thread(target=producer, daemon=True, name="tpx-data-prefetch").start()

    def take() -> Optional[np.ndarray]:
        item = q.get()
        if item is done:
            return None
        if isinstance(item, BaseException):
            # a data error must fail the job loudly, not hang the loop
            raise item
        return item  # type: ignore[return-value]

    try:
        first = take()
        if first is None:
            return
        pending = put(first)
        while True:
            # dispatch batch N+1's host->device copy BEFORE yielding batch
            # N, so the transfer overlaps the consumer's running step
            nxt = take()  # host batch; None = dataset exhausted
            nxt_dev = put(nxt) if nxt is not None else None
            yield {"tokens": pending}
            if nxt_dev is None:
                return
            pending = nxt_dev
    finally:
        stop.set()  # generator closed/GC'd: release the producer thread
