"""Streaming input pipeline: sharded sources, producer ring, device prefetch.

The engine's training numbers have always come from datasets staged
resident before the first step; anything bigger serializes input
assembly against compute and the MFU line silently lies about it
(ROADMAP open item 1). This package is the streaming path:

- :class:`ArraySource` — an indexable ``(x, y)`` sample source:
  in-memory arrays or memory-mapped ``.npy`` files (reads materialize
  per batch, so the dataset never has to fit in RAM).
- :class:`InputPipeline` — per-host **sharded iteration** (each rank
  draws from its own contiguous shard, per-epoch per-rank shuffle —
  the :class:`~torchmpi_tpu.utils.data.DistributedIterator` contract),
  assembled by ``input_workers`` background producer threads feeding a
  bounded **reorder ring** of ``input_prefetch_batches`` contiguous
  host buffers, with the host-to-device transfer **double-buffered**
  like the PS ``ps_prefetch`` path: the pipeline dispatches batch
  k+1's ``device_put`` before handing out batch k, so ``next()``
  returns an already device-resident batch while the next transfer is
  in flight.

Producers are pure numpy — never jax. The XLA CPU backend executes
collectives as blocking rendezvous on the host thread pool, and a
background-thread jax dispatch can deadlock it on low-core machines
(see ``DistributedIterator._device_transfer_in_producer``); keeping
device work on the consumer thread sidesteps the hazard on every
platform while the async ``device_put`` still overlaps the transfer
with the training step.

Delivery is **in-order and lossless** regardless of worker count: the
ring admits batch b only inside the reorder window
``[next_emit, next_emit + depth)`` and the consumer pops strictly
sequentially. A producer that dies mid-epoch fails the ring and the
consumer raises :class:`InputProducerError` — never a silent
truncation of the epoch.

``tm_input_*`` telemetry makes "input-bound" a measured verdict:
``tm_input_queue_depth`` (staged batches ahead of the consumer — 0
means the producers can't keep up), producer/consumer stall counters,
and a delivered-batch counter the engine's ``mfu_incl_input``
accounting joins against.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Optional, Tuple

import numpy as np

from .. import constants, telemetry as _telemetry

_ring = _telemetry.spans  # the process-global span recorder
_names = _telemetry.names
_MET = None


def _metric_handles():
    global _MET
    if _MET is None:
        m = _telemetry.metrics
        _MET = (
            m.gauge(
                "tm_input_queue_depth",
                "host batches staged ahead of the consumer in the input "
                "ring (sampled at each delivery; persistently 0 means "
                "the producers cannot keep up — input-bound)",
            ),
            m.counter(
                "tm_input_producer_stall_seconds",
                "seconds producer workers spent blocked on ring space "
                "(the consumer is the bottleneck — compute-bound)",
            ),
            m.counter(
                "tm_input_consumer_stall_seconds",
                "seconds the consumer spent waiting for the next host "
                "batch (the producers are the bottleneck — input-bound; "
                "the engine subtracts this window from its MFU step "
                "accounting)",
            ),
            m.counter(
                "tm_input_batches_total",
                "batches delivered by the input pipeline, by path "
                "(host=assembled by a producer, device=made resident)",
            ),
        )
    return _MET


class InputProducerError(RuntimeError):
    """A background input producer died; the epoch cannot complete.

    Raised by the consumer on its next fetch — producer death is LOUD,
    never a silently truncated epoch — with the producer's exception as
    ``__cause__``."""


class ArraySource:
    """An indexable ``(x, y)`` sample source.

    Accepts anything numpy can fancy-index — in-memory arrays or
    ``np.load(..., mmap_mode='r')`` memmaps (:meth:`from_npy`), so an
    on-disk dataset streams per batch instead of staging resident."""

    def __init__(self, x, y):
        if len(x) != len(y):
            raise ValueError(
                f"x has {len(x)} samples but y has {len(y)}"
            )
        self.x, self.y = x, y

    def __len__(self) -> int:
        return len(self.x)

    @classmethod
    def from_npy(cls, x_path, y_path, mmap: bool = True) -> "ArraySource":
        """Open on-disk ``.npy`` arrays, memory-mapped by default."""
        mode = "r" if mmap else None
        return cls(
            np.load(x_path, mmap_mode=mode), np.load(y_path, mmap_mode=mode)
        )

    def gather(self, idx: np.ndarray):
        """Materialize the samples at ``idx`` as contiguous host arrays
        (the ring's transfer-ready buffers; memmap reads land here)."""
        return (
            np.ascontiguousarray(self.x[idx]),
            np.ascontiguousarray(self.y[idx]),
        )


class _Ring:
    """Bounded reorder window between producer workers and the consumer.

    Workers insert batch ``b`` only when it falls inside
    ``[next_emit, next_emit + depth)`` (blocking otherwise — the
    bounded-buffer backpressure); the consumer pops strictly in order.
    One lock, one condition: every state change notifies everyone."""

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self.cv = threading.Condition()
        self.slots: dict = {}
        self.next_emit = 0
        self.next_ticket = 0
        self.error: Optional[BaseException] = None
        self.closed = False

    def ticket(self, total: int) -> Optional[int]:
        """Claim the next batch ordinal to assemble; None when the epoch
        is fully claimed (or the ring shut down)."""
        with self.cv:
            if self.closed or self.error is not None \
                    or self.next_ticket >= total:
                return None
            t = self.next_ticket
            self.next_ticket += 1
            return t

    def put(self, idx: int, item) -> float:
        """Insert batch ``idx``; returns seconds spent blocked on window
        space (the producer-stall telemetry)."""
        stall = 0.0
        with self.cv:
            while (
                idx >= self.next_emit + self.depth
                and self.error is None
                and not self.closed
            ):
                t0 = time.perf_counter()
                self.cv.wait(0.1)
                stall += time.perf_counter() - t0
            if self.error is None and not self.closed:
                self.slots[idx] = item
                self.cv.notify_all()
        return stall

    def fail(self, exc: BaseException) -> None:
        with self.cv:
            if self.error is None:
                self.error = exc
            self.cv.notify_all()

    def close(self) -> None:
        with self.cv:
            self.closed = True
            self.slots.clear()
            self.cv.notify_all()

    def get(self, alive: Callable[[], bool]) -> Tuple[Any, float, int]:
        """Pop the next in-order batch; returns ``(item, stall_seconds,
        staged_ahead)``. Raises :class:`InputProducerError` when a
        producer died (or silently vanished) before delivering it."""
        stall = 0.0
        with self.cv:
            while self.next_emit not in self.slots:
                if self.error is not None:
                    raise InputProducerError(
                        "input producer died mid-epoch"
                    ) from self.error
                if self.closed:
                    raise InputProducerError("input ring closed mid-epoch")
                if not alive():
                    raise InputProducerError(
                        "every input producer exited without delivering "
                        f"batch {self.next_emit}"
                    )
                t0 = time.perf_counter()
                self.cv.wait(0.1)
                stall += time.perf_counter() - t0
            item = self.slots.pop(self.next_emit)
            self.next_emit += 1
            depth_now = len(self.slots)
            self.cv.notify_all()
        return item, stall, depth_now


class InputPipeline:
    """Per-host sharded streaming iterator with producer ring + device
    prefetch (see the module notes for the full contract).

    Yields rank-stacked device batches ``(x[p, B/p, ...], y[p, B/p])``
    ready for the engine's ``[p, B, ...]`` batch format, placed on
    ``sharding`` when given. ``__call__`` starts one epoch (the
    ``engine.train(iterator_fn)`` shape); each epoch advances the
    per-rank shuffle like :class:`~torchmpi_tpu.utils.data.
    DistributedIterator`. Partial tail batches are dropped (static
    shapes keep the jitted step from recompiling).

    ``prefetch``/``workers`` default to the ``input_prefetch_batches``
    / ``input_workers`` constants; ``transform`` optionally runs per
    batch inside the producer (augmentation, casting — pure host code
    only)."""

    def __init__(
        self,
        source,
        batch_size: int,
        num_ranks: int,
        shuffle: bool = True,
        seed: int = 0,
        sharding=None,
        prefetch: Optional[int] = None,
        workers: Optional[int] = None,
        transform: Optional[Callable] = None,
    ):
        if isinstance(source, tuple):
            source = ArraySource(*source)
        if batch_size < num_ranks or batch_size % num_ranks != 0:
            raise ValueError(
                f"global batch {batch_size} must be a positive multiple "
                f"of the {num_ranks} ranks (>= one sample per rank)"
            )
        self.source = source
        self.batch_size = batch_size
        self.p = num_ranks
        self.per_rank = batch_size // num_ranks
        self.shuffle = shuffle
        self.seed = seed
        self.sharding = sharding
        self.transform = transform
        self.prefetch = max(1, int(
            prefetch if prefetch is not None
            else constants.get("input_prefetch_batches")
        ))
        self.workers = max(1, int(
            workers if workers is not None
            else constants.get("input_workers")
        ))
        n = len(source)
        self.shard_len = n // num_ranks
        self.batches_per_epoch = self.shard_len // self.per_rank
        if self.batches_per_epoch == 0:
            raise ValueError(
                f"dataset of {n} samples is too small for {num_ranks} "
                f"ranks x {self.per_rank} per-rank batch"
            )
        self._epoch = 0
        #: seconds the consumer stalled waiting on producers, summed
        #: over the pipeline's lifetime — the engine's input-stall join
        self.consumer_stall_s = 0.0

    def __len__(self) -> int:
        return self.batches_per_epoch

    # -- deterministic sharded index plan (pure; tests drive it directly)
    def epoch_order(self, epoch: int) -> np.ndarray:
        """The ``[p, shard_len]`` global-index plan of one epoch: rank r
        draws from its contiguous shard ``[r*shard_len, (r+1)*shard_len)``,
        permuted per epoch by ``RandomState(seed + epoch)`` — a pure
        function of (seed, epoch, world size), identical however many
        workers assemble it."""
        if not self.shuffle:
            return np.arange(self.shard_len * self.p).reshape(
                self.p, self.shard_len
            )
        rs = np.random.RandomState(self.seed + epoch)
        return np.stack([
            r * self.shard_len + rs.permutation(self.shard_len)
            for r in range(self.p)
        ])

    def batch_indices(self, epoch: int, b: int) -> np.ndarray:
        """Global sample indices ``[p, per_rank]`` of batch ``b``."""
        order = self.epoch_order(epoch)
        return order[:, b * self.per_rank:(b + 1) * self.per_rank]

    # -- producer side (pure numpy; see module notes)
    def _assemble(self, order: np.ndarray, b: int):
        idx = order[:, b * self.per_rank:(b + 1) * self.per_rank]
        xb, yb = self.source.gather(idx)
        if self.transform is not None:
            xb, yb = self.transform(xb, yb)
        return xb, yb

    def _producer(self, ring: _Ring, order: np.ndarray, total: int,
                  epoch: int, cause: int) -> None:
        """``cause``: the id of the epoch's ``input.epoch_start`` span,
        which started this thread; an assembly's step is the pipeline's
        own count, ``(epoch, batch)``."""
        try:
            telemetry_on = _telemetry.enabled()
            while True:
                b = ring.ticket(total)
                if b is None:
                    return
                with _ring.span(_names.INPUT_ASSEMBLE, parent=cause,
                                step=(epoch, b)):
                    host = self._assemble(order, b)
                stall = ring.put(b, host)
                if telemetry_on:
                    _, prod_stall, _, batches = _metric_handles()
                    if stall:
                        prod_stall.inc(stall)
                    batches.inc(path="host")
        except BaseException as e:  # noqa: BLE001 - any producer death
            # must surface on the consumer, not vanish with the thread
            ring.fail(e)

    # -- consumer side
    def _stage(self, host_batch):
        """Dispatch the host batch's device transfer (async — the
        double-buffer's in-flight leg)."""
        import jax
        import jax.numpy as jnp

        xb, yb = host_batch
        if self.sharding is not None:
            # one sharding for both legs, or a (x_sharding, y_sharding)
            # pair when the legs shard differently (e.g. tokens over a
            # 2-D dp x sp mesh, labels replicated)
            xs, ys = (
                self.sharding
                if isinstance(self.sharding, (tuple, list))
                else (self.sharding, self.sharding)
            )
            return jax.device_put(xb, xs), jax.device_put(yb, ys)
        return jnp.asarray(xb), jnp.asarray(yb)

    def _fetch(self, ring: _Ring, alive: Callable[[], bool]):
        """Wait for the next host batch and dispatch its copy to the
        device."""
        with _ring.span(_names.INPUT_RING_WAIT):
            host, stall, depth_now = ring.get(alive)
        self.consumer_stall_s += stall
        if _telemetry.enabled():
            qdepth, _, cons_stall, batches = _metric_handles()
            qdepth.set(depth_now)
            if stall:
                cons_stall.inc(stall)
            batches.inc(path="device")
        with _ring.span(_names.INPUT_STAGE):
            return self._stage(host)

    def _run_epoch(self, epoch: int):
        order = self.epoch_order(epoch)
        total = self.batches_per_epoch
        ring = _Ring(self.prefetch)
        threads = []

        def alive() -> bool:
            return any(t.is_alive() for t in threads)

        staged = deque()
        try:
            # what an epoch pays before its first batch: new producer
            # threads, an empty ring, and the double buffer, which hands
            # out batch k only once batch k+1's copy is dispatched
            with _ring.span(_names.INPUT_EPOCH_START,
                            {"epoch": epoch}) as start:
                threads += [
                    threading.Thread(
                        target=self._producer,
                        args=(ring, order, total, epoch, start.id),
                        name=f"tm-input-{epoch}-{w}", daemon=True,
                    )
                    for w in range(min(self.workers, total))
                ]
                for t in threads:
                    t.start()
                for _ in range(min(2, total)):
                    staged.append(self._fetch(ring, alive))
            # hand out batch k while batch k+1's transfer, dispatched one
            # fetch ago, overlaps the caller's training step
            for _ in range(total - len(staged)):
                yield staged.popleft()
                staged.append(self._fetch(ring, alive))
            while staged:
                yield staged.popleft()
        finally:
            ring.close()

    def __iter__(self):
        epoch = self._epoch
        self._epoch += 1
        return self._run_epoch(epoch)

    def __call__(self):
        """One epoch's iterator — the ``engine.train(iterator_fn)``
        calling convention."""
        return iter(self)


__all__ = [
    "ArraySource",
    "InputPipeline",
    "InputProducerError",
]
