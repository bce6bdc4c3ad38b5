"""Dataset + distributed input pipeline.

Analog of ``examples/mnist/makeiterator.lua``: the global batch is divided by
world size (``batch 336/size``, makeiterator.lua:31) and each rank sees its
own partition of the dataset; iterators support prefetching the next batch
while the current step computes (``sgdengine.lua:118-124``'s
``iterator:prefetch()``).

This environment has no network egress and no local MNIST archive, so
``synthetic_mnist`` generates a deterministic MNIST-shaped classification
dataset (class-prototype + noise images, 784 features, 10 classes). The
convergence *test strategy* is unchanged from the reference: distributed
training must match the sequential baseline's loss on the same data
(``examples/mnist/mnist_allreduce.lua:87-113``). ``load_mnist_idx`` reads
real MNIST IDX files when a directory is provided.
"""

from __future__ import annotations

import queue
import struct
import threading
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def synthetic_mnist(
    num_train: int = 8192,
    num_test: int = 2048,
    num_classes: int = 10,
    seed: int = 1234,
    image_shape: Tuple[int, int] = (28, 28),
):
    """Deterministic MNIST-shaped dataset: each class is a smoothed random
    prototype image; samples are prototype + gaussian noise, clipped to
    [0, 1]. Linearly separable enough for logistic regression to reach low
    error in a few epochs, like real MNIST."""
    rng = np.random.RandomState(seed)
    h, w = image_shape
    protos = rng.randn(num_classes, h * w).astype(np.float32)
    # Smooth prototypes to make pixels locally correlated (image-like).
    protos = protos.reshape(num_classes, h, w)
    for _ in range(2):
        protos = (
            protos
            + np.roll(protos, 1, axis=1)
            + np.roll(protos, -1, axis=1)
            + np.roll(protos, 1, axis=2)
            + np.roll(protos, -1, axis=2)
        ) / 5.0
    protos = protos.reshape(num_classes, h * w)
    protos /= np.abs(protos).max(axis=1, keepdims=True)

    def make(n, rs):
        labels = rs.randint(0, num_classes, size=n).astype(np.int32)
        x = protos[labels] + 0.9 * rs.randn(n, h * w).astype(np.float32)
        x = np.clip(0.5 + 0.5 * x, 0.0, 1.0).astype(np.float32)
        return x.reshape(n, h, w), labels

    train = make(num_train, np.random.RandomState(seed + 1))
    test = make(num_test, np.random.RandomState(seed + 2))
    return train, test


def synthetic_imagenet(
    num_train: int = 1024,
    num_test: int = 256,
    num_classes: int = 1000,
    image_size: int = 224,
    seed: int = 4321,
):
    """Deterministic ImageNet-shaped dataset (NHWC float32 in [0, 1]):
    class prototypes are smooth low-frequency color fields; samples add
    gaussian noise. Same role as ``synthetic_mnist`` for the ResNet
    data-parallel config (BASELINE.json config #4) in a zero-egress
    environment."""
    rng = np.random.RandomState(seed)
    h = w = image_size
    # low-res prototypes upsampled: cheap and image-like
    lo = 8
    protos_lo = rng.randn(num_classes, lo, lo, 3).astype(np.float32)
    reps = -(-h // lo)

    def upsample(p):
        big = np.repeat(np.repeat(p, reps, axis=0), reps, axis=1)
        return big[:h, :w]

    def make(n, rs):
        labels = rs.randint(0, num_classes, size=n).astype(np.int32)
        x = np.empty((n, h, w, 3), np.float32)
        for i in range(n):
            base = upsample(protos_lo[labels[i]])
            x[i] = base + 0.5 * rs.randn(h, w, 3).astype(np.float32)
        x = np.clip(0.5 + 0.25 * x, 0.0, 1.0)
        return x, labels

    train = make(num_train, np.random.RandomState(seed + 1))
    test = make(num_test, np.random.RandomState(seed + 2))
    return train, test


def synthetic_tokens(
    num_seqs: int = 512,
    seq_len: int = 1024,
    vocab: int = 8192,
    seed: int = 97,
):
    """Deterministic LM dataset: ``(tokens_in, tokens_target)`` int32 pairs
    of shape ``[num_seqs, seq_len]`` where target[t] = in[t+1]. The stream
    is an order-1 structured process (each token is a fixed affine map of
    its predecessor plus occasional jumps), so a model genuinely reduces
    loss by attending backwards — same zero-egress role as
    ``synthetic_mnist``."""
    rs = np.random.RandomState(seed)
    raw = np.empty((num_seqs, seq_len + 1), np.int64)
    raw[:, 0] = rs.randint(0, vocab, size=num_seqs)
    jumps = rs.rand(num_seqs, seq_len) < 0.05
    noise = rs.randint(0, vocab, size=(num_seqs, seq_len))
    for t in range(seq_len):
        step = (raw[:, t] * 31 + 17) % vocab
        raw[:, t + 1] = np.where(jumps[:, t], noise[:, t], step)
    tokens = raw.astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def load_mnist_idx(directory: str):
    """Load real MNIST from IDX files if present (no download)."""
    import gzip
    import os

    def read_images(path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            magic, n, h, w = struct.unpack(">IIII", f.read(16))
            assert magic == 2051
            return (
                np.frombuffer(f.read(), np.uint8)
                .reshape(n, h, w)
                .astype(np.float32)
                / 255.0
            )

    def read_labels(path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            magic, n = struct.unpack(">II", f.read(8))
            assert magic == 2049
            return np.frombuffer(f.read(), np.uint8).astype(np.int32)

    def find(stem):
        import glob

        hits = glob.glob(f"{directory}/{stem}*")
        if not hits:
            raise FileNotFoundError(f"{stem} under {directory}")
        return hits[0]

    return (
        (read_images(find("train-images")), read_labels(find("train-labels"))),
        (read_images(find("t10k-images")), read_labels(find("t10k-labels"))),
    )


class DistributedIterator:
    """Rank-partitioned minibatch iterator with background prefetch.

    Yields rank-stacked device batches ``(x[p, B/p, ...], y[p, B/p])``: the
    global batch of ``batch_size`` is split evenly over the communicator's
    ``p`` ranks (makeiterator.lua:31's ``batch/size``), each rank drawing
    from its own contiguous shard of the dataset (partitioned sampling).
    ``prefetch`` batches are staged onto devices ahead of consumption by a
    background thread.
    """

    def __init__(
        self,
        x: np.ndarray,
        y: np.ndarray,
        batch_size: int,
        num_ranks: int,
        shuffle: bool = True,
        seed: int = 0,
        prefetch: int = 2,
        sharding=None,
    ):
        # Note: partial tail batches are always dropped (static shapes keep
        # the jitted step from recompiling), like the reference's fixed
        # batch/size partitioning.
        if batch_size < num_ranks or batch_size % num_ranks != 0:
            raise ValueError(
                f"global batch {batch_size} must be a positive multiple of "
                f"the {num_ranks} ranks (>= one sample per rank)"
            )
        self.x, self.y = x, y
        self.batch_size = batch_size
        self.p = num_ranks
        self.per_rank = batch_size // num_ranks
        self.shuffle = shuffle
        self.seed = seed
        self.prefetch = max(1, prefetch)
        self.sharding = sharding
        n = len(x)
        self.shard_len = n // num_ranks
        self.batches_per_epoch = self.shard_len // self.per_rank
        if self.batches_per_epoch == 0:
            raise ValueError(
                f"dataset of {n} samples is too small for {num_ranks} ranks x "
                f"{self.per_rank} per-rank batch"
            )
        self._epoch = 0

    def __len__(self) -> int:
        return self.batches_per_epoch

    def _epoch_order(self) -> np.ndarray:
        if not self.shuffle:
            return np.arange(self.shard_len * self.p).reshape(
                self.p, self.shard_len
            )
        rs = np.random.RandomState(self.seed + self._epoch)
        # Each rank permutes within its own contiguous shard.
        return np.stack(
            [
                r * self.shard_len + rs.permutation(self.shard_len)
                for r in range(self.p)
            ]
        )

    def _host_batches(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        order = self._epoch_order()
        for b in range(self.batches_per_epoch):
            idx = order[:, b * self.per_rank : (b + 1) * self.per_rank]
            yield self.x[idx], self.y[idx]

    def _device_transfer_in_producer(self) -> bool:
        """Stage batches onto devices from the prefetch thread only on real
        accelerators. The XLA CPU backend executes collectives as blocking
        rendezvous on the host thread pool; on low-core machines a
        background-thread jax dispatch can starve one rendezvous participant
        and deadlock the whole program (observed: 8 virtual devices, 1 core,
        conv workload). On CPU the producer therefore stays pure-numpy and
        transfer happens in the consumer thread."""
        if self.sharding is None:
            return False
        devices = getattr(self.sharding, "device_set", None)
        if not devices:
            return False
        return next(iter(devices)).platform != "cpu"

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        stage_in_producer = self._device_transfer_in_producer()

        def put_on_device(xb, yb):
            # straight from host memory to each device's shard: a
            # jnp.asarray first would land the whole batch on device 0
            if self.sharding is not None:
                return (
                    jax.device_put(xb, self.sharding),
                    jax.device_put(yb, self.sharding),
                )
            return jnp.asarray(xb), jnp.asarray(yb)

        def producer():
            try:
                for xb, yb in self._host_batches():
                    if stop.is_set():
                        return
                    q.put(put_on_device(xb, yb) if stage_in_producer else (xb, yb))
            finally:
                # Deliver the end-of-epoch sentinel without risking a
                # permanent block: if the consumer broke early (stop set) the
                # queue may stay full forever and a blocking put would leak
                # this thread and pin its staged batches.
                while not stop.is_set():
                    try:
                        q.put(None, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    break
                yield item if stage_in_producer else put_on_device(*item)
        finally:
            stop.set()
            # drain so the producer can exit
            while not q.empty():
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            # advance the shuffle epoch even when the consumer stops early,
            # so a max-steps loop never replays the same permutation
            self._epoch += 1
