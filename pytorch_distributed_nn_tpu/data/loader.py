"""Host-side batch loader with background prefetch.

Capability parity with the reference's vendored DataLoader (reference:
src/data_loader_ops/my_data_loader.py:254-318): per-epoch shuffling, a
stateful `next_batch()` that wraps around epochs, and asynchronous
prefetching. The reference used fork-based worker processes feeding a queue
(:37-53); here the default is a daemon thread that prepares (augments +
stacks) upcoming batches into a bounded queue and optionally
`jax.device_put`s them with the target sharding so host→HBM transfer
overlaps compute — the TPU equivalent of pinned-memory prefetch (:56-75).

``workers=N`` prepares batches on N worker threads, the mechanism the
streaming loader uses (data/streaming.py): each batch is gathered from the
uint8 dataset, normalized and augmented on a thread of its own (no
full-dataset float32 materialization), ``max(prefetch, workers)`` batches
ahead, and handed to the caller in submission order. The work runs off the
interpreter lock — large numpy operations and the C++ augment engine — so
threads spread it over the host's cores. This is the path for datasets too
large for the HBM-resident `DeviceDataLoader` (trainer.py's ~2 GB budget):
device upload still happens once per batch. Default stays workers=0.
"""

from __future__ import annotations

import queue
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Tuple

import numpy as np

from pytorch_distributed_nn_tpu.data.datasets import (
    Dataset,
    _normalize,
    augment_batch,
)
from pytorch_distributed_nn_tpu.observability.spans import span

Batch = Tuple[np.ndarray, np.ndarray]


class _IndexedLoader:
    """Shared ordering/epoch machinery for the host and device loaders:
    per-epoch (optionally shuffled) index permutations, drop-last
    semantics, and a stateful wrap-around cursor."""

    def __init__(
        self,
        dataset: Dataset,
        batch_size: int,
        shuffle: bool,
        seed: int,
        drop_last: bool,
    ):
        if batch_size > len(dataset):
            raise ValueError(
                f"batch_size {batch_size} exceeds dataset size {len(dataset)}"
            )
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self._rng = np.random.RandomState(seed)
        self._epoch = 0
        self._order: Optional[np.ndarray] = None
        self._pos = 0

    @property
    def steps_per_epoch(self) -> int:
        n = len(self.dataset)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _epoch_order(self) -> np.ndarray:
        idx = np.arange(len(self.dataset))
        if self.shuffle:
            self._rng.shuffle(idx)
        return idx

    def _epoch_index_slices(self, order: np.ndarray) -> Iterator[np.ndarray]:
        for start in range(0, len(order), self.batch_size):
            idx = order[start : start + self.batch_size]
            if len(idx) < self.batch_size and self.drop_last:
                break
            yield idx

    def _next_idx(self) -> np.ndarray:
        """Stateful cursor: full batches, plus the short tail batch when
        drop_last is False, wrapping (and reshuffling) across epochs."""
        exhausted = self._order is None or (
            self._pos >= len(self._order)
            or (self.drop_last
                and self._pos + self.batch_size > len(self._order))
        )
        if exhausted:
            if self._order is not None:
                self._epoch += 1
            self._order = self._epoch_order()
            self._pos = 0
        idx = self._order[self._pos : self._pos + self.batch_size]
        self._pos += self.batch_size
        return idx


class DataLoader(_IndexedLoader):
    """Shuffling, augmenting, prefetching batch source over a Dataset."""

    def __init__(
        self,
        dataset: Dataset,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
        prefetch: int = 2,
        sharding=None,
        workers: int = 0,
    ):
        super().__init__(dataset, batch_size, shuffle, seed, drop_last)
        self.prefetch = max(0, prefetch)
        self.sharding = sharding
        self.workers = max(0, workers)
        self._seed = seed
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending: deque = deque()
        self._aug_counter = 0
        # input-wait accounting (docs/observability.md): how long the
        # LAST next_batch() blocked the caller on host work — its
        # input/produce span. Near zero when the prefetch thread or the
        # workers kept up, the full fetch when they didn't; the dispatch
        # to the device (input/put) is not in it: that blocks when the
        # runtime's launch queue is full, which is the device being busy,
        # not the loader being slow.
        self.last_wait_ms = 0.0

    def _to_device(self, x: np.ndarray, y: np.ndarray) -> Batch:
        if self.sharding is not None:
            import jax

            x = jax.device_put(x, self.sharding)
            y = jax.device_put(y, self.sharding)
        return x, y

    def _host_batch(self, idx: np.ndarray) -> Batch:
        x = self.dataset.images[idx]
        y = self.dataset.labels[idx]
        if self.dataset.augment:
            x = augment_batch(x, self._rng)
        return x, y

    def _make_batch(self, idx: np.ndarray) -> Batch:
        return self._to_device(*self._host_batch(idx))

    def _produce(self):
        while not self._stop.is_set():
            for idx in self._epoch_index_slices(self._epoch_order()):
                batch = self._make_batch(idx)
                while not self._stop.is_set():
                    try:
                        self._queue.put(batch, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            self._epoch += 1

    def _ensure_thread(self):
        if self._thread is None:
            self._queue = queue.Queue(maxsize=self.prefetch)
            self._thread = threading.Thread(target=self._produce, daemon=True)
            self._thread.start()

    # --- worker-thread path (workers > 0) ------------------------------

    def _worker_batch(self, idx: np.ndarray, counter: int) -> Batch:
        """One batch on a worker thread: uint8 gather -> normalize ->
        augment. Seeded per batch by (loader seed, batch counter): workers
        cannot share the thread path's sequential rng stream, and a
        different --seed still draws different augmentations."""
        ds = self.dataset
        x = _normalize(ds.raw_images[idx], ds.mean, ds.std)
        if ds.augment:
            x = augment_batch(x, np.random.RandomState([self._seed, counter]))
        return x, ds.labels[idx]

    def _pool_next(self) -> Batch:
        """The next host batch from the worker threads, in submission
        order; a worker's exception is raised here."""
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                self.workers, thread_name_prefix="pdtn-loader-worker"
            )
        depth = max(self.prefetch, self.workers)
        while len(self._pending) < depth:
            self._aug_counter += 1
            self._pending.append(self._pool.submit(
                self._worker_batch, self._next_idx(), self._aug_counter
            ))
        return self._pending.popleft().result()

    def next_batch(self) -> Batch:
        """Stateful batch fetch, wrapping across epochs.

        (parity: `DataLoader.next_batch`, my_data_loader.py:318)
        """
        if self.workers == 0 and self.prefetch > 0:
            # the prefetch thread has put the batch on the device already
            self._ensure_thread()
            with span("input/produce") as produce:
                batch = self._queue.get()
            self.last_wait_ms = produce.seconds * 1000
            return batch
        with span("input/produce") as produce:
            if self.workers > 0:
                x, y = self._pool_next()
            else:  # the synchronous path (prefetch=0)
                x, y = self._host_batch(self._next_idx())
        self.last_wait_ms = produce.seconds * 1000
        with span("input/put"):
            return self._to_device(x, y)

    def epoch_batches(self) -> Iterator[Batch]:
        """One full epoch, in order (used by the evaluator / eval loops)."""
        for idx in self._epoch_index_slices(self._epoch_order()):
            yield self._make_batch(idx)

    def close(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if self._pool is not None:
            # nothing is killed, so nothing dies holding a lock: queued
            # batches are cancelled, the ones running finish
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None
            self._pending.clear()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


_PAD = 4  # reflect-pad 4 -> random crop -> random flip, as augment_batch


def crop_flip_draws(key, batch: int):
    """Per-image crop origins in the reflect-padded image (``dy``, ``dx``
    in [0, 2 * pad]) and flip flags for one batch: the device loader's half
    of the PRNG contract (``DeviceDataLoader._idx_key`` makes ``key``)."""
    import jax

    kc1, kc2, kf = jax.random.split(key, 3)
    dy = jax.random.randint(kc1, (batch,), 0, 2 * _PAD + 1)
    dx = jax.random.randint(kc2, (batch,), 0, 2 * _PAD + 1)
    flip = jax.random.bernoulli(kf, 0.5, (batch,))
    return dy, dx, flip


def _reflect(k, n: int):
    """Index into an axis of length ``n`` for position ``k`` of its
    reflect-padded self (pad < n: one mirror at 0, one at n - 1)."""
    import jax.numpy as jnp

    k = jnp.abs(k)
    return jnp.where(k > n - 1, 2 * (n - 1) - k, k)


class DeviceDataLoader(_IndexedLoader):
    """Device-resident batch source: the whole dataset lives in HBM.

    The host loader ships ~13 MB of f32 pixels per b1024 CIFAR step, and
    a host-bound input pipeline puts that transfer on the step's critical
    path. The reference's own
    design keeps the full dataset on every node ("we don't pass data among
    nodes to maintain data locality", reference README.md:24) — the
    TPU-native version of that is the dataset resident in HBM: uint8
    pixels uploaded ONCE (CIFAR-10 train = 157 MB, SVHN = 225 MB, MNIST =
    47 MB — all comfortably within a v5e's 16 GB), and each step ships a
    4 KB index array; gather + reflect-pad-crop-flip augmentation +
    normalization run on-device in one jitted prep program whose output is
    already sharded over the mesh's data axis.

    ``.images`` holds one flat row of ``H*W*C`` bytes per image: the chip
    tiles a (N, H, W, C) uint8 array with N minor-most, and gathering a
    batch's rows out of that costs a relayout of the whole resident set on
    every step (4.6 ms of the 140.74 ms ResNet-18 b4096 step; ledger, PR
    25, ``resnet18_b4096``, ``breakdown.device_ops`` ``copy``). Crop and
    flip only move pixels, so they are folded into per-image source row and
    column indices (reflect pad and flip included: no padded copy, no
    ``rev``) and applied to the uint8 batch as two one-hot selections —
    batched bfloat16 matmuls with float32 accumulation, exact because a
    pixel value 0..255 is exact in bfloat16 and each output is one
    product — then cast and normalised once. At B 4096 over 50,000 images
    on a v5e that is 1.26 ms a batch against 23.80 for the padded float32
    batch and its two ``take_along_axis`` gathers, and 120.85 against 140.86
    ms for the ResNet-18 step it is fused into; the same two gathers on
    uint8 read 3.45 and 122.89 (my chip call 1, PR 29).

    Augmentation draws from the JAX PRNG (seeded per loader), so crop/flip
    draws differ from the host loader's numpy stream; the transform is
    the same pixel for pixel (``crop_flip_draws`` fed to
    ``datasets._augment_numpy`` gives this loader's batch).

    Same surface as DataLoader: steps_per_epoch / next_batch /
    epoch_batches / close.
    """

    def __init__(
        self,
        dataset: Dataset,
        batch_size: int,
        mesh,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
    ):
        super().__init__(dataset, batch_size, shuffle, seed, drop_last)
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS

        self._counter = 0
        self._key = jax.random.PRNGKey(seed)
        self.last_wait_ms = 0.0  # see DataLoader.last_wait_ms

        replicated = NamedSharding(mesh, P())
        bsharding = NamedSharding(mesh, P(DATA_AXIS))
        raw = dataset.raw_images
        self.images = jax.device_put(raw.reshape(len(raw), -1), replicated)
        self.labels = jax.device_put(
            dataset.labels.astype(np.int32), replicated
        )
        self._idx_sharding = bsharding
        mean = jnp.asarray(dataset.mean, jnp.float32) * 255.0
        std = jnp.asarray(dataset.std, jnp.float32) * 255.0
        augment = dataset.augment
        H, W, C = raw.shape[1:]

        def prep(images, labels, idx, key):
            # flat rows, as .images holds them; an (N, H, W, C) array is
            # taken too, at the price of relaying it out
            B = idx.shape[0]
            x = images.reshape(images.shape[0], -1)[idx].reshape(B, H, W, C)
            y = labels[idx]
            if augment:
                dy, dx, flip = crop_flip_draws(key, B)
                rows = _reflect(dy[:, None] + jnp.arange(H) - _PAD, H)
                j = jnp.arange(W)
                j = jnp.where(flip[:, None], W - 1 - j, j)
                cols = _reflect(j + dx[:, None] - _PAD, W)
                # out[b,i,j] = x[b, rows[b,i], cols[b,j]] as two one-hot
                # matmuls: per-image gathers over the 3-wide channel axis
                # (take_along_axis) take 2.7x as long alone on the chip
                # (class docstring), and a vmap'd lax.dynamic_slice lowers
                # to a serial loop of B dynamic-update-slices there.
                x = jnp.einsum(
                    "bih,bhwc->biwc",
                    jax.nn.one_hot(rows, H, dtype=jnp.bfloat16),
                    x.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                x = jnp.einsum(
                    "bjw,biwc->bijc",
                    jax.nn.one_hot(cols, W, dtype=jnp.bfloat16),
                    x.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
            x = (x.astype(jnp.float32) - mean) / std
            return x, y

        # prep_fn is public for train-step fusion (the Trainer inlines it
        # INTO the jitted train step so each step is one dispatch):
        self.prep_fn = prep
        self._prep = jax.jit(prep, out_shardings=(bsharding, bsharding))

    def _idx_key(self, idx: np.ndarray):
        """Upload the index batch + derive the per-batch augmentation key
        — the single home of the PRNG-stream contract shared by the fused
        and unfused paths."""
        import jax

        idx_dev = jax.device_put(idx.astype(np.int32), self._idx_sharding)
        self._counter += 1
        return idx_dev, jax.random.fold_in(self._key, self._counter)

    def _produce_idx(self) -> np.ndarray:
        """The next index batch, as the ``input/produce`` span that
        ``last_wait_ms`` reports: the loader's host work."""
        with span("input/produce") as produce:
            idx = self._next_idx()
        self.last_wait_ms = produce.seconds * 1000
        return idx

    def next_indices(self):
        """(idx_device, prng_key) for one batch — the fused-step path:
        the Trainer passes these (plus .images/.labels/.prep_fn) into one
        jitted program that builds the batch AND takes the train step."""
        idx = self._produce_idx()
        with span("input/put"):
            return self._idx_key(idx)

    def indices_spec(self):
        """What ``next_indices`` returns, as abstract values: the index
        batch on the data axis and the key, uncommitted as ``fold_in``
        leaves it."""
        import jax

        return (
            jax.ShapeDtypeStruct(
                (self.batch_size,), np.int32, sharding=self._idx_sharding
            ),
            jax.ShapeDtypeStruct(self._key.shape, self._key.dtype),
        )

    def _batch_for(self, idx: np.ndarray) -> Batch:
        import jax

        idx_dev, key = self._idx_key(idx)
        batch = self._prep(self.images, self.labels, idx_dev, key)
        if jax.default_backend() == "cpu":
            # The intra-process multi-device CPU backend can deadlock its
            # collective rendezvous when two different multi-device
            # programs (prep and the train step) are in flight at once;
            # forcing prep to finish serializes them. TPU keeps the async
            # overlap.
            jax.block_until_ready(batch)
        return batch

    def next_batch(self) -> Batch:
        idx = self._produce_idx()
        with span("input/put"):
            return self._batch_for(idx)

    def epoch_batches(self) -> Iterator[Batch]:
        for idx in self._epoch_index_slices(self._epoch_order()):
            yield self._batch_for(idx)

    def close(self):
        self.images = None
        self.labels = None
