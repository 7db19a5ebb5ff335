"""Zero-stall input: sharded streaming loader with checkpointable state.

The in-memory loaders (data/loader.py, data/text.py) assume the whole
dataset fits in host RAM (or HBM) — the reference's own locality design
("full dataset on every node", reference README.md:24), and exactly the
scaling wall ROADMAP item 2 names. This module removes it:

- **Record format** (`.pdsr` shards): a length-prefixed record file —
  ``b"PDSR" | u32 version | u64 record_count`` header, then
  ``u32 length | payload`` per record. Payloads are dataset-kind specific
  (image: little-endian u32 label + raw uint8 NHWC pixels; tokens: raw
  little-endian int32 token ids, variable length). A ``dataset.json``
  manifest at the shard-dir root describes the kind, per-shard record
  counts and the decode parameters (shape/mean/std, vocab/branching).
  ``cli data export`` converts the existing in-memory datasets.
- **Per-host sharding**: each process reads shard files
  ``shards[host_index::host_count]`` — no host ever touches the full
  corpus, so the dataset can exceed RAM.
- **Streaming pipeline**: a reader thread walks shards in a per-epoch
  seeded order, decode/augment/mask runs on a worker pool, and a bounded
  ``prefetch`` queue of ready (optionally ``device_put``) batches feeds
  the trainer — step time is gated by the device program, never by input
  I/O. ``prefetch=0`` is the fully synchronous ("cold") path.
- **Checkpointable iterator state**: the batch sequence is a pure
  function of ``(seed, shard layout, consumed count)`` — identical
  across fresh runs, across ``workers`` counts, and across a
  save/restore at any mid-epoch step. ``state()`` returns a small
  JSON-able pytree (shard list + epoch + within-shard cursor +
  prefetch-consumed count + packer carry + seed); the trainer captures
  it inside every checkpoint (``model_step_<N>.data.json`` sidecar,
  training/checkpoint.py) and ``restore()`` continues the exact stream —
  the bitwise ``crash_resume`` guarantee extended to the batch sequence
  (chaos scenario ``data_resume``).

Determinism across worker counts holds because batch *composition* is
decided by the single in-order reader (which also snapshots the cursor
after each batch), while the parallel workers only apply per-batch
transforms whose RNG is derived from ``(seed, batch_index)`` — never
from worker identity or arrival order. Ready batches are consumed in
submission order, so the pool cannot reorder the stream.
"""

from __future__ import annotations

import json
import os
import queue
import struct
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, List, Optional, Tuple

import numpy as np

from pytorch_distributed_nn_tpu.observability.spans import span

MAGIC = b"PDSR"
VERSION = 1
META_NAME = "dataset.json"
META_FORMAT = "pdtn-stream-v1"
STATE_FORMAT = "pdtn-stream-state-v1"
_HEADER = struct.Struct("<4sIQ")  # magic, version, record_count
_LEN = struct.Struct("<I")

Batch = Tuple[np.ndarray, np.ndarray]


# ---------------------------------------------------------------------------
# Record format: write / read
# ---------------------------------------------------------------------------


class ShardWriter:
    """Write one ``.pdsr`` shard atomically (tmp + rename on close)."""

    def __init__(self, path: str):
        self.path = path
        self._tmp = path + ".tmp"
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._f = open(self._tmp, "wb")
        self._f.write(_HEADER.pack(MAGIC, VERSION, 0))
        self.count = 0

    def write(self, payload: bytes) -> None:
        self._f.write(_LEN.pack(len(payload)))
        self._f.write(payload)
        self.count += 1

    def close(self) -> None:
        if self._f is None:
            return
        self._f.seek(0)
        self._f.write(_HEADER.pack(MAGIC, VERSION, self.count))
        self._f.flush()
        self._f.close()
        self._f = None
        os.replace(self._tmp, self.path)


class ShardReader:
    """Sequential record reader over one shard, seekable by record index.

    ``seek(n)`` skips to record ``n`` by walking the length prefixes —
    O(n) metadata reads, paid only on open/restore, never per batch.
    """

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "rb")
        magic, version, count = _HEADER.unpack(self._f.read(_HEADER.size))
        if magic != MAGIC:
            raise ValueError(f"{path}: not a PDSR shard (bad magic)")
        if version != VERSION:
            raise ValueError(f"{path}: unsupported shard version {version}")
        self.count = count
        self.pos = 0  # next record index

    def seek(self, record: int) -> None:
        if record < self.pos:
            self._f.seek(_HEADER.size)
            self.pos = 0
        while self.pos < record:
            (length,) = _LEN.unpack(self._f.read(_LEN.size))
            self._f.seek(length, os.SEEK_CUR)
            self.pos += 1

    def read(self) -> Optional[bytes]:
        """Next record's payload, or None at end of shard."""
        if self.pos >= self.count:
            return None
        (length,) = _LEN.unpack(self._f.read(_LEN.size))
        payload = self._f.read(length)
        if len(payload) != length:
            raise ValueError(
                f"{self.path}: torn record {self.pos} "
                f"({len(payload)} of {length} bytes)"
            )
        self.pos += 1
        return payload

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def iter_records(path: str) -> Iterator[bytes]:
    r = ShardReader(path)
    try:
        while True:
            payload = r.read()
            if payload is None:
                return
            yield payload
    finally:
        r.close()


def load_meta(path: str) -> dict:
    """Read and validate a shard directory's ``dataset.json`` manifest."""
    meta_file = os.path.join(path, META_NAME)
    if not os.path.isfile(meta_file):
        raise FileNotFoundError(
            f"{path}: no {META_NAME} — not a streaming shard directory "
            "(create one with `cli data export`)"
        )
    with open(meta_file) as f:
        meta = json.load(f)
    if meta.get("format") != META_FORMAT:
        raise ValueError(
            f"{path}: unknown shard-dir format {meta.get('format')!r}"
        )
    return meta


def _write_meta(out_dir: str, meta: dict) -> None:
    tmp = os.path.join(out_dir, META_NAME + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f, sort_keys=True, indent=1)
    os.replace(tmp, os.path.join(out_dir, META_NAME))


# ---------------------------------------------------------------------------
# Export: in-memory datasets -> shard directories
# ---------------------------------------------------------------------------


def export_image_dataset(dataset, out_dir: str, shards: int = 8) -> dict:
    """Convert an in-memory image ``Dataset`` (data/datasets.py) into a
    shard directory. Records keep the canonical uint8 storage (4x smaller
    than f32); normalization/augmentation happen at load time, exactly as
    in the in-memory loaders. Returns the written manifest."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    n = len(dataset)
    if n < shards:
        shards = max(1, n)
    os.makedirs(out_dir, exist_ok=True)
    raw = dataset.raw_images
    labels = np.asarray(dataset.labels, np.int64)
    bounds = [(i * n) // shards for i in range(shards + 1)]
    entries = []
    for s in range(shards):
        fname = f"shard-{s:05d}.pdsr"
        w = ShardWriter(os.path.join(out_dir, fname))
        for i in range(bounds[s], bounds[s + 1]):
            w.write(_LEN.pack(int(labels[i])) + raw[i].tobytes())
        w.close()
        entries.append({"file": fname, "records": w.count})
    meta = {
        "format": META_FORMAT,
        "kind": "image",
        "name": dataset.name,
        "shape": list(raw.shape[1:]),
        "num_classes": int(dataset.num_classes),
        "mean": list(dataset.mean),
        "std": list(dataset.std),
        "augment": bool(dataset.augment),
        "num_records": int(n),
        "shards": entries,
    }
    _write_meta(out_dir, meta)
    return meta


def export_text_corpus(
    out_dir: str,
    shards: int = 4,
    sequences: int = 4096,
    vocab_size: int = 1024,
    branching: int = 8,
    min_len: int = 16,
    max_len: int = 128,
    seed: int = 0,
    corpus_seed: Optional[int] = None,
) -> dict:
    """Draw ``sequences`` variable-length token sequences from the
    synthetic bigram corpus (data/text.BigramCorpus — the repo's stand-in
    for a real tokenized corpus on this zero-egress host) and write them
    as token shards. Variable lengths are the point: they exercise the
    loader's fixed-(B, L) packing. Returns the written manifest."""
    from pytorch_distributed_nn_tpu.data.text import BigramCorpus

    if not 2 <= min_len <= max_len:
        raise ValueError(f"bad length range [{min_len}, {max_len}]")
    if corpus_seed is None:
        corpus_seed = seed
    corpus = BigramCorpus(vocab_size, branching=branching, seed=corpus_seed)
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.RandomState(
        np.random.MT19937(np.random.SeedSequence((seed, 0xD47A)))
    )
    lengths = rng.randint(min_len, max_len + 1, size=sequences)
    entries = []
    total_tokens = 0
    bounds = [(i * sequences) // shards for i in range(shards + 1)]
    for s in range(shards):
        fname = f"shard-{s:05d}.pdsr"
        w = ShardWriter(os.path.join(out_dir, fname))
        tokens_here = 0
        for i in range(bounds[s], bounds[s + 1]):
            toks = corpus.sample_tokens(rng, 1, int(lengths[i]))[0]
            w.write(toks.astype("<i4").tobytes())
            tokens_here += int(lengths[i])
        w.close()
        entries.append(
            {"file": fname, "records": w.count, "tokens": tokens_here}
        )
        total_tokens += tokens_here
    meta = {
        "format": META_FORMAT,
        "kind": "tokens",
        "vocab_size": int(vocab_size),
        "branching": int(branching),
        "corpus_seed": int(corpus_seed),
        "num_records": int(sequences),
        "num_tokens": int(total_tokens),
        "min_len": int(min_len),
        "max_len": int(max_len),
        "shards": entries,
    }
    _write_meta(out_dir, meta)
    return meta


# ---------------------------------------------------------------------------
# The streaming loader
# ---------------------------------------------------------------------------


class _Cursor:
    """The reader's position — everything needed to reproduce the stream.

    ``carry`` is the token packer's leftover buffer (tokens read from a
    record but not yet emitted in a block); images never carry."""

    __slots__ = ("epoch", "shard_pos", "record_pos", "consumed", "carry")

    def __init__(self, epoch=0, shard_pos=0, record_pos=0, consumed=0,
                 carry=None):
        self.epoch = epoch
        self.shard_pos = shard_pos
        self.record_pos = record_pos
        self.consumed = consumed
        self.carry = np.zeros((0,), np.int32) if carry is None else carry


class StreamingLoader:
    """Sharded streaming batch source with checkpointable iterator state.

    Presents the in-memory loaders' surface (``steps_per_epoch`` /
    ``next_batch`` / ``close`` / ``skip``) plus the iterator-state
    contract (``state()`` / ``restore()``) the resume path consumes.

    - kind ``"image"``: batches of ``batch_size`` records, normalized
      (and augmented, when the manifest says so) exactly like
      ``DataLoader``; the epoch's *shard order* is reshuffled per epoch
      (records stay sequential within a shard — the streaming analogue
      of shard-level shuffling), and the epoch's partial tail batch is
      dropped (``drop_last`` semantics).
    - kind ``"tokens"``: variable-length sequences are packed into fixed
      ``(batch_size, seq_len)`` blocks by stream concatenation (leftover
      tokens carry into the next block) and BERT-masked per batch
      (data/text.mask_tokens); the corpus is treated as an infinite
      stream — epochs only mark shard-order reshuffles.

    ``prefetch=0`` runs everything synchronously on the caller's thread
    (the "cold" configuration); ``prefetch>0`` starts the
    reader/worker/output pipeline and keeps up to ``prefetch`` ready
    (device-put) batches ahead of the trainer.
    """

    def __init__(
        self,
        path: str,
        batch_size: int,
        *,
        seq_len: Optional[int] = None,
        mask_prob: float = 0.15,
        vocab_size: Optional[int] = None,
        seed: int = 0,
        sharding=None,
        prefetch: int = 2,
        workers: int = 0,
        host_index: Optional[int] = None,
        host_count: Optional[int] = None,
    ):
        self.path = path
        self.meta = load_meta(path)
        self.kind = self.meta["kind"]
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.sharding = sharding
        self.prefetch = max(0, int(prefetch))
        self.workers = max(0, int(workers))
        self.mask_prob = float(mask_prob)
        self.last_wait_ms = 0.0
        if host_index is None or host_count is None:
            host_index, host_count = _default_host()
        if not 0 <= host_index < host_count:
            raise ValueError(
                f"host_index {host_index} out of range for "
                f"{host_count} hosts"
            )
        # per-host shard assignment: strided, so adding a shard never
        # reshuffles every host's set
        self.shards = self.meta["shards"][host_index::host_count]
        if not self.shards:
            raise ValueError(
                f"{path}: {len(self.meta['shards'])} shard(s) leave none "
                f"for host {host_index} of {host_count} — export with at "
                "least one shard per host"
            )
        if self.kind == "image":
            self._shape = tuple(self.meta["shape"])
            self._mean = tuple(self.meta["mean"])
            self._std = tuple(self.meta["std"])
            self._augment = bool(self.meta.get("augment"))
            self._rec_per_epoch = sum(s["records"] for s in self.shards)
            if self.batch_size > self._rec_per_epoch:
                raise ValueError(
                    f"batch_size {batch_size} exceeds this host's "
                    f"{self._rec_per_epoch} records"
                )
        elif self.kind == "tokens":
            if seq_len is None:
                raise ValueError("kind 'tokens' requires seq_len")
            self.seq_len = int(seq_len)
            self.vocab_size = int(
                vocab_size if vocab_size is not None
                else self.meta["vocab_size"]
            )
            self._tok_per_epoch = sum(
                int(s.get("tokens", 0)) for s in self.shards
            )
        else:
            raise ValueError(f"{path}: unknown dataset kind {self.kind!r}")
        self._cursor = _Cursor()
        self._last_state = self._snapshot(self._cursor)
        self._reader: Optional[ShardReader] = None
        self._reader_key: Optional[tuple] = None
        # pipeline plumbing (prefetch > 0)
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._pool: Optional[ThreadPoolExecutor] = None
        self._fqueue: Optional[queue.Queue] = None
        self._ready: Optional[queue.Queue] = None

    # -- ordering / schedule ----------------------------------------------

    def _shard_order(self, epoch: int) -> np.ndarray:
        """This epoch's shard visiting order — a pure function of
        (seed, epoch), so any restart recomputes it identically."""
        rng = np.random.RandomState(
            np.random.MT19937(np.random.SeedSequence((self.seed + 23, epoch)))
        )
        order = np.arange(len(self.shards))
        rng.shuffle(order)
        return order

    def _batch_rng(self, index: int) -> np.random.RandomState:
        """Per-batch transform RNG: a pure function of (seed, index) —
        the same counter-based stream contract as data/text.MLMBatches,
        independent of worker identity or arrival order."""
        return np.random.RandomState(
            np.random.MT19937(np.random.SeedSequence((self.seed + 1, index)))
        )

    @property
    def steps_per_epoch(self) -> int:
        if self.kind == "image":
            return max(1, self._rec_per_epoch // self.batch_size)
        block = self.batch_size * self.seq_len
        return max(1, self._tok_per_epoch // block) if self._tok_per_epoch \
            else 100

    # -- the in-order reader (single thread / sync caller) -----------------

    def _ensure_reader(self, cur: _Cursor) -> ShardReader:
        order = self._shard_order(cur.epoch)
        shard = self.shards[int(order[cur.shard_pos])]
        key = (cur.epoch, cur.shard_pos)
        if self._reader is None or self._reader_key != key:
            if self._reader is not None:
                self._reader.close()
            self._reader = ShardReader(os.path.join(self.path, shard["file"]))
            self._reader_key = key
        self._reader.seek(cur.record_pos)
        return self._reader

    def _advance_shard(self, cur: _Cursor) -> bool:
        """Move to the next shard; returns True when an epoch ended."""
        cur.shard_pos += 1
        cur.record_pos = 0
        if cur.shard_pos >= len(self.shards):
            cur.epoch += 1
            cur.shard_pos = 0
            return True
        return False

    def _next_raw(self):
        """Produce the next raw batch IN ORDER, mutating the cursor.

        Returns ``(index, raw, state_after)`` where ``state_after`` is
        the serializable snapshot a consumer stores once this batch has
        been *consumed* — restoring it reproduces every later batch.
        """
        cur = self._cursor
        if self.kind == "image":
            raw = self._next_raw_image(cur)
        else:
            raw = self._next_raw_tokens(cur)
        index = cur.consumed
        cur.consumed += 1
        return index, raw, self._snapshot(cur)

    def _next_raw_image(self, cur: _Cursor):
        imgs, labels = [], []
        while len(imgs) < self.batch_size:
            payload = self._ensure_reader(cur).read()
            if payload is None:
                epoch_end = self._advance_shard(cur)
                if epoch_end and imgs:
                    imgs, labels = [], []  # drop_last: epoch tail dropped
                continue
            (label,) = _LEN.unpack(payload[: _LEN.size])
            imgs.append(
                np.frombuffer(payload, np.uint8, offset=_LEN.size)
                .reshape(self._shape)
            )
            labels.append(label)
            cur.record_pos += 1
        return np.stack(imgs), np.asarray(labels, np.int32)

    def _next_raw_tokens(self, cur: _Cursor):
        need = self.batch_size * self.seq_len
        parts = [cur.carry]
        have = len(cur.carry)
        while have < need:
            payload = self._ensure_reader(cur).read()
            if payload is None:
                self._advance_shard(cur)  # infinite stream: wrap epochs
                continue
            toks = np.frombuffer(payload, "<i4").astype(np.int32)
            parts.append(toks)
            have += len(toks)
            cur.record_pos += 1
        flat = np.concatenate(parts)
        cur.carry = flat[need:].copy()
        return flat[:need].reshape(self.batch_size, self.seq_len)

    # -- per-batch transform (worker pool) ---------------------------------

    def _transform(self, raw, index: int) -> Batch:
        rng = self._batch_rng(index)
        if self.kind == "image":
            from pytorch_distributed_nn_tpu.data.datasets import (
                _normalize,
                augment_batch,
            )

            imgs, labels = raw
            x = _normalize(imgs, self._mean, self._std)
            if self._augment:
                x = augment_batch(x, rng)
            return x, labels
        from pytorch_distributed_nn_tpu.data.text import mask_tokens

        return mask_tokens(raw, rng, self.vocab_size, self.mask_prob)

    def _to_device(self, batch: Batch) -> Batch:
        if self.sharding is None:
            return batch
        import jax

        x, y = batch
        return jax.device_put(x, self.sharding), jax.device_put(
            y, self.sharding
        )

    # -- pipeline (prefetch > 0) -------------------------------------------

    def _ensure_pipeline(self) -> None:
        if self._threads:
            return
        self._stop.clear()
        depth = max(1, self.prefetch)
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, self.workers),
            thread_name_prefix="pdtn-stream-worker",
        )
        self._fqueue = queue.Queue(maxsize=depth)
        self._ready = queue.Queue(maxsize=depth)
        reader = threading.Thread(
            target=self._reader_loop, name="pdtn-stream-reader", daemon=True
        )
        output = threading.Thread(
            target=self._output_loop, name="pdtn-stream-output", daemon=True
        )
        self._threads = [reader, output]
        reader.start()
        output.start()

    def _put_until_stop(self, q: queue.Queue, item) -> bool:
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _reader_loop(self) -> None:
        try:
            while not self._stop.is_set():
                index, raw, state = self._next_raw()
                fut = self._pool.submit(self._transform, raw, index)
                if not self._put_until_stop(self._fqueue, (fut, state)):
                    return
        except Exception as e:  # surfaced to the consumer via the queue
            self._put_until_stop(self._fqueue, (e, None))

    def _output_loop(self) -> None:
        while not self._stop.is_set():
            try:
                item = self._fqueue.get(timeout=0.2)
            except queue.Empty:
                continue
            fut, state = item
            try:
                if isinstance(fut, Exception):
                    raise fut
                batch = self._to_device(fut.result())
            except Exception as e:
                self._put_until_stop(self._ready, (e, None))
                return
            if not self._put_until_stop(self._ready, (batch, state)):
                return

    def _stop_pipeline(self) -> None:
        if not self._threads:
            return
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        self._fqueue = None
        self._ready = None
        # the reader thread ran ahead of the consumer: rewind the cursor
        # to the last CONSUMED batch so a restart reproduces the stream
        self._set_cursor(self._last_state)

    # -- public surface ----------------------------------------------------

    def next_batch(self) -> Batch:
        # last_wait_ms is the input/produce span, as in data/loader.py:
        # the read, the transform or the wait on the pipeline — not the
        # dispatch to the device (the pipeline's thread has done it)
        if self.prefetch == 0:
            with span("input/produce") as produce:
                index, raw, state = self._next_raw()
                batch = self._transform(raw, index)
            with span("input/put"):
                batch = self._to_device(batch)
        else:
            self._ensure_pipeline()
            with span("input/produce") as produce:
                batch, state = self._ready.get()
            if isinstance(batch, Exception):
                raise RuntimeError(
                    f"streaming pipeline failed: {batch!r}"
                ) from batch
        self._last_state = state
        self.last_wait_ms = produce.seconds * 1000
        return batch

    def epoch_batches(self) -> Iterator[Batch]:
        """One nominal epoch, synchronously (eval/debug consumers)."""
        for _ in range(self.steps_per_epoch):
            index, raw, _ = self._next_raw()
            yield self._to_device(self._transform(raw, index))

    def skip(self, n: int) -> None:
        """Fast-forward ``n`` batches without decoding/transforming them —
        the sidecar-less resume fallback (O(n) metadata reads)."""
        if self._threads:
            raise RuntimeError("skip() requires a stopped pipeline")
        for _ in range(int(n)):
            *_, state = self._next_raw()
            self._last_state = state

    def state(self) -> dict:
        """Serializable iterator state of the last CONSUMED batch — with
        prefetch in flight, produced-but-unconsumed batches are excluded
        by construction (the snapshot rides with each batch)."""
        return json.loads(json.dumps(self._last_state))

    def restore(self, state: dict) -> None:
        """Resume the exact stream a saved ``state()`` describes."""
        if state.get("format") != STATE_FORMAT:
            raise ValueError(
                f"unknown iterator-state format {state.get('format')!r}"
            )
        if state.get("kind") != self.kind:
            raise ValueError(
                f"iterator state is kind {state.get('kind')!r}, this "
                f"loader is {self.kind!r}"
            )
        if list(state.get("shards") or []) != [s["file"] for s in self.shards]:
            raise ValueError(
                "iterator state was saved against a different shard "
                "layout; resume needs the same data_path and host count"
            )
        self._stop_pipeline()
        self._set_cursor(state)
        self._last_state = self._snapshot(self._cursor)

    def restore_repartitioned(self, state: dict) -> dict:
        """Elastic-resume restore: accept iterator state saved under a
        DIFFERENT per-host shard assignment and re-partition the stream.

        When the host count changes across a resume, this host's
        ``shards[k::n]`` slice changes too, so the saved cursor cannot be
        applied verbatim — but global progress CAN be preserved: the
        batch sequence is a pure function of ``(seed, layout, consumed)``,
        so the position after ``state["consumed"]`` batches under the NEW
        layout is fully determined. Matching layouts take the exact
        ``restore`` path (bitwise stream continuation); mismatched
        layouts re-derive the cursor:

        - ``image``: pure arithmetic over the manifest's per-shard record
          counts (no record reads at all) — epoch, shard position and
          record offset fall out of ``consumed`` and the seeded per-epoch
          shard order;
        - ``tokens``: the packer's carry buffer holds real leftover
          tokens, so the stream is replayed via :meth:`skip` (record
          reads, but no decode/transform work).

        Returns an info dict (``repartitioned``, ``consumed``,
        ``saved_shards``, ``shards``) the trainer folds into its
        ``data_refastforward`` telemetry event. Raises on a state from a
        different dataset kind or seed — progress under one seed says
        nothing about the stream of another.
        """
        if state.get("format") != STATE_FORMAT:
            raise ValueError(
                f"unknown iterator-state format {state.get('format')!r}"
            )
        if state.get("kind") != self.kind:
            raise ValueError(
                f"iterator state is kind {state.get('kind')!r}, this "
                f"loader is {self.kind!r}"
            )
        saved_shards = list(state.get("shards") or [])
        consumed = int(state.get("consumed", 0))
        if saved_shards == [s["file"] for s in self.shards]:
            self.restore(state)
            return {
                "repartitioned": False, "consumed": consumed,
                "saved_shards": len(saved_shards),
                "shards": len(self.shards),
            }
        if int(state.get("seed", self.seed)) != self.seed:
            raise ValueError(
                f"iterator state was saved with seed {state.get('seed')} "
                f"but this loader uses seed {self.seed}; the re-derived "
                "stream position would be meaningless"
            )
        self._stop_pipeline()
        if self._reader is not None:
            self._reader.close()
            self._reader = None
            self._reader_key = None
        self._cursor = _Cursor()
        if self.kind == "image":
            self._cursor = self._image_cursor_at(consumed)
        else:
            self._last_state = self._snapshot(self._cursor)
            self.skip(consumed)
        self._cursor.consumed = consumed
        self._last_state = self._snapshot(self._cursor)
        return {
            "repartitioned": True, "consumed": consumed,
            "saved_shards": len(saved_shards), "shards": len(self.shards),
        }

    def _image_cursor_at(self, consumed: int) -> _Cursor:
        """The cursor after ``consumed`` image batches of THIS layout —
        pure arithmetic over the manifest's per-shard record counts (the
        image stream reads whole records in shard order with drop_last
        epoch tails, so no payload ever needs to be touched)."""
        per_epoch = self.steps_per_epoch
        epoch = consumed // per_epoch
        records = (consumed % per_epoch) * self.batch_size
        cur = _Cursor(epoch=epoch, consumed=consumed)
        order = self._shard_order(epoch)
        for pos in range(len(self.shards)):
            count = int(self.shards[int(order[pos])]["records"])
            if records <= count:
                cur.shard_pos = pos
                cur.record_pos = records
                break
            records -= count
        return cur

    def close(self) -> None:
        self._stop_pipeline()
        if self._reader is not None:
            self._reader.close()
            self._reader = None
            self._reader_key = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- state plumbing ----------------------------------------------------

    def _snapshot(self, cur: _Cursor) -> dict:
        state = {
            "format": STATE_FORMAT,
            "kind": self.kind,
            "seed": self.seed,
            "shards": [s["file"] for s in self.shards],
            "epoch": int(cur.epoch),
            "shard_pos": int(cur.shard_pos),
            "record_pos": int(cur.record_pos),
            "consumed": int(cur.consumed),
        }
        if self.kind == "tokens":
            state["carry"] = [int(t) for t in cur.carry]
        return state

    def _set_cursor(self, state: dict) -> None:
        self._cursor = _Cursor(
            epoch=int(state["epoch"]),
            shard_pos=int(state["shard_pos"]),
            record_pos=int(state["record_pos"]),
            consumed=int(state["consumed"]),
            carry=np.asarray(state.get("carry") or [], np.int32),
        )
        self._reader_key = None  # force a re-open + seek


def _default_host() -> Tuple[int, int]:
    """(host_index, host_count) from jax when a backend is already up;
    (0, 1) otherwise — the loader itself never initializes jax."""
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index(), jax.process_count()
        except Exception:
            pass
    return 0, 1
