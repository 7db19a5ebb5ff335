"""Zero-stall checkpointing: overlap snapshot/serialize/write with training.

HOST I/O on the critical path is avoidable wall-clock: ``trainer.py`` used
to save checkpoints synchronously inside the step loop, so the loop stalled
for the whole device→host fetch + serialize + write of the state (~90 MB
params+momentum for ResNet-18, ~1.3 GB for a BERT-base Adam state) every
``--eval-freq`` steps. The reference got this right structurally by
putting its evaluator in a separate process off the workers' critical
path (reference README.md:22-28); this module is
the TPU-native equivalent: the whole snapshot/serialize/write pipeline
overlaps with training.

A save splits into two halves::

    save(state)                         # the TRAIN LOOP waits for this
      ├─ backpressure wait              # depth-1: at most one save in flight
      ├─ on-device clone (async dispatch, ~HBM bandwidth)
      └─ enqueue → returns              # stall_ms = everything above
    writer thread                       # beside the training steps
      ├─ device_get(clone)              # the d2h fetch
      ├─ serialize + host_codec compress
      ├─ atomic publish + CRC32 manifest + retry   (the EXISTING writers)
      └─ keep-last GC

``stall_ms`` is what the loop waits for, not all a save can cost it. The
writer is a thread of the same interpreter: whatever it does with the
interpreter lock held keeps the loop from dispatching, and a save
follows a flush, which has drained the device's queue: the device idles
until the loop gets the lock back. Through PR 30 that was ≈ 430 ms a save
against a ``stall_ms`` of 8 (msgpack's ``packb`` over the whole state, a
zero-filled ctypes buffer, four state-sized ``bytes`` copies: PERF.md,
PR 31). So the rule for everything the writer runs between the fetch and
the rename (``checkpoint.save_checkpoint``): no pass over state-sized or
leaf-sized bytes with the lock held — numpy copies, the codec's foreign
call, ``zlib.crc32`` and ``file.write`` release it; buffers are
allocated uninitialised. What is left to the lock is per-leaf
bookkeeping (≈ 25-45 ms a save on the same job since, the device's own
fetch and snapshot work included). The step records' ``dispatch_gap_ms``
shows what a save did cost the loop (``obs summary``'s I/O-stall section
sets it beside ``stall_ms``).

Contracts, in order of importance:

- **Byte identity.** The writer thread calls the same
  ``checkpoint.save_checkpoint`` / sharded writers the sync path calls, on
  a host snapshot that flax serializes to the same msgpack bytes — so an
  async checkpoint is indistinguishable from a sync one:
  ``verify_checkpoint`` / ``quarantine_checkpoint`` /
  ``resume_latest_valid`` work unchanged, and the chaos suite asserts
  byte-for-byte equality.
- **Donation safety.** The train step donates its state buffers, so the
  snapshot must not alias them: the clone is a jitted ``jnp.copy`` per
  leaf (a guaranteed fresh buffer — jit of the *identity* may alias its
  input, which the next donated step would invalidate under the
  background ``device_get``). Cost: one transient extra copy of the state
  in device memory, freed as soon as the d2h fetch completes.
- **Bounded, never lossy.** In-flight depth is 1. A second save arriving
  while one is in flight WAITS for it (emitting a ``ckpt_backpressure``
  event with the wait), it is never silently dropped — a checkpoint the
  user asked for always lands on disk or raises.
- **Errors surface at the next wait point.** ``flaky_io`` faults are
  absorbed by the writers' retry exactly as on the sync path; a hard
  failure (retries exhausted, disk full) is stored and re-raised from the
  next ``save()`` / ``wait()`` / ``drain()`` — the same step the sync
  path would have raised from, one interval later.
- **Collective contract (GSPMD).** The per-process shard fetch and local
  npz write are collective-free and run on the writer thread; the COMMIT
  (checksum + meta.json + atomic rename by process 0) needs every
  process's file complete, so on multi-process runs it runs at the next
  main-thread wait point behind the usual barriers
  (``checkpoint.publish_sharded``). Single-process runs commit inline on
  the writer thread.
- **Preemption composes.** ``Trainer._emergency_save`` drains the
  in-flight save before writing its own synchronous checkpoint, so
  SIGTERM / ``InjectedCrash`` still produce a valid final checkpoint and
  never race the writer thread on the same ``model_step_<N>`` path.

Telemetry: ``checkpoint_write`` events gain ``queued_ms`` / ``write_ms`` /
``stall_ms`` / ``fetch_ms`` (and, from the file writer, ``serialize_ms`` /
``compress_ms`` / ``file_ms``); the registry gains the ``ckpt_queue_depth``
gauge and ``ckpt_stall_ms_total`` counter (exported via promexport like
every other metric); ``obs summary`` renders the I/O-stall section from
the events.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

from pytorch_distributed_nn_tpu.observability.core import get_telemetry
from pytorch_distributed_nn_tpu.observability.spans import span
from pytorch_distributed_nn_tpu.training import checkpoint as ckpt

logger = logging.getLogger(__name__)

_STOP = object()  # writer-thread shutdown sentinel


class SaveHandle:
    """One in-flight (or completed) save.

    ``dev_state`` is the on-device snapshot — the overlapped evaluator
    runs on it (``--overlap-eval``), which is why the writer thread only
    frees it when ``retain_device_state`` is False. ``done`` is set once
    the checkpoint is PUBLISHED (single-process) or locally written and
    awaiting commit (multi-process sharded).
    """

    def __init__(self, step: int, dev_state, fault_plan=None,
                 retain_device_state: bool = False, data_state=None):
        self.step = step
        self.dev_state = dev_state
        self.fault_plan = fault_plan
        self.retain_device_state = retain_device_state
        # input-pipeline iterator state, captured host-side at save()
        # time (it is tiny and must reflect THIS step's stream position,
        # not wherever the loader is when the writer runs)
        self.data_state = data_state
        self.stall_ms: float = 0.0
        self.enqueued_at: float = 0.0
        self.path: Optional[str] = None
        self.done = threading.Event()


class AsyncCheckpointer:
    """Depth-1 background checkpoint pipeline over the existing writers.

    One instance per run (the Trainer owns it). Thread model: ``save`` /
    ``wait`` / ``drain`` / ``close`` are called from the train-loop
    thread; one daemon writer thread does the d2h fetch + serialize +
    publish. Telemetry emission is thread-safe by construction
    (``TelemetrySink`` locks; registry is get-or-create under a lock).
    """

    def __init__(self, directory: str, *, sharded: bool = False,
                 keep_last: Optional[int] = None, write_fn=None,
                 writer_nice: int = 15, geometry: Optional[dict] = None):
        if keep_last is not None and keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        self.directory = directory
        self.sharded = sharded
        self.keep_last = keep_last
        # written-on geometry stamped into every manifest this pipeline
        # publishes (checkpoint.mesh_geometry; elastic-resume input)
        self.geometry = geometry
        # serialize/compress are CPU work: on a host whose cores are busy
        # feeding the chip (or a core-starved CI box) a full-priority
        # writer steals cycles from the step loop and the "overlap" leaks
        # back into step time. nice>0 makes the writer a strictly
        # background citizen — it only stretches the WRITE, never the
        # steps. 0 disables (best-effort: per-thread priority is a Linux
        # affordance).
        self.writer_nice = writer_nice
        # test seam: wraps/replaces checkpoint.save_checkpoint (same
        # signature) — how the backpressure tests inject a slow/failing
        # writer without monkeypatching the module under test
        self._write_fn = write_fn
        # jnp.copy per leaf, NOT jit(identity): identity may alias the
        # input buffers, which the next donated train step invalidates
        self._clone = jax.jit(
            lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
        )
        self._cv = threading.Condition()
        self._in_flight: Optional[SaveHandle] = None
        self._error: Optional[BaseException] = None
        # multi-process sharded saves: (tmp, final, step, shapes, t0)
        # awaiting the main-thread commit barrier
        self._pending_commit: Optional[tuple] = None
        self._queue: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._worker, name="pdtn-ckpt-writer", daemon=True
        )
        self._closed = False
        self._thread.start()

    # -- producer side (train-loop thread) --------------------------------

    def warmup(self, state) -> None:
        """Compile the on-device clone for ``state``'s tree ahead of the
        first save, so the first checkpoint's ``stall_ms`` doesn't carry
        a one-off ~100 ms XLA compile. Cheap (one transient state copy);
        the trainer calls this at init, off the timed path."""
        jax.block_until_ready(self._clone(state))

    def save(self, state, step: Optional[int] = None, fault_plan=None,
             retain_device_state: bool = False,
             data_state: Optional[dict] = None) -> SaveHandle:
        """Enqueue one checkpoint; returns once the background pipeline
        owns it. Blocks only for (a) a previous save still in flight
        (backpressure — emits ``ckpt_backpressure``) and (b) the on-device
        clone dispatch; the returned handle's ``stall_ms`` is exactly that
        blockage, which the ``checkpoint_write`` event reports.

        Pass ``step`` explicitly when you have it: the fallback
        ``int(state.step)`` is a device→host scalar fetch that waits for
        the step in flight — precisely the sync this module exists to
        avoid.
        """
        if self._closed:
            raise RuntimeError("AsyncCheckpointer is closed")
        t0 = time.perf_counter()
        self._raise_pending()
        self._wait_idle(next_step=step)
        self._commit_pending()
        self._raise_pending()
        if step is None:
            step = int(state.step)
        with span("ckpt/snapshot"):
            snapshot = self._clone(state)
        handle = SaveHandle(
            int(step), snapshot, fault_plan=fault_plan,
            retain_device_state=retain_device_state, data_state=data_state,
        )
        handle.stall_ms = (time.perf_counter() - t0) * 1000
        handle.enqueued_at = time.perf_counter()
        reg = get_telemetry().registry
        reg.gauge(
            "ckpt_queue_depth", help="checkpoint saves in flight"
        ).set(1)
        reg.counter(
            "ckpt_stall_ms_total",
            help="cumulative train-loop ms blocked on checkpointing",
        ).inc(handle.stall_ms)
        with self._cv:
            self._in_flight = handle
        self._queue.put(handle)
        return handle

    def wait(self) -> None:
        """Block until the in-flight save (if any) has published; raise
        any stored writer error. The canonical 'surface faults here'
        point."""
        self._wait_idle(emit=False)
        self._commit_pending()
        self._raise_pending()

    def drain(self, raise_errors: bool = True) -> None:
        """``wait`` that optionally demotes errors to a log line — the
        emergency-save path drains best-effort (the process is going down
        and an older checkpoint may still exist)."""
        try:
            self.wait()
        except Exception:
            if raise_errors:
                raise
            logger.exception("async checkpoint drain: in-flight save failed")

    def close(self, raise_errors: bool = False) -> None:
        """Drain, stop the writer thread. Idempotent."""
        if self._closed:
            return
        self.drain(raise_errors=raise_errors)
        self._closed = True
        self._queue.put(_STOP)
        self._thread.join(timeout=30.0)

    # -- internals ---------------------------------------------------------

    def _raise_pending(self):
        with self._cv:
            err, self._error = self._error, None
        if err is not None:
            raise err

    def _wait_idle(self, next_step: Optional[int] = None,
                   emit: bool = True) -> float:
        """Wait for the in-flight save; returns the wait in ms and emits
        the backpressure event when a save actually had to wait."""
        with self._cv:
            if self._in_flight is None:
                return 0.0
            blocked_on = self._in_flight.step
            with span("ckpt/backpressure") as waited:
                while self._in_flight is not None:
                    self._cv.wait()
            waited_ms = waited.seconds * 1000
        if emit:
            # never a silent drop: the new save WAITED for the slow one
            get_telemetry().emit(
                "ckpt_backpressure", step=next_step,
                blocked_on_step=blocked_on,
                waited_ms=round(waited_ms, 3),
            )
            logger.warning(
                "checkpoint backpressure: save of step %s waited %.0f ms "
                "for the in-flight save of step %d — writer slower than "
                "the checkpoint interval",
                next_step, waited_ms, blocked_on,
            )
        return waited_ms

    def _commit_pending(self) -> None:
        """Main-thread commit of a deferred multi-process sharded publish
        (the commit barrier of the collective contract)."""
        pending = self._pending_commit
        if pending is None:
            return
        self._pending_commit = None
        tmp, final, step, shapes, bytes_, t_snap, data_state = pending
        ckpt._barrier(f"write_{step}")
        if jax.process_index() == 0:
            ckpt.publish_sharded(tmp, final, step, shapes,
                                 geometry=self.geometry)
            if data_state is not None:
                ckpt.save_data_state(final, data_state)
        ckpt._barrier(f"publish_{step}")
        self._emit_write(step, final, bytes_, t_snap, queued_ms=None,
                         fetch_ms=None, fmt="sharded", stall_ms=0.0)
        self._gc()

    def _worker(self) -> None:
        if self.writer_nice:
            try:
                import os

                os.setpriority(
                    os.PRIO_PROCESS, threading.get_native_id(),
                    self.writer_nice,
                )
            except (AttributeError, OSError):  # non-Linux / no permission
                pass
        while True:
            item = self._queue.get()
            if item is _STOP:
                return
            try:
                # one span over everything this thread does for the save:
                # whatever is not under a child span is its self time
                with span("ckpt/write"):
                    self._process(item)
            except BaseException as e:  # surfaced at the next wait point
                logger.exception(
                    "async checkpoint of step %d failed", item.step
                )
                with self._cv:
                    if self._error is None:
                        self._error = e
            finally:
                item.done.set()
                get_telemetry().registry.gauge(
                    "ckpt_queue_depth", help="checkpoint saves in flight"
                ).set(0)
                with self._cv:
                    self._in_flight = None
                    self._cv.notify_all()

    def _process(self, item: SaveHandle) -> None:
        t_run = time.perf_counter()
        queued_ms = (t_run - item.enqueued_at) * 1000
        # local ref FIRST: the overlap-eval thread shares the handle and
        # nulls item.dev_state when it finishes — possibly mid-fetch here
        dev_state = item.dev_state
        if self.sharded:
            shards, shapes = ckpt.collect_host_shards(dev_state)
            fetch_ms = (time.perf_counter() - t_run) * 1000
            if not item.retain_device_state:
                item.dev_state = None  # free the device copy asap
            final = ckpt.checkpoint_path(self.directory, item.step)
            tmp = final + ".tmp"
            ckpt.write_sharded_local(tmp, shards)
            nbytes = sum(int(v.nbytes) for v in shards.values())
            if jax.process_count() == 1:
                ckpt.publish_sharded(tmp, final, item.step, shapes,
                                     geometry=self.geometry)
                if item.data_state is not None:
                    ckpt.save_data_state(final, item.data_state)
                self._emit_write(
                    item.step, final, nbytes, t_run, queued_ms, fetch_ms,
                    fmt="sharded", stall_ms=item.stall_ms,
                )
                self._gc()
            else:
                # commit barrier must run on the main thread (collective);
                # deferred to the next save()/wait()/close()
                self._pending_commit = (
                    tmp, final, item.step, shapes, nbytes, t_run,
                    item.data_state,
                )
            item.path = final
            return
        with span("ckpt/fetch"):
            host = jax.device_get(dev_state)
        fetch_ms = (time.perf_counter() - t_run) * 1000
        if not item.retain_device_state:
            item.dev_state = None
        writer = self._write_fn or ckpt.save_checkpoint
        item.path = writer(
            self.directory, host, step=item.step,
            fault_plan=item.fault_plan,
            data_state=item.data_state,
            geometry=self.geometry,
            event_extra={
                "async": True,
                "stall_ms": round(item.stall_ms, 3),
                "queued_ms": round(queued_ms, 3),
                "fetch_ms": round(fetch_ms, 3),
            },
        )
        self._gc()

    def _emit_write(self, step, path, nbytes, t0, queued_ms, fetch_ms,
                    fmt, stall_ms):
        fields = {
            "path": path, "bytes": nbytes, "format": fmt, "async": True,
            "seconds": round(time.perf_counter() - t0, 6),
            "write_ms": round((time.perf_counter() - t0) * 1000, 3),
            "stall_ms": round(stall_ms, 3),
            "process": jax.process_index(),
        }
        if queued_ms is not None:
            fields["queued_ms"] = round(queued_ms, 3)
        if fetch_ms is not None:
            fields["fetch_ms"] = round(fetch_ms, 3)
        get_telemetry().emit("checkpoint_write", step=step, **fields)

    def _gc(self) -> None:
        if self.keep_last is None:
            return
        if self.sharded and jax.process_index() != 0:
            return
        try:
            ckpt.gc_checkpoints(self.directory, self.keep_last)
        except Exception:
            logger.exception("checkpoint GC failed (non-fatal)")
