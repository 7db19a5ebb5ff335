"""Training spans: one primitive for "the program was doing X from t0 to t1".

``with span("train/flush"):`` does two things. It opens a
``jax.profiler.TraceAnnotation``, so that while a profiler trace is being
collected (``--profile-steps``, the flight recorder) the span is an event
on the host plane of that trace, on the clock the device planes use: a
device idle gap can be read against what the program was doing. When no
trace is being collected the annotation is a no-op in C++ (well under a
microsecond), so there is no switch. And on exit it observes the
host-clock duration into ``phase_seconds{phase=<name>}`` of the installed
telemetry's registry (``observability.core.get_telemetry``) — always.

The profiler writes an annotation into the trace when it *closes*, and
drops one that is still open when the trace stops — which is what a
checkpoint write of seconds is in a trace of a few steps. So while a trace
is being collected a span also leaves an instant event ``<name>:begin``
when it opens: a ``:begin`` with no span of its name after it on its
thread is a span that was open from there to the end of the trace.

Spans nest lexically: a span never outlives its parent on its thread.
None is opened inside a jitted function. ``CATALOGUE`` is every name the
program opens; ``span()`` refuses any other, so a reader of traces
(``benchmark/spans.py``) can rely on the list.

jax is imported when the first span opens, not with this module: the
loaders and the ``obs`` tools import it without jax.
"""

from __future__ import annotations

import time

from pytorch_distributed_nn_tpu.observability.core import get_telemetry

#: (name, the thread that opens it, what it covers)
CATALOGUE = (
    ("train/step", "loop", "one iteration of Trainer.train()'s step loop"),
    ("train/data", "loop", "the data phase: the loader's next batch or indices"),
    ("input/produce", "loop", "host work that makes the batch: index draw, "
     "shard read, batch generation, the wait on the prefetch queue, "
     "pipeline or worker pool"),
    ("input/put", "loop", "dispatch of the batch to the device: device_put, "
     "PRNG fold_in, the jitted prep program"),
    ("train/dispatch", "loop", "the call of the jitted train step (returns "
     "when the runtime has queued it, or blocks while its queue is full)"),
    ("train/flush", "loop", "a metrics flush with records pending"),
    ("train/flush_fetch", "loop", "the flush's blocking jax.device_get"),
    ("train/flush_publish", "loop", "record building, the stream write, "
     "events and gauges"),
    ("ckpt/save", "loop", "one periodic checkpoint, as the loop sees it"),
    ("ckpt/backpressure", "loop", "waiting for the previous save's writer"),
    ("ckpt/snapshot", "loop", "dispatch of the on-device snapshot clone"),
    ("ckpt/write", "writer", "everything the writer thread does for one "
     "save, from dequeue to publish and keep-last GC"),
    ("ckpt/fetch", "writer", "device -> host fetch of the snapshot"),
    ("ckpt/serialize", "writer", "flax msgpack serialization"),
    ("ckpt/compress", "writer", "host codec compression"),
    ("ckpt/file", "writer", "tmp write, atomic rename, manifest and "
     "iterator-state sidecars"),
)
NAMES = frozenset(name for name, _, _ in CATALOGUE)
BEGIN = ":begin"  # suffix of the instant event a span leaves as it opens

_TraceAnnotation = None


class Span:
    """The context manager ``span()`` returns; ``seconds`` is set on exit.

    Takes any name: ``utils.timing.PhaseTimer`` builds its free-form
    phases on it. ``registry`` overrides the installed telemetry's.
    """

    __slots__ = ("name", "seconds", "_registry", "_annotation", "_t0")

    def __init__(self, name: str, registry=None):
        self.name = name
        self.seconds = 0.0
        self._registry = registry

    def __enter__(self) -> "Span":
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        if _TraceAnnotation.is_enabled():  # a trace is being collected
            with _TraceAnnotation(self.name + BEGIN):
                pass
        self._annotation = _TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        registry = self._registry
        if registry is None:
            registry = get_telemetry().registry
        registry.histogram(
            "phase_seconds", help="wall-clock per phase",
            labels={"phase": self.name},
        ).observe(self.seconds)
        return False


def span(name: str) -> Span:
    """A span of the catalogue; any other name is a ``ValueError``."""
    if name not in NAMES:
        raise ValueError(
            f"{name!r} is not in the span catalogue (observability/spans.py "
            f"CATALOGUE): {sorted(NAMES)}")
    return Span(name)
