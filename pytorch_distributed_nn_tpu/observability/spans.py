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

Set-up has spans too: ``setup/*`` (``SetupLog``). Each one also keeps
its parent, its ``time.monotonic()`` start and end (the clock of the step
records' ``mono``) and the compile work jax did while it was the
innermost open ``setup/*`` span on its thread
(``observability/compiles.py`` charges it). A run's log holds them in
memory until the run's telemetry can take them, and writes them out as
one ``setup`` event when a span with no ``setup/*`` parent closes:
``setup/init`` at the end of ``Trainer.__init__``, ``setup/first_step``
at the end of each ``train()`` call's first iteration.

jax is imported when the first span opens, not with this module: the
loaders and the ``obs`` tools import it without jax.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

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
    ("setup/init", "loop", "Trainer.__init__, entry to return"),
    ("setup/model", "loop", "model construction, create_train_state (eager "
     "parameter and optimizer init), warm-start or resume restore"),
    ("setup/step_build", "loop", "build_train_step and the jitted step's "
     "construction, the state's device_put onto the mesh"),
    ("setup/data", "loop", "the train and test loaders, synthetic-set "
     "generation included"),
    ("setup/step_cost", "loop", "the step program's one trace and lowering, "
     "and _static_step_cost's reading of it"),
    ("setup/first_step", "loop", "the first iteration of a train() call: "
     "data, dispatch (the compile or cache fetch of the lowered step; its "
     "trace and lowering too on a run without a stream), snapshot warm-up"),
)
NAMES = frozenset(name for name, _, _ in CATALOGUE)
SETUP_NAMES = frozenset(n for n in NAMES if n.startswith("setup/"))
_PLAIN = NAMES - SETUP_NAMES
BEGIN = ":begin"  # suffix of the instant event a span leaves as it opens
STAGES = ("trace", "lower", "backend")  # of a compile, as jax reports them
SOURCES = ("compiled", "cached")        # of a program the backend handed back

_TraceAnnotation = None


class Span:
    """The context manager ``span()`` returns; ``seconds`` is set on exit.
    ``registry`` overrides the installed telemetry's."""

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
    """A span of the catalogue; any other name is a ``ValueError``, and
    so is a ``setup/*`` name: those come from a run's ``SetupLog``."""
    if name not in _PLAIN:
        raise ValueError(
            f"{name!r} is not in the span catalogue (observability/spans.py "
            f"CATALOGUE) or is a setup/* span (SetupLog.span): "
            f"{sorted(_PLAIN)}")
    return Span(name)


# -- set-up ------------------------------------------------------------------

_open = threading.local()  # .setup: the setup/* spans open on this thread


def open_setup_spans() -> List["SetupSpan"]:
    """This thread's open ``setup/*`` spans, outermost first."""
    stack = getattr(_open, "setup", None)
    if stack is None:
        stack = _open.setup = []
    return stack


class CompileTally:
    """Compile work charged to one span, or to one program outside every
    span: exclusive seconds by stage, the persistent cache's fetch
    seconds (inside ``backend``), programs by source and the programs
    ``lowered`` (one a ``lower`` stage), and seconds and source by
    ``fun_name``."""

    __slots__ = ("seconds", "fetch_s", "programs", "funs")

    def __init__(self):
        self.seconds: Dict[str, float] = dict.fromkeys(STAGES, 0.0)
        self.fetch_s = 0.0
        self.programs: Dict[str, int] = dict.fromkeys((*SOURCES, "lowered"), 0)
        self.funs: Dict[str, list] = {}  # fun_name -> [seconds, source]

    def add(self, stage: str, seconds: float, fun_name: str,
            source: Optional[str] = None) -> None:
        self.seconds[stage] += seconds
        fun = self.funs.setdefault(fun_name, [0.0, None])
        fun[0] += seconds
        if stage == "lower":
            self.programs["lowered"] += 1
        if source is not None:         # a backend stage: one program
            self.programs[source] += 1
            fun[1] = source

    def fields(self) -> dict:
        return {"compile_s": dict(self.seconds), "fetch_s": self.fetch_s,
                "programs": dict(self.programs)}


class SetupSpan(Span):
    """A ``setup/*`` span: besides the span's own work it keeps its
    parent, its monotonic start and end, and the compile work charged to
    it while it is the innermost open ``setup/*`` span on its thread."""

    __slots__ = ("parent", "step", "mono0", "mono1", "compiles", "_log",
                 "_outer")

    def __init__(self, name: str, log: "SetupLog",
                 parent: Optional[str] = None, step: Optional[int] = None):
        super().__init__(name, log.registry)
        self.parent = parent
        self.step = step
        self.compiles = CompileTally()
        self._log = log

    def __enter__(self) -> "SetupSpan":
        stack = open_setup_spans()
        self._outer = stack[-1] if stack else None
        if self.parent is None and self._outer is not None:
            self.parent = self._outer.name
        stack.append(self)
        super().__enter__()
        self.mono0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.mono1 = time.monotonic()
        stack = open_setup_spans()
        if stack and stack[-1] is self:
            stack.pop()
        super().__exit__(*exc)
        self._log.closed(self)
        return False

    @property
    def registry(self):
        return self._log.registry

    def record(self) -> dict:
        return {"name": self.name, "parent": self.parent,
                "mono0": self.mono0, "mono1": self.mono1,
                "seconds": self.mono1 - self.mono0, **self.compiles.fields()}


class SetupLog:
    """The ``setup/*`` spans of one run. ``registry`` is the run's, made
    before its telemetry, so that a span that closes before the stream
    opens never observes into the previous process default's. Closed
    spans wait here until ``telemetry`` is set and a span with no
    ``setup/*`` parent closes; then they go out as one ``setup`` event."""

    TOP = 5  # slowest fun_names an event names

    def __init__(self, registry):
        self.registry = registry
        self.telemetry = None
        self._closed: List[SetupSpan] = []

    def span(self, name: str, parent: Optional[str] = None,
             step: Optional[int] = None) -> SetupSpan:
        """``parent`` names a span that is not a ``setup/*`` one; ``step``
        is the step record the span's event is written under."""
        if name not in SETUP_NAMES:
            raise ValueError(f"{name!r} is not a setup/* span of the "
                             f"catalogue: {sorted(SETUP_NAMES)}")
        return SetupSpan(name, self, parent, step)

    def closed(self, s: SetupSpan) -> None:
        self._closed.append(s)
        if s._outer is None and self.telemetry is not None:
            self.write(s.step)

    def write(self, step: Optional[int]) -> None:
        held, self._closed = self._closed, []
        funs = [(sec, fun, src, s.name) for s in held
                for fun, (sec, src) in s.compiles.funs.items()]
        funs.sort(key=lambda f: f[0], reverse=True)
        self.telemetry.emit(
            "setup", step=step, spans=[s.record() for s in held],
            slowest=[{"fun_name": fun, "seconds": sec, "source": src,
                      "span": name}
                     for sec, fun, src, name in funs[:self.TOP]])
