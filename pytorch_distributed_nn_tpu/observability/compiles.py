"""One ``jax.monitoring`` listener for the process: every program jax
makes, the time each stage took, whether the persistent cache handed it
back, and the span that caused it.

jax 0.9.0 reports the stages of making a program as time spans that carry
a ``fun_name`` (it records a stage's start as a scalar of the same name):

    /jax/core/compile/jaxpr_trace_duration           trace: a jit traced
        inside another reports its own span, inside the outer one's
    /jax/core/compile/jaxpr_to_mlir_module_duration  lower
    /jax/core/compile/backend_compile_duration       backend: one a
        program, the persistent cache's fetch included

and, with the persistent cache on, ``/jax/compilation_cache/cache_hits``
(the program was fetched) and ``…/cache_retrieval_time_sec`` inside the
backend stage (``…/cache_misses`` marks a compile written to the cache; a
backend stage without a hit compiled, cache or no cache).

Each stage is charged its exclusive seconds (a nested trace is not
counted twice) to the innermost open ``setup/*`` span on its thread
(``spans.SetupLog``), which writes them out in its ``setup`` event; each
``lower`` stage also counts one program ``lowered``. Outside every
``setup/*`` span a program's stages go out, when its backend stage closes,
as one ``compile`` event of the routed run, under the step its loop is on
(``None`` before the loop), with its lowerings: the answer to "which step
recompiled". Either way they feed ``compile_seconds{stage=}`` and
``programs_total{source=compiled|cached}`` of the run's registry. jax
stamps its spans with ``time.time()``; a ``compile`` event is stamped on
``time.monotonic()`` as the backend stage closes (its ``mono``).

The listener is registered once per process (``install()``) and never
taken down. A ``Trainer`` routes it to its run (``route``) for as long as
its telemetry is the installed one, and ``unroute``s at close: the many
Trainers of one test process neither leak into each other nor count
twice, and with no run routed a compile outside every span counts
nowhere.
"""

from __future__ import annotations

import threading
import weakref
from typing import Optional

from pytorch_distributed_nn_tpu.observability.core import get_telemetry
from pytorch_distributed_nn_tpu.observability.spans import (
    CompileTally,
    open_setup_spans,
)

STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
HIT = "/jax/compilation_cache/cache_hits"
FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"

_install_lock = threading.Lock()
_installed = False
_routes: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_local = threading.local()


class Route:
    """A run's entry in the routing table (keyed by its telemetry, held
    weakly): the step its loop is on, which the loop sets."""

    __slots__ = ("step",)

    def __init__(self):
        self.step: Optional[int] = None


def install() -> None:
    """Register the listener, once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax

        jax.monitoring.register_scalar_listener(_opened)
        jax.monitoring.register_event_time_span_listener(_closed)
        jax.monitoring.register_event_listener(_event)
        jax.monitoring.register_event_duration_secs_listener(_duration)
        _installed = True


def route(telemetry) -> Route:
    """Send compiles outside every span to ``telemetry``'s run while it
    is the installed telemetry."""
    r = _routes[telemetry] = Route()
    return r


def unroute(telemetry) -> None:
    _routes.pop(telemetry, None)


def _thread():
    t = _local
    if not hasattr(t, "frames"):
        t.frames = []    # open stages: the inclusive seconds of their children
        t.hit = False    # the open backend stage was a cache fetch
        t.pending = CompileTally()  # a program's stages outside every span
    return t


def _bare(fun_name: str) -> str:
    """``jit(train_step)`` -> ``train_step``: the stages name one program
    alike."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _opened(event: str, value, **kw) -> None:
    if event in STAGE:
        _thread().frames.append(0.0)


def _closed(event: str, start: float, end: float, fun_name: str = "?",
            **kw) -> None:
    stage = STAGE.get(event)
    if stage is None:
        return
    t = _thread()
    inclusive = end - start
    nested = t.frames.pop() if t.frames else 0.0
    if t.frames:
        t.frames[-1] += inclusive
    seconds = max(inclusive - nested, 0.0)
    source = None
    if stage == "backend":
        source, t.hit = ("cached" if t.hit else "compiled"), False
    name = _bare(fun_name)
    opened = open_setup_spans()
    if opened:
        opened[-1].compiles.add(stage, seconds, name, source)
        registry = opened[-1].registry
    else:
        telemetry = get_telemetry()
        r = _routes.get(telemetry)
        if r is None:
            return
        registry = telemetry.registry
        t.pending.add(stage, seconds, name, source)
        if source is not None:
            tally, t.pending = t.pending, CompileTally()
            telemetry.emit(
                "compile", step=r.step, fun_name=name, source=source,
                compile_s=tally.seconds, fetch_s=tally.fetch_s,
                lowered=tally.programs["lowered"])
    registry.histogram(
        "compile_seconds", help="seconds a stage of making a program took",
        labels={"stage": stage}).observe(seconds)
    if source is not None:
        registry.counter(
            "programs_total", help="programs the backend handed back",
            labels={"source": source}).inc()


def _event(event: str, **kw) -> None:
    if event == HIT:
        _thread().hit = True


def _duration(event: str, duration: float, **kw) -> None:
    if event != FETCH:
        return
    opened = open_setup_spans()
    tally = opened[-1].compiles if opened else _thread().pending
    tally.fetch_s += duration
