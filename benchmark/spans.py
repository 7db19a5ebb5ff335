"""Device idle time, attributed to the program's own spans.

The program marks its step loop, its loaders and its checkpoint writer
with ``jax.profiler.TraceAnnotation`` spans (``pytorch_distributed_nn_tpu/
observability/spans.py`` has the catalogue). In a profiler trace they sit
on the host plane, on the clock the device planes use. This module cuts
the same whole-steps window ``trace.summarize`` cuts, takes each chip's
idle time (window - union of ``XLA Ops``) and **partitions** it by span
name, every idle nanosecond to exactly one name, by what the host was
doing at that moment:

  1. a span of the step loop's thread in which the host itself works
     (``HOST_WORK``: producing a batch, publishing a flush, waiting for the
     writer, dispatching the snapshot) — the loop could not have launched
     the next step then, whatever else was going on;
  2. else the innermost ``ckpt/*`` span open on another thread (the
     checkpoint writer): the loop's thread is waiting for the interpreter
     lock or the runtime while the writer works;
  3. else the innermost span open on the step loop's thread
     (``train/dispatch``, ``train/flush_fetch``, ``input/put``: waiting on
     the runtime, or the self time of an enclosing span);
  4. else ``unattributed``: the share the catalogue does not cover.

The step loop's thread is the host line with the most ``train/step``
spans. The profiler drops an annotation that is still open when the trace
stops (a checkpoint write of seconds in a trace of a few steps), so the
program also leaves an instant ``<name>:begin`` event as each span opens:
a last ``:begin`` with no span of its name after it on its thread is read
as a span open from there to the end of the trace.

Per-chip numbers are averaged over the chips. A trace without device
planes, without a whole step or without a ``train/step`` span (a program
older than the catalogue, a CPU rehearsal) reduces to ``None`` and every
reader returns nothing.
"""

from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional, Tuple

from benchmark.trace import (
    MODULES_LINE,
    OPS_LINE,
    Interval,
    Trace,
    clip,
    length,
    load,
    step_starts,
    subtract,
    union,
)

Segment = Tuple[float, float, str]  # (start_ns, end_ns, span name)

LOOP_SPAN = "train/step"     # the thread that carries it is the step loop's
CKPT = "ckpt/"
PREFIXES = ("train/", "input/", CKPT)
BEGIN = ":begin"
HOST_WORK = ("input/produce", "train/flush_publish", "ckpt/backpressure",
             "ckpt/snapshot")
UNATTRIBUTED = "unattributed"
GAPS = 5
OPEN = float("inf")          # end of a span the trace's end cut off


def span_events(events) -> List[Segment]:
    """The program's spans among one host thread's events, those still
    open when the trace stopped included (see the module docstring)."""
    spans: List[Segment] = []
    begun: Dict[str, float] = {}    # name -> its last :begin
    for e in events:
        if not e.text.startswith(PREFIXES):
            continue
        if e.text.endswith(BEGIN):
            name = e.text[:-len(BEGIN)]
            begun[name] = max(begun.get(name, e.start), e.start)
        else:
            spans.append((e.start, e.end, e.text))
    for name, at in begun.items():
        if not any(n == name and start >= at for start, _, n in spans):
            spans.append((at, OPEN, name))
    return spans


def threads(trace: Trace) -> Tuple[List[Segment], List[List[Segment]]]:
    """(the step loop's spans, the spans of each other thread that carries
    ``ckpt/*`` spans). The step loop's thread is the one with the most
    ``train/step`` spans; without one, ``([], [])``."""
    per_thread = [span_events(ev) for ev in trace.host.values()]
    steps = [sum(1 for s in spans if s[2] == LOOP_SPAN) for spans in per_thread]
    if not any(steps):
        return [], []
    loop = steps.index(max(steps))
    writers = [spans for i, spans in enumerate(per_thread) if i != loop
               and any(s[2].startswith(CKPT) for s in spans)]
    return per_thread[loop], writers


def innermost(spans: List[Segment]) -> List[Segment]:
    """One thread's nested spans as disjoint segments in time order, each
    named by the innermost span open in it (so a name's segments are its
    self time)."""
    out: List[Segment] = []
    stack: List[Segment] = []
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(start)
        if stack:
            # a child never outlives its parent (clock jitter aside)
            end = min(end, stack[-1][1])
            if start > cursor:
                out.append((cursor, start, stack[-1][2]))
        cursor = max(cursor, start)
        if end > start:
            stack.append((start, end, name))
    close_until(OPEN)
    return out


class _Measure:
    """Length of merged ``intervals`` inside any [a, b], in O(log n)."""

    def __init__(self, intervals: List[Interval]):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.cum = [0.0]
        for a, b in intervals:
            self.cum.append(self.cum[-1] + (b - a))

    def within(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)    # first that ends after a
        j = bisect.bisect_left(self.starts, b)   # first that starts at/after b
        if i >= j:
            return 0.0
        total = self.cum[j] - self.cum[i]
        total -= max(0.0, a - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - b)
        return total


def claim(segments: List[Segment], taken: List[Interval]):
    """(the parts of ``segments`` outside merged ``taken``, ``taken`` with
    them added)."""
    pieces = []
    for a, b, name in segments:
        pieces += [(lo, hi, name) for lo, hi in subtract([(a, b)], taken)]
    return pieces, union(taken + [(a, b) for a, b, _ in pieces])


def partition(idle: List[Interval], loop: List[Segment],
              writers: List[List[Segment]]) -> Dict[str, float]:
    """Merged ``idle`` intervals -> ns per name, by the module docstring's
    precedence; the values sum to ``length(idle)``."""
    loop_segments = innermost(loop)
    rules = [[s for s in loop_segments if s[2] in HOST_WORK]]
    rules += [innermost([s for s in w if s[2].startswith(CKPT)])
              for w in writers]
    rules.append([s for s in loop_segments if s[2] not in HOST_WORK])
    measure = _Measure(idle)
    by: Dict[str, float] = collections.defaultdict(float)
    taken: List[Interval] = []
    for segments in rules:
        pieces, taken = claim(segments, taken)
        for a, b, name in pieces:
            by[name] += measure.within(a, b)
    by[UNATTRIBUTED] = length(idle) - sum(by.values())
    return {name: ns for name, ns in by.items()
            if ns > 0 or name == UNATTRIBUTED}


def _open_at(segments: List[Segment], t: float) -> Optional[str]:
    for a, b, name in segments:
        if a <= t < b:
            return name
    return None


def summarize(trace: Trace) -> Optional[dict]:
    """The partition of a trace's idle time, or ``None`` where the trace
    has no device plane, no whole step or no ``train/step`` span."""
    loop, writers = threads(trace)
    if not trace.chips or not loop:
        return None
    starts = {plane: step_starts(lines.get(MODULES_LINE, []))[1]
              for plane, lines in trace.chips.items()}
    steps = min(len(s) for s in starts.values()) - 1
    if steps < 1:
        return None
    by: Dict[str, float] = collections.defaultdict(float)
    idle_ns = 0.0
    first = None
    for plane in sorted(trace.chips):
        lo, hi = starts[plane][0], starts[plane][steps]
        ops = [(e.start, e.end) for e in trace.chips[plane].get(OPS_LINE, [])
               if lo <= e.start < hi]
        idle = subtract([(lo, hi)], union(clip(ops, lo, hi)))
        idle_ns += length(idle)
        for name, ns in partition(idle, loop, writers).items():
            by[name] += ns
        if first is None:
            first = (lo, hi, idle)
    chips = len(trace.chips)
    lo, hi, idle = first
    loop_segments = innermost(loop)
    writer_segments = [s for w in writers for s in innermost(w)]
    names: Dict[str, dict] = {}
    for spans in [loop] + writers:
        for a, b, name in spans:
            if lo <= a < hi:
                row = names.setdefault(
                    name, {"calls": 0, "total_ms": 0.0, "self_ms": 0.0})
                row["calls"] += 1
                row["total_ms"] += (min(b, hi) - a) / 1e6
    for a, b, name in loop_segments + writer_segments:
        if name in names:
            names[name]["self_ms"] += length(clip([(a, b)], lo, hi)) / 1e6
    gaps = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:GAPS]:
        mid = (a + b) / 2
        gaps.append({"ms": (b - a) / 1e6, "at_ms": (a - lo) / 1e6,
                     "loop_span": _open_at(loop_segments, mid),
                     "writer_span": _open_at(writer_segments, mid)})
    return {
        "steps": steps,
        "idle_ms_per_step": idle_ns / chips / steps / 1e6,
        # ms per step, mean of chips; the values sum to idle_ms_per_step
        "ms_per_step": {name: ns / chips / steps / 1e6
                        for name, ns in sorted(by.items())},
        "saves": names.get("ckpt/save", {}).get("calls", 0),
        # chip 0's window: calls, total and self time of each span that
        # begins in it, cut at the window's end
        "spans": names,
        "longest_gaps": gaps,
    }


def reduce(path: str) -> Optional[dict]:
    return summarize(load(path))
