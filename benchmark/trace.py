"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers and the result line's ``device`` and ``breakdown`` use.

Read with ``jax.profiler.ProfileData`` — nothing but jax. What a trace of
this program on libtpu 0.0.34 / TPU v5e holds (looked at by hand, PR 22;
``python3 -m benchmark.tools.trace_tool dump`` shows it):

  planes ``/device:TPU:<n>``, one per chip, with lines
      ``XLA Modules``    one event per executed program,
                         ``jit__lambda(<fingerprint>)``
      ``XLA Ops``        one event per HLO operation, back to back; the
                         event's name is the instruction's whole text,
                         ``%fusion.593 = bf16[...] fusion(...), kind=kOutput``
                         — a Mosaic kernel is a ``custom-call`` whose
                         target is ``tpu_custom_call``, named after the
                         flax module that called it (``%attn.36``)
      ``Async XLA Ops``  one event per async pair, from ``-start`` to the
                         end of its ``-done``
      (``Steps``, ``Scalar Unit``, ``TC Overlay`` are not read)
  plane  ``/host:CPU``   one line per host thread; ``python3`` carries the
                         Python tracer's ``$file:line function`` events.
  Device and host events share one clock.

The traced window is cut on each chip between starts of the *step
program* — the module that takes most of the device's time — so it holds
a whole number of steps whatever the trace's edges caught. The first
start is left out: the profiler clips a program already running to the
moment tracing began.

  steps      starts of the step program from the second on, less one; the
             same number on every chip (the fewest any chip's trace holds)
  busy       union of the intervals of ``XLA Ops`` events, clipped to the window
  idle       window - busy; its longest gaps are named by the innermost
             Python frame the step loop's thread was in at the time
  collective in flight: the op itself (synchronous) or its async event;
             exposed: the part of that during which no other op ran
  kernels    Mosaic calls, sorted into the families and kinds the
             configuration's ``kernels`` block describes

Per-chip numbers are averaged over the chips.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_LINES = (OPS_LINE, ASYNC_LINE, MODULES_LINE)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
MOSAIC_TARGET = "tpu_custom_call"
TOP = 10    # entries of each ``breakdown`` list: the contract's most

_INSTRUCTION = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_KIND = re.compile(r"\bkind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


@dataclasses.dataclass
class Op:
    """One HLO instruction, as far as its text in the trace tells."""

    name: str            # fusion.593
    opcode: str          # fusion, custom-call, all-reduce, copy-done ...
    outputs: int = 1     # elements of the result tuple
    operands: int = 0
    kind: str = ""       # kLoop, kOutput ... (fusions)
    target: str = ""     # custom_call_target

    @property
    def base(self) -> str:
        return re.sub(r"\.\d+$", "", self.name)

    @property
    def group(self) -> str:
        """What ``breakdown`` sums under: the name without its number,
        and for an anonymous fusion its kind."""
        if self.base == "fusion" and self.kind:
            return f"fusion:{self.kind}"
        return self.base

    @property
    def collective(self) -> Optional[str]:
        """'sync', 'start', 'done' or None."""
        for c in COLLECTIVES:
            if self.opcode == c:
                return "sync"
            if self.opcode in (c + "-start", c + "-done"):
                return self.opcode[len(c) + 1:]
        return None


def _top_level_items(text: str) -> int:
    """Items of a comma-separated list, commas inside brackets ignored."""
    depth, items = 0, 1
    for ch in text:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            items += 1
    return items


def _matching(text: str, start: int) -> int:
    """Index of the bracket that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] in "([{":
            depth += 1
        elif text[i] in ")]}":
            depth -= 1
            if depth == 0:
                return i
    return len(text)


def parse_op(text: str) -> Op:
    m = _INSTRUCTION.match(text)
    if not m:                       # not HLO text: a bare name
        name = text.lstrip("%").split(" ")[0]
        return Op(name, re.sub(r"\.\d+$", "", name))
    name, rest = m.group("name"), m.group("rest")
    outputs = 1
    after_shape = rest
    if rest.startswith("("):
        close = _matching(rest, 0)
        outputs = _top_level_items(rest[1:close])
        after_shape = rest[close + 1:]
    op = _OPCODE.search(after_shape)
    opcode, operands = (re.sub(r"\.\d+$", "", name), 0)
    if op:
        opcode = op.group(1)
        open_at = after_shape.index("(", op.start(1))
        operands = after_shape[open_at:_matching(after_shape, open_at)].count("%")
    kind, target = _KIND.search(rest), _TARGET.search(rest)
    return Op(name, opcode, outputs, operands,
              kind.group(1) if kind else "", target.group(1) if target else "")


@dataclasses.dataclass
class Event:
    text: str
    start: float   # ns
    end: float     # ns
    op: Optional[Op] = None

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclasses.dataclass
class Trace:
    chips: Dict[str, Dict[str, List[Event]]]   # plane -> line -> events
    host: Dict[str, List[Event]]               # thread -> events


def _events(line, parse: bool) -> List[Event]:
    parsed: Dict[str, Op] = {}
    out = []
    for e in line.events:
        op = None
        if parse:
            op = parsed.get(e.name)
            if op is None:
                op = parsed[e.name] = parse_op(e.name)
        out.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns, op))
    out.sort(key=lambda ev: ev.start)
    return out


def read(path: str):
    """``jax.profiler.ProfileData`` of an ``.xplane.pb`` as the profiler
    wrote it, or of a ``.textproto`` of the same message (the fixtures)."""
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def load(path: str) -> Trace:
    data = read(path)
    chips: Dict[str, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chips[plane.name] = {
                line.name: _events(line, line.name != MODULES_LINE)
                for line in plane.lines if line.name in DEVICE_LINES
            }
        elif plane.name == HOST_PLANE:
            # several threads can share a name
            for i, line in enumerate(plane.lines):
                host[f"{line.name}#{i}"] = _events(line, False)
    return Trace(chips, host)


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(merged: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merged)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- the reduction -----------------------------------------------------------

def step_program(modules: List[Event]) -> Optional[str]:
    """The program that takes most of the device's time."""
    total: Dict[str, float] = collections.defaultdict(float)
    for e in modules:
        total[e.text] += e.end - e.start
    return max(total, key=total.get) if total else None


def step_starts(modules: List[Event]) -> Tuple[Optional[str], List[float]]:
    """(step program, its starts without the first — see the module
    docstring)."""
    program = step_program(modules)
    return program, [e.start for e in modules if e.text == program][1:]


def classify_kernel(op: Op, kernels: Optional[dict]) -> Tuple[str, str]:
    """(family, kind) of a Mosaic call under a configuration's ``kernels``
    block: the family whose ``match`` finds the call's name, the kind
    whose operand and output counts are the call's."""
    for family, spec in (kernels or {}).items():
        if not re.search(spec["match"], op.name):
            continue
        for kind, shape in spec["kinds"].items():
            if shape.get("operands", op.operands) == op.operands and (
                    shape.get("outputs", op.outputs) == op.outputs):
                return family, kind
        return family, "unknown"
    return "unknown", "unknown"


def _python_threads(host: Dict[str, List[Event]]) -> List[str]:
    """Threads the Python tracer saw, busiest first: the step loop's,
    then the others (the checkpoint writer ...)."""
    count = {t: sum(1 for e in ev if e.text.startswith("$"))
             for t, ev in host.items()}
    return sorted((t for t in count if count[t]), key=lambda t: -count[t])


def _innermost(events: List[Event], starts: List[float], at: float):
    """The shortest event that covers the instant ``at``."""
    i = bisect.bisect_right(starts, at)
    best = None
    for reach in (256, len(events)):
        for e in events[max(0, i - reach):i]:
            if e.start <= at < e.end and (
                    best is None or e.end - e.start < best.end - best.start):
                best = e
        if best is not None:
            break
    return best


def host_activity(threads: List[Tuple[List[Event], List[float]]],
                  lo: float, hi: float) -> str:
    """What the host was doing at the midpoint of the gap [lo, hi]: the
    innermost Python frame of the step loop's thread, or — when that
    thread is between frames, which is what waiting for the interpreter
    lock looks like — the frame another Python thread was in."""
    mid = (lo + hi) / 2
    for n, (events, starts) in enumerate(threads):
        e = _innermost(events, starts, mid)
        if e is not None:
            where = e.text.lstrip("$")
            return where if n == 0 else f"other thread in {where}"
    return "between host events" if threads else "host not traced"


def summarize(trace: Trace, kernels: Optional[dict] = None) -> Optional[dict]:
    if not trace.chips:
        return None
    per_chip = []
    group_seconds: Dict[str, float] = collections.defaultdict(float)
    kernel_rows: Dict[Tuple[str, str], dict] = {}
    idle: List[List] = []
    threads = [(trace.host[t], [e.start for e in trace.host[t]])
               for t in _python_threads(trace.host)]
    starts = {plane: step_starts(lines.get(MODULES_LINE, []))
              for plane, lines in trace.chips.items()}
    # the same number of steps on every chip: the trace's edges can catch
    # one start more on one chip than on another
    steps = min(len(s) for _, s in starts.values()) - 1
    if steps < 1:
        return None
    program = None
    for n, plane in enumerate(sorted(trace.chips)):
        lines = trace.chips[plane]
        program, begun = starts[plane]
        lo, hi = begun[0], begun[steps]
        ops = [e for e in lines.get(OPS_LINE, []) if lo <= e.start < hi]
        busy = union(clip(((e.start, e.end) for e in ops), lo, hi))
        flights = [(e.start, e.end) for e in ops if e.op.collective == "sync"]
        flights += [(e.start, e.end) for e in lines.get(ASYNC_LINE, [])
                    if e.op.collective == "start" and lo <= e.start < hi]
        others = union(clip(((e.start, e.end) for e in ops
                             if not e.op.collective), lo, hi))
        in_flight = union(clip(flights, lo, hi))
        per_chip.append({
            "plane": plane,
            "steps": steps,
            "window_s": (hi - lo) / 1e9,
            "busy_s": length(busy) / 1e9,
            "collective_in_flight_s": length(in_flight) / 1e9,
            "collective_exposed_s": length(subtract(in_flight, others)) / 1e9,
            "collective_count": len(flights),
        })
        for e in ops:
            group_seconds[e.op.group] += e.seconds
            if e.op.target == MOSAIC_TARGET:
                key = classify_kernel(e.op, kernels)
                row = kernel_rows.setdefault(key, {
                    "family": key[0], "kind": key[1], "seconds": 0.0,
                    "calls": 0, "names": set()})
                row["seconds"] += e.seconds
                row["calls"] += 1
                row["names"].add(e.op.base)
        if n == 0:
            gaps = subtract([(lo, hi)], busy)
            for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
                idle.append([host_activity(threads, a, b), (b - a) / 1e9])
    chips = len(per_chip)

    def mean(key: str) -> float:
        return sum(c[key] for c in per_chip) / chips

    rows = []
    for row in kernel_rows.values():
        rows.append({**row, "seconds": row["seconds"] / chips,
                     "calls": row["calls"] / chips,
                     "names": sorted(row["names"])})
    ranked = sorted(group_seconds.items(), key=lambda kv: -kv[1])
    return {
        "step_program": program,
        "chips": per_chip,
        "steps": steps,
        "window_s": mean("window_s"),
        "busy_s": mean("busy_s"),
        "collectives": {
            "count": per_chip[0]["collective_count"],
            "in_flight_s": mean("collective_in_flight_s"),
            "exposed_s": mean("collective_exposed_s"),
        },
        "kernels": sorted(rows, key=lambda r: (r["family"], r["kind"])),
        "device_ops": [[name, s / chips] for name, s in ranked[:TOP]],
        "idle_gaps": idle,
    }
