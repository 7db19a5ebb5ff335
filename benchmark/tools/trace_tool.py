"""Look at a profiler trace by hand, and cut a fixture out of one.

    python3 -m benchmark.tools.trace_tool dump <xplane.pb|.textproto> [events-per-line]
    python3 -m benchmark.tools.trace_tool trim <xplane.pb> <out.textproto> <steps> [min-ns [skip-steps]]

``dump`` prints every plane and line with its event count, its first
events and the stats they carry — the first thing to do with a trace from
a new libtpu, before trusting ``benchmark/trace.py`` on it. ``trim`` keeps
the device lines the reduction reads for the first ``steps`` whole steps,
without the events shorter than ``min-ns`` (the thousands of async starts
and bitcasts), each instruction's text cut to what the reduction parses,
plus the longest events of the Python threads in that span, and writes
them as a text-format ``XSpace`` — small enough to commit, and loaded back
by ``trace.load``.
"""

from __future__ import annotations

import sys

from benchmark import trace as tr


def dump(path: str, per_line: int = 4) -> None:
    for plane in tr.read(path).planes:
        print(f"PLANE {plane.name!r} stats={dict(plane.stats)}")
        for line in plane.lines:
            events = list(line.events)
            span = ""
            if events:
                lo = min(e.start_ns for e in events)
                hi = max(e.start_ns + e.duration_ns for e in events)
                span = f" span={(hi - lo) / 1e6:.3f} ms from {lo / 1e6:.3f} ms"
            print(f"  LINE {line.name!r} events={len(events)}{span}")
            keys = set()
            for e in events:
                keys.update(k for k, _ in e.stats)
            if keys:
                print(f"    stat keys: {sorted(keys)}")
            for e in events[:per_line]:
                stats = {k: (str(v)[:160]) for k, v in e.stats}
                print(f"    {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}")


def _quote(text: str) -> str:
    return '"' + str(text).replace("\\", "\\\\").replace('"', '\\"') + '"'


def short_text(text: str) -> str:
    """An instruction's text cut to what ``trace.parse_op`` reads from it:
    name, result arity, opcode, operand count, fusion kind, call target."""
    op = tr.parse_op(text)
    result = "x" if op.outputs == 1 else "(" + ", ".join(["x"] * op.outputs) + ")"
    out = f"%{op.name} = {result} {op.opcode}({', '.join(['%o'] * op.operands)})"
    if op.kind:
        out += f", kind={op.kind}"
    if op.target:
        out += f', custom_call_target="{op.target}"'
    if tr.parse_op(out) != op:
        raise ValueError(f"cannot shorten {text[:200]!r}")
    return out


def trim(path: str, out: str, steps: int, min_ns: float = 1000.0,
         skip: int = 0, host_events: int = 300) -> None:
    data = tr.read(path)
    chunks = []
    plane_id = 0
    span = None
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        plane_id += 1
        lines = {ln.name: list(ln.events) for ln in plane.lines
                 if ln.name in tr.DEVICE_LINES}
        modules = [tr.Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines.get(tr.MODULES_LINE, [])]
        program = tr.step_program(modules)
        starts = sorted(e.start for e in modules if e.text == program)
        if len(starts) < skip + steps + 2:
            raise SystemExit(f"{plane.name}: only {len(starts)} starts traced")
        # one start before the window (the reduction leaves the first out)
        starts = starts[skip:]
        lo, hi = starts[0], starts[steps + 1]
        span = (lo, hi) if span is None else (min(span[0], lo), max(span[1], hi))
        kept = {}
        for name, events in lines.items():
            keep = [e for e in events if lo <= e.start_ns <= hi and (
                name == tr.MODULES_LINE or e.duration_ns >= min_ns)]
            if name == tr.ASYNC_LINE:   # only collectives are read from it
                keep = [e for e in keep
                        if tr.parse_op(e.name).collective == "start"]
            kept[name] = [
                (e.name if name == tr.MODULES_LINE else short_text(e.name),
                 e.start_ns, e.duration_ns) for e in keep]
        chunks.append(_plane(plane_id, plane.name, kept, span[0]))
    host = data.find_plane_with_name(tr.HOST_PLANE)
    if host is not None and span is not None:
        threads = [(ln.name, [e for e in ln.events
                              if span[0] <= e.start_ns <= span[1]])
                   for ln in host.lines]
        python = sorted(
            (t for t in threads if any(e.name.startswith("$") for e in t[1])),
            key=lambda t: -len(t[1]))
        kept = {}
        for i, (name, events) in enumerate(python):
            events = sorted(events, key=lambda e: -e.duration_ns)[:host_events]
            kept[f"{i}:{name}"] = [(e.name, e.start_ns, e.duration_ns)
                                   for e in sorted(events, key=lambda e: e.start_ns)]
        chunks.append(_plane(plane_id + 1, tr.HOST_PLANE, kept, span[0],
                             strip_prefix=True))
    with open(out, "w") as f:
        f.write("".join(chunks))


def _plane(plane_id: int, name: str, lines: dict, origin: float,
           strip_prefix: bool = False) -> str:
    """One ``XPlane`` in text format; times rebased to ``origin``."""
    meta = {}
    body = []
    for i, (line_name, events) in enumerate(lines.items(), 1):
        if strip_prefix:
            line_name = line_name.split(":", 1)[1]
        body.append(f"  lines {{\n    id: {i}\n    name: {_quote(line_name)}\n"
                    f"    timestamp_ns: 0\n")
        for text, start_ns, duration_ns in events:
            mid = meta.setdefault(text, len(meta) + 1)
            body.append(
                f"    events {{ metadata_id: {mid} "
                f"offset_ps: {int(round((start_ns - origin) * 1000))} "
                f"duration_ps: {int(round(duration_ns * 1000))} }}\n")
        body.append("  }\n")
    head = f"planes {{\n  id: {plane_id}\n  name: {_quote(name)}\n"
    for text, mid in meta.items():
        body.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                    f"name: {_quote(text)} }} }}\n")
    return head + "".join(body) + "}\n"


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) >= 2 and argv[0] == "dump":
        dump(argv[1], int(argv[2]) if len(argv) > 2 else 4)
    elif len(argv) >= 4 and argv[0] == "trim":
        trim(argv[1], argv[2], int(argv[3]),
             float(argv[4]) if len(argv) > 4 else 1000.0,
             int(argv[5]) if len(argv) > 5 else 0)
    else:
        print(__doc__, file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
