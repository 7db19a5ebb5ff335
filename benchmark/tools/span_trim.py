"""Cut a fixture with the program's spans out of a profiler trace.

    python3 -m benchmark.tools.span_trim <xplane.pb> <out.textproto> <steps> [min-ns [skip-steps]]

``trace_tool trim`` keeps the longest events of the Python threads, which
drops most of the program's spans (``benchmark/spans.py``): they are
short. This keeps the same device lines for ``steps`` whole steps after
``skip-steps``, and of every host thread that carries spans: each span
that reaches into that window, each ``<name>:begin`` event inside it, the
``:begin`` of each span the trace's end cut off, and the longest Python
frames, for the eye.
"""

from __future__ import annotations

import sys

from benchmark import spans
from benchmark import trace as tr
from benchmark.tools import trace_tool

FRAMES = 40     # Python frames kept per thread


def device_planes(data, steps: int, min_ns: float, skip: int):
    """([(plane name, {line: [(text, start_ns, duration_ns)]})], (lo, hi)):
    what ``trace_tool.trim`` keeps of the device planes."""
    planes, lo, hi = [], None, None
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines
                 if ln.name in tr.DEVICE_LINES}
        modules = [tr.Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines.get(tr.MODULES_LINE, [])]
        program = tr.step_program(modules)
        starts = sorted(e.start for e in modules if e.text == program)[skip:]
        if len(starts) < steps + 2:
            raise SystemExit(f"{plane.name}: only {len(starts)} starts after "
                             f"the {skip} skipped")
        # one start before the window (the reduction leaves the first out)
        a, b = starts[0], starts[steps + 1]
        lo, hi = (a, b) if lo is None else (min(lo, a), max(hi, b))
        kept = {}
        for name, events in lines.items():
            keep = [e for e in events if a <= e.start_ns <= b and (
                name == tr.MODULES_LINE or e.duration_ns >= min_ns)]
            if name == tr.ASYNC_LINE:   # only collectives are read from it
                keep = [e for e in keep
                        if tr.parse_op(e.name).collective == "start"]
            kept[name] = [
                (e.name if name == tr.MODULES_LINE
                 else trace_tool.short_text(e.name), e.start_ns, e.duration_ns)
                for e in keep]
        planes.append((plane.name, kept))
    return planes, (lo, hi)


def host_lines(data, lo: float, hi: float) -> dict:
    host = data.find_plane_with_name(tr.HOST_PLANE)
    kept = {}
    for i, line in enumerate(host.lines if host is not None else []):
        events = [tr.Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events]
        program = [e for e in events if e.text.startswith(spans.PREFIXES)]
        if not program:
            continue
        cut_off = {(name, a) for a, b, name in spans.span_events(program)
                   if b == spans.OPEN}
        keep = [e for e in program if e.end >= lo and e.start <= hi or (
            e.text.endswith(spans.BEGIN)
            and (e.text[:-len(spans.BEGIN)], e.start) in cut_off)]
        frames = sorted((e for e in events if e.text.startswith("$")
                         and e.end >= lo and e.start <= hi),
                        key=lambda e: e.start - e.end)[:FRAMES]
        kept[f"{i}:{line.name}"] = [
            (e.text, e.start, e.end - e.start)
            for e in sorted(keep + frames, key=lambda e: e.start)]
    return kept


def trim(path: str, out: str, steps: int, min_ns: float = 1000.0,
         skip: int = 0) -> None:
    data = tr.read(path)
    planes, (lo, hi) = device_planes(data, steps, min_ns, skip)
    chunks = [trace_tool._plane(n, name, kept, lo)
              for n, (name, kept) in enumerate(planes, 1)]
    chunks.append(trace_tool._plane(len(planes) + 1, tr.HOST_PLANE,
                                    host_lines(data, lo, hi), lo,
                                    strip_prefix=True))
    with open(out, "w") as f:
        f.write("".join(chunks))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 3:
        print(__doc__, file=sys.stderr)
        return 2
    trim(argv[0], argv[1], int(argv[2]),
         float(argv[3]) if len(argv) > 3 else 1000.0,
         int(argv[4]) if len(argv) > 4 else 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
