"""The operator's view of a device trace: the per-op table and the flight
recorder's incident report.

- the command — ``python -m pytorch_distributed_nn_tpu.observability.xplane
  <trace_dir>`` prints the per-op and per-family device-time table of a
  ``--profile N`` capture or an incident bundle's ``trace/``;
- the flight recorder — ``write_incident_report`` turns a just-captured
  incident bundle (``observability/flightrec.py``) into ``report.md``:
  trigger summary, per-op device-time table from the bundle's trace,
  event-ring tail, environment pointer.

The trace itself is opened in ``utils/profiling`` (``summarize_xplane``).
Every entry point degrades gracefully (a report is still written, marking
the trace section unavailable) when the trace cannot be read or has no
device planes (CPU-only captures). How fast the system is is not read
here: that is ``python3 -m benchmark.run``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

from pytorch_distributed_nn_tpu.utils.profiling import (
    _find_xplane,
    family_summary,
    format_family_summary,
    format_summary,
    summarize_xplane,
)

#: inline-report ceiling: the whole file is parsed into memory, a
#: host-heavy CPU trace can be far larger than this, and the recorder's
#: background report thread must not take that from the training host. The
#: command (`main`) has no cap: an explicit invocation is the user's own
#: time.
REPORT_MAX_TRACE_BYTES = 48 << 20


def trace_summary_text(trace_dir: str, top: int = 30, collapse: bool = True,
                       max_bytes: Optional[int] = None,
                       cost: Optional[dict] = None,
                       steps: Optional[int] = None) -> str:
    """Per-op table for ``trace_dir``, or a one-line reason it is
    unavailable — never raises (the recorder's report must always be
    writable, trace or no trace).

    With ``cost`` (a ``StepCost`` families dict — e.g. the run manifest's
    ``step_cost["families"]``) and the step count the trace covers, a
    per-family table with static FLOPs/bytes and achieved TFLOP/s is
    appended: the live twin of the PERF.md roofline tables, classified by
    the SAME ``op_family`` the cost model uses."""
    if max_bytes is not None:
        try:
            size = os.path.getsize(_find_xplane(trace_dir))
        except Exception as e:
            return f"(trace summary unavailable: {e})"
        if size > max_bytes:
            return (
                f"(trace is {size / 1e6:.0f} MB — past the inline "
                "summary ceiling; run `python -m pytorch_distributed_nn_tpu"
                f".observability.xplane {trace_dir}` or open it with "
                "TensorBoard)"
            )
    try:
        summary = summarize_xplane(trace_dir, top=top, collapse=collapse)
    except Exception as e:
        return f"(trace summary unavailable: {e})"
    if not summary:
        return ("(no device planes with XLA op events in the trace — "
                "CPU-only capture; open the raw trace with TensorBoard)")
    out = format_summary(summary)
    try:
        fams = family_summary(summary)
        out += "\n\nper family:\n" + format_family_summary(
            fams, cost=cost, steps=steps
        )
    except Exception:  # the op table must survive a family-table bug
        pass
    return out


# ---------------------------------------------------------------------------
# Incident report generation (flightrec bundles)
# ---------------------------------------------------------------------------

_RING_TAIL = 40  # ring records rendered into the report


def _fmt_ring_record(rec: dict) -> str:
    kind = rec.get("kind")
    if kind == "manifest":
        return f"manifest run={rec.get('run_id')} rank={rec.get('rank')}"
    if kind == "event":
        extra = {
            k: v for k, v in rec.items()
            if k not in ("kind", "type", "time", "mono", "step")
        }
        step = f" step={rec['step']}" if "step" in rec else ""
        return (f"event {rec.get('type')}{step} "
                f"{json.dumps(extra, default=str)[:160]}")
    parts = [f"step={rec.get('step')}"]
    for k in ("loss", "step_time", "data_time", "straggler_dropped"):
        if k in rec:
            try:
                parts.append(f"{k}={float(rec[k]):.4f}")
            except (TypeError, ValueError):
                parts.append(f"{k}={rec[k]}")
    return "step " + " ".join(parts)


def render_incident_report(bundle_dir: str,
                           trace_error: Optional[str] = None) -> str:
    """Markdown report for one incident bundle (pure file reading)."""
    def load(name):
        try:
            with open(os.path.join(bundle_dir, name)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    incident = load("incident.json")
    manifest = load("manifest.json")
    env = load("env.json")
    lines = [
        f"# Incident: {incident.get('kind', '?')} @ step "
        f"{incident.get('step', '?')}",
        "",
        f"- **reason**: {incident.get('reason', '?')}",
        f"- **run**: `{incident.get('run_id') or manifest.get('run_id')}` "
        f"(rank {manifest.get('rank', 0)}, host "
        f"{manifest.get('host', '?')})",
        f"- **triggered**: {time.strftime('%Y-%m-%d %H:%M:%S %Z', time.localtime(incident['triggered_time'])) if incident.get('triggered_time') else '?'}",
        f"- **capture window**: steps "
        f"{incident.get('capture_from_step', '?')}.."
        f"{incident.get('capture_until_step', '?')}",
        f"- **detector spec**: `{incident.get('spec', '?')}`",
    ]
    detail = incident.get("detail")
    if detail:
        lines.append(f"- **detail**: `{json.dumps(detail, default=str)}`")
    cfg = manifest.get("config") or {}
    if cfg:
        lines.append(
            f"- **config**: {cfg.get('network')}/{cfg.get('dataset')} "
            f"batch {cfg.get('batch_size')} · mesh "
            f"{manifest.get('mesh_shape')}"
        )
    lines += ["", "## Device trace", ""]
    trace_dir = os.path.join(bundle_dir, "trace")
    if trace_error:
        lines.append(f"(trace not captured: {trace_error})")
    elif not os.path.isdir(trace_dir):
        lines.append("(no trace directory in this bundle)")
    else:
        # efficiency columns: the run manifest's static step cost + the
        # capture window length make per-family achieved TFLOP/s derivable
        # right in the incident report (docs/observability.md)
        cost = (manifest.get("step_cost") or {}).get("families")
        steps = None
        try:
            lo = incident.get("capture_from_step")
            hi = incident.get("capture_until_step")
            if lo is not None and hi is not None and int(hi) > int(lo):
                steps = int(hi) - int(lo)
        except (TypeError, ValueError):
            pass
        lines.append("```")
        lines.append(trace_summary_text(
            trace_dir, max_bytes=REPORT_MAX_TRACE_BYTES,
            cost=cost, steps=steps,
        ))
        lines.append("```")
    ring = []
    try:
        with open(os.path.join(bundle_dir, "events.jsonl")) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        ring.append(json.loads(line))
                    except ValueError:
                        pass
    except OSError:
        pass
    lines += [
        "",
        f"## Event ring ({len(ring)} records; last {_RING_TAIL} shown, "
        "newest last)",
        "",
        "```",
    ]
    lines += [_fmt_ring_record(r) for r in ring[-_RING_TAIL:]]
    lines.append("```")
    lines += ["", "## Environment", ""]
    env_flags = (env.get("env") or {})
    if env_flags:
        lines.append("```")
        lines += [f"{k}={v}" for k, v in env_flags.items()]
        lines.append("```")
    lines.append(
        f"(full capture: `env.json`; jax {env.get('jax_version', '?')} on "
        f"{env.get('backend', '?')}, {env.get('device_count', '?')} "
        "device(s))"
    )
    lines.append("")
    return "\n".join(lines)


def write_incident_report(bundle_dir: str,
                          trace_error: Optional[str] = None) -> str:
    """Render and write ``report.md`` into the bundle; returns the path."""
    path = os.path.join(bundle_dir, "report.md")
    with open(path, "w") as f:
        f.write(render_incident_report(bundle_dir, trace_error=trace_error))
    return path


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    """Print a per-op device-time table from a jax.profiler trace dir.

    <trace_dir> is the directory passed to `--profile-dir` (or
    `jax.profiler.trace`), or an incident bundle's `trace/`; the tool
    finds the newest plugins/profile/*/*.xplane.pb under it. `--full`
    keeps full op names instead of collapsing fusions into families.
    Device time per step, idle share and exposed collective time are the
    benchmark's (`python3 -m benchmark.run ... --trace 1`), not this
    table's.
    """
    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("trace_dir")
    p.add_argument("--full", action="store_true",
                   help="full op names (no fusion-family collapsing)")
    p.add_argument("--top", type=int, default=30)
    args = p.parse_args(argv)

    summary = summarize_xplane(
        args.trace_dir, top=args.top, collapse=not args.full
    )
    if not summary:
        print("no device planes with XLA op events found", file=sys.stderr)
        return 1
    print(format_summary(summary))
    print("\nper family:")
    print(format_family_summary(family_summary(summary)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
