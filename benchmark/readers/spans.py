"""Per-layer metrics from the program's own spans: device idle time
attributed to the span the host was in (``benchmark/spans.py``, traced
runs), and the wall clock of the step records.

Every reader returns nothing where its spans or fields are absent: a
program older than the span catalogue, a trace without device planes.
"""

from __future__ import annotations

import statistics

from benchmark import spans

NOTE = "idle_by_span"


def _idle(ctx):
    """The trace's idle partition, reduced once per run and kept in the
    line's ``notes`` (``None`` there: the trace has no spans)."""
    if NOTE not in ctx.notes:
        path = ctx.result.get("xplane")
        ctx.notes[NOTE] = spans.reduce(path) if path else None
    return ctx.notes[NOTE]


def _exposed(ctx, name: str):
    idle = _idle(ctx)
    return idle["ms_per_step"].get(name, 0.0) if idle else None


def wall_clock_samples_per_s(ctx):
    """Global batch over the mean ``wall_ms`` of the window's step
    records: the program's own wall clock, beside ``samples_per_s``."""
    w = ctx.result["window"]
    walls = [r["wall_ms"] for r in w.steps if r.get("wall_ms")]
    return 1000.0 * w.global_batch / statistics.fmean(walls) if walls else None


def input_exposed_ms_per_step(ctx):
    return _exposed(ctx, "input/produce")


def flush_exposed_ms_per_step(ctx):
    return _exposed(ctx, "train/flush_publish")


def ckpt_exposed_ms_per_save(ctx):
    """Idle under any ``ckpt/*`` span, on either thread, per save begun in
    the traced window; the split by span goes to the line's ``notes``."""
    idle = _idle(ctx)
    if not idle or not idle["saves"]:
        return None
    per_save = {name: ms * idle["steps"] / idle["saves"]
                for name, ms in idle["ms_per_step"].items()
                if name.startswith(spans.CKPT)}
    ctx.notes["ckpt_exposed_ms_per_save"] = per_save
    return sum(per_save.values())


def idle_unattributed_pct(ctx):
    idle = _idle(ctx)
    if not idle:
        return None
    total = idle["idle_ms_per_step"]
    return 100.0 * idle["ms_per_step"][spans.UNATTRIBUTED] / total if total else 0.0
