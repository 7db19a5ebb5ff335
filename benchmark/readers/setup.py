"""Per-layer metrics of set-up, from the program's own ``setup/*`` spans
and compile counters (``observability/spans.py`` ``SetupLog``,
``observability/compiles.py``): the ``setup`` events the trainer writes
at the end of its constructor and after each ``train()`` call's first
iteration, and the ``compile`` events of programs made outside every
``setup/*`` span. jax-free.

Every reader returns nothing where the stream holds no ``setup`` event: a
program older than the set-up spans. The split of the run's ``setup_s``
goes to the line's ``notes["setup"]``.
"""

from __future__ import annotations

NOTE = "setup"


def _events(ctx, etype: str) -> list:
    return [r for r in ctx.result.get("records", [])
            if r.get("kind") == "event" and r.get("type") == etype]


def _spans(ctx) -> list:
    """Every span record of every ``setup`` event, in the stream's order."""
    return [s for e in _events(ctx, "setup") for s in e.get("spans", [])]


def _first(ctx, name: str):
    return next((s for s in _spans(ctx) if s["name"] == name), None)


def _before_window(ctx):
    """(compile seconds, programs compiled, programs fetched) charged
    before the window's opening stamp: every ``setup`` event's spans and
    the ``compile`` events stamped before it. None without spans."""
    opened = ctx.result["window"].opened
    spans = [s for e in _events(ctx, "setup") if e["mono"] <= opened
             for s in e.get("spans", [])]
    if not spans:
        return None
    outside = [e for e in _events(ctx, "compile") if e["mono"] <= opened]
    seconds = (sum(sum(s["compile_s"].values()) for s in spans)
               + sum(sum(e["compile_s"].values()) for e in outside))
    compiled = (sum(s["programs"]["compiled"] for s in spans)
                + sum(e["source"] == "compiled" for e in outside))
    cached = (sum(s["programs"]["cached"] for s in spans)
              + sum(e["source"] == "cached" for e in outside))
    _note(ctx)
    return seconds, compiled, cached


def setup_init_s(ctx):
    s = _first(ctx, "setup/init")
    if s is None:
        return None
    _note(ctx)
    return s["seconds"]


def setup_first_step_s(ctx):
    """``setup/first_step`` of the first ``train()`` call: the one that
    traces, lowers and compiles or fetches the step."""
    s = _first(ctx, "setup/first_step")
    return s["seconds"] if s else None


def setup_compile_s(ctx):
    got = _before_window(ctx)
    return got[0] if got else None


def setup_programs_compiled(ctx):
    got = _before_window(ctx)
    return got[1] if got else None


def _note(ctx) -> None:
    """``notes["setup"]``, once a run: seconds and programs per span, the
    slowest programs, the compile events by where they fell, and what the
    spans leave of ``setup_s``."""
    if NOTE in ctx.notes:
        return
    events = _events(ctx, "setup")
    w = ctx.result["window"]
    spans = {}
    for e in events:
        for s in e.get("spans", []):
            key = s["name"]
            if key in spans:        # a later call's first iteration
                key = f"{key}@{e.get('step')}"
            spans[key] = {"s": s["seconds"],
                          "compile_s": sum(s["compile_s"].values()),
                          "fetch_s": s.get("fetch_s"), **s["programs"]}
    slowest = sorted((f for e in events for f in e.get("slowest", [])),
                     key=lambda f: f["seconds"], reverse=True)[:5]
    compiles = _events(ctx, "compile")
    inside = [e for e in compiles if w.opened < e["mono"] <= w.closed]
    ctx.notes[NOTE] = {
        "spans": spans,
        "slowest": slowest,
        "compile_events": {
            "before_window": sum(e["mono"] <= w.opened for e in compiles),
            "in_window": [{"step": e.get("step"), "fun_name": e["fun_name"],
                           "source": e["source"]} for e in inside],
            "after_window": sum(e["mono"] > w.closed for e in compiles),
        },
        "setup_s": _accounting(ctx, events),
    }


def _accounting(ctx, events):
    """``setup_s`` (process start to the window's opening stamp) cut at
    the spans: imports and backend (``phases_s``), the constructor's
    span, the first call's first iteration, the rest of the first call's
    warm-up windows, the second call up to the opening stamp. What is
    left is the driver's own work between them: importing the trainer
    module, the ``device_get`` of the initial weights, reading the stream
    between the calls."""
    setup_s = ctx.result["end_to_end"].get("setup_s")
    phases = ctx.result.get("phases_s") or {}
    w = ctx.result["window"]
    # (the step it began, the span) of each call's first iteration
    firsts = [(e.get("step"), s) for e in events for s in e.get("spans", [])
              if s["name"] == "setup/first_step"]
    init = next((s for e in events for s in e.get("spans", [])
                 if s["name"] == "setup/init"), None)
    if (setup_s is None or init is None or not firsts
            or phases.get("trainer_built") is None
            or phases.get("warm_up_to_window") is None):
        return None
    imports_backend = (setup_s - phases["trainer_built"]
                       - phases["warm_up_to_window"])
    t_ready = w.opened - setup_s + imports_backend
    call1 = firsts[0][1]
    out = {"total": setup_s, "imports_and_backend": imports_backend,
           "init": init["seconds"], "first_step": call1["seconds"]}
    parts = {"before_init": init["mono0"] - t_ready,
             "init_to_first_step": call1["mono0"] - init["mono1"]}
    closing = {r["step"]: r["mono"] for r in ctx.result.get("records", [])
               if r.get("kind") == "step"}
    later = [(step, s) for step, s in firsts[1:] if s["mono0"] < w.opened]
    last_warm = closing.get(later[0][0] - 1) if later else None
    if last_warm is not None:
        call2 = later[0][1]
        out["warm_up_windows"] = last_warm - call1["mono1"]
        parts["between_calls"] = call2["mono0"] - last_warm
        out["ramp"] = w.opened - call2["mono0"]
    else:
        out["warm_up_windows_and_ramp"] = w.opened - call1["mono1"]
    residual = setup_s - sum(v for k, v in out.items() if k != "total")
    out["residual"] = residual
    out["residual_pct"] = 100.0 * residual / setup_s if setup_s else None
    out["residual_parts"] = parts
    return out
