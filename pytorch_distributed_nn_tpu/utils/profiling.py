"""Offline summary of a ``jax.profiler`` device trace: the operator's table.

The reference's only "profiler" was wall-clock phase logging inside the
worker loop (reference: src/distributed_worker.py:146-173) consumed by
regex in notebooks. Here a captured ``.xplane.pb`` (``--profile N``, or a
flight-recorder incident bundle) is reduced by ``summarize_xplane`` to a
per-op / per-family device-time table, without a TensorBoard server:
``python -m pytorch_distributed_nn_tpu.observability.xplane <trace_dir>``,
an incident's ``report.md``, and ``analysis/calibration.fit_from_trace``.

This is the one module of the package that opens a trace, with
``jax.profiler.ProfileData`` — nothing but jax, imported only when a trace
is opened. It defines no rate and no per-step time: how fast the system is
comes from ``python3 -m benchmark.run``, whose own reader (``benchmark/``,
independent on purpose) the fixture test in ``tests/test_tools.py`` pins
this one against.
"""

from __future__ import annotations

import collections
import glob
import os
from dataclasses import dataclass
from typing import Dict, List, Optional


@dataclass
class OpTime:
    """Aggregated device time for one XLA op (or op family)."""

    name: str
    total_ms: float
    count: int
    pct: float


# ---------------------------------------------------------------------------
# Op-family classification — the ONE implementation shared by the xplane
# summarizer (observability/xplane.py) and the static cost model
# (analysis/costmodel.py), so a trace row and a cost-model row can never
# disagree about which PERF.md family an op belongs to. Lives here (not in
# analysis/) because this module stays importable without jax or the
# analysis package — the `obs incidents` report path must never pay a
# backend import.
# ---------------------------------------------------------------------------

#: the canonical families of the PERF.md roofline tables
FAMILIES = (
    "convert_reduce_fusion",  # forward compute: convs/GEMMs fused with
    #                           stat reduces + dtype converts
    "multiply_add_fusion",    # backward compute: wgrad GEMMs/convs fused
    #                           with the optimizer multiply-add
    "elementwise",            # bandwidth-bound fusions: normalize/apply,
    #                           residual adds, activation backward
    "other",                  # copies, collectives, host ops, the tail
)

_ELEMENTWISE_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "log", "tanh", "logistic", "rsqrt", "sqrt", "power",
    "negate", "abs", "sign", "floor", "ceil", "compare", "select", "and",
    "or", "not", "xor", "clamp", "convert", "reduce", "broadcast", "iota",
))


def op_family(name: str) -> str:
    """Map an op/fusion name (trace event or HLO instruction) to a family.

    XLA names fusions after their content on every backend this repo
    targets (``%convert_reduce_fusion.3``, ``%multiply_add_fusion``,
    ``broadcast_add_fusion.1`` ...), so the name alone carries the family.
    Unrecognized names — copies, collectives, custom calls, standalone
    convs/dots — land in ``other``; the cost model refines flop-bearing
    standalone ops by their forward/backward metadata separately
    (analysis/costmodel.py), which a trace row cannot.
    """
    n = str(name).lstrip("%").split(" ")[0]
    base = n.split(".")[0].lower()
    if "convert_reduce" in base:
        return "convert_reduce_fusion"
    if "multiply_add" in base or "convolution_add" in base:
        return "multiply_add_fusion"
    if base.endswith("fusion") or base in _ELEMENTWISE_OPS:
        return "elementwise"
    return "other"


def family_summary(summary: Dict[str, List[OpTime]]) -> Dict[str, dict]:
    """Collapse a per-op device-time table into the canonical families.

    Input is ``summarize_xplane`` output; the result maps every family in
    :data:`FAMILIES` (always all four, zeros included, so consumers can
    tabulate without existence checks) to ``{total_ms, count, pct}``
    aggregated across ALL device planes.
    """
    out = {f: {"total_ms": 0.0, "count": 0, "pct": 0.0} for f in FAMILIES}
    total = 0.0
    for rows in summary.values():
        for r in rows:
            fam = op_family(r.name)
            out[fam]["total_ms"] += r.total_ms
            out[fam]["count"] += r.count
            total += r.total_ms
    if total > 0:
        for rec in out.values():
            rec["pct"] = 100.0 * rec["total_ms"] / total
            rec["total_ms"] = round(rec["total_ms"], 3)
            rec["pct"] = round(rec["pct"], 1)
    return out


def format_family_summary(
    families: Dict[str, dict],
    cost: Optional[Dict[str, dict]] = None,
    steps: Optional[int] = None,
) -> str:
    """Render the per-family table; with a static cost (``StepCost``
    families dict: ``{family: {"flops": .., "hbm_bytes": ..}}`` per step)
    and a step count, the FLOPs/bytes and achieved-TFLOP/s columns become
    derivable and are appended — the live twin of the hand-built PERF.md
    roofline tables.
    """
    derivable = bool(cost) and bool(steps)
    header = f"  {'family':<24} {'ms':>10} {'%':>6} {'n':>7}"
    if derivable:
        header += f" {'GFLOP/step':>11} {'MB/step':>9} {'TFLOP/s':>9}"
    lines = [header]
    for fam in FAMILIES:
        rec = families.get(fam) or {}
        ms = float(rec.get("total_ms", 0.0))
        line = (f"  {fam:<24} {ms:>10.3f} {rec.get('pct', 0.0):>6.1f} "
                f"{rec.get('count', 0):>7}")
        if derivable:
            c = (cost or {}).get(fam) or {}
            flops = float(c.get("flops", 0.0))
            hbm = float(c.get("hbm_bytes", 0.0))
            ach = (
                flops * steps / (ms / 1000.0) / 1e12 if ms > 0 and flops
                else 0.0
            )
            line += (f" {flops / 1e9:>11.3f} {hbm / 1e6:>9.2f} "
                     f"{ach:>9.2f}")
        lines.append(line)
    return "\n".join(lines)


def _find_xplane(trace_dir: str) -> str:
    """The newest trace under ``trace_dir`` (the directory given to
    ``--profile-dir`` / ``jax.profiler.trace``), or ``trace_dir`` itself
    when it already is a trace file."""
    if os.path.isfile(trace_dir):
        return trace_dir
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    )
    if not paths:
        raise FileNotFoundError(
            f"no .xplane.pb under {trace_dir}/plugins/profile/ — "
            "was a trace captured here?"
        )
    return paths[-1]


def _load_xplane(path: str):
    """``jax.profiler.ProfileData`` of an ``.xplane.pb`` as the profiler
    wrote it, or of a ``.textproto`` of the same message."""
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def summarize_xplane(
    trace_dir: str,
    top: int = 30,
    collapse: bool = True,
) -> Dict[str, List[OpTime]]:
    """Per-op device-time table from the latest trace under ``trace_dir``.

    Returns {device_plane_name: [OpTime, ...]} sorted by total time.
    ``collapse=True`` groups ops by family (fusion name prefix before the
    first '.'), which is the right granularity for "where does the step
    go"; ``collapse=False`` keeps full op names.
    """
    data = _load_xplane(_find_xplane(trace_dir))
    out: Dict[str, List[OpTime]] = {}
    for plane in data.planes:
        if "TPU" not in plane.name and "GPU" not in plane.name:
            continue
        tot: collections.Counter = collections.Counter()
        cnt: collections.Counter = collections.Counter()
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                key = ev.name.split(".")[0] if collapse else ev.name
                tot[key] += ev.duration_ns / 1e6  # ms
                cnt[key] += 1
        if not tot:
            continue
        total = sum(tot.values())
        rows = [
            OpTime(name=k, total_ms=v, count=cnt[k], pct=100.0 * v / total)
            for k, v in tot.most_common(top)
        ]
        # Truncation must not silently drop device time: fold the tail
        # into one synthetic row so every consumer's sum equals the true
        # total.
        if len(tot) > top:
            shown = sum(r.total_ms for r in rows)
            shown_n = sum(r.count for r in rows)
            rows.append(OpTime(
                name=f"(other {len(tot) - top} ops)",
                total_ms=total - shown,
                count=sum(cnt.values()) - shown_n,
                pct=100.0 * (total - shown) / total,
            ))
        out[plane.name] = rows
    return out


def format_summary(summary: Dict[str, List[OpTime]]) -> str:
    lines = []
    for plane, ops in summary.items():
        total = sum(o.total_ms for o in ops)
        lines.append(f"== {plane}: {total:.2f} ms device op time ==")
        for o in ops:
            lines.append(
                f"  {o.total_ms:9.3f} ms {o.pct:5.1f}% n={o.count:<5} "
                f"{o.name[:110]}"
            )
    return "\n".join(lines)
