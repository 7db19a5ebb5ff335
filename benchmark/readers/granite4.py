"""Per-layer metrics of the state-space scan (configuration
``granite4_h_micro_pp4``): the roofline share of the ``ssd`` Mosaic calls
in the trace and their device time a step, with the program's scan
counters from the window's step records in the line's notes. jax-free.

Every reader returns nothing where what it reads is absent: a program
without the scan (the calls are not in its trace), a configuration without
the kernel, an untraced run.
"""

from __future__ import annotations

import statistics

from benchmark import manifest

SSD = "ssd_scan"
COUNTED = ("ssd_calls", "ssd_chunks", "ssd_state_absmax")


def ssd_scan_roofline(ctx):
    """100 x least time / device time over the ``ssd`` calls, per kind
    (fwd, bwd) in the line's ``notes["ssd_scan"]``, against the chunked
    algorithm's count (``flops/granite4_kernels.py``)."""
    config = ctx.cell.config
    spec = config.get("kernels", {}).get(SSD)
    rel = config.get("kernel_costs")
    if not spec or not rel:
        return None
    m = config["model"]
    costs = manifest.load_module(ctx.cell.root, rel).ssd(
        ctx.result["global_batch"] // ctx.cell.chips,
        config["tokens_per_sample"], m["mamba_n_heads"], m["mamba_d_head"],
        m["mamba_d_state"], m["mamba_chunk_size"], m["mamba_n_groups"],
        spec["itemsize"])
    return manifest.load_module(
        ctx.cell.root, "benchmark/readers/kernels.py")._share(ctx, SSD, costs)


def ssd_scan_ms_per_step(ctx):
    """Device time of the ``ssd`` calls per traced step; their share of
    ``device_ms_per_step``, their calls a step and the program's scan
    counters in the line's ``notes["ssd_scan_time"]``."""
    if not ctx.trace or not ctx.trace["steps"]:
        return None
    rows = [r for r in ctx.trace["kernels"] if r["family"] == SSD]
    if not rows:
        return None
    steps = ctx.trace["steps"]
    ms = 1e3 * sum(r["seconds"] for r in rows) / steps
    busy_ms = 1e3 * ctx.trace["busy_s"] / steps
    note = {
        "share_of_device_ms_pct": 100.0 * ms / busy_ms if busy_ms else None,
        "calls_per_step": {r["kind"]: r["calls"] / steps for r in rows},
    }
    counted = [r for r in ctx.result["window"].steps if COUNTED[0] in r]
    if counted:
        note["counters"] = {k: statistics.fmean(r[k] for r in counted)
                            for k in COUNTED if k in counted[0]}
        note["counters"]["ssd_state_absmax_max"] = max(
            r.get("ssd_state_absmax", 0.0) for r in counted)
    ctx.notes["ssd_scan_time"] = note
    return ms
