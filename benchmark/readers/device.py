"""Per-layer metrics of the train step and the device, from the traced
window's reduction (``benchmark/trace.py``) and the runtime's counters.
jax-free: they read the summary, not the trace."""

from __future__ import annotations


def device_ms_per_step(ctx):
    """Union of the intervals in which an operation ran on a chip, over
    the traced steps, mean of the chips."""
    t = ctx.trace
    if not t or not t["steps"]:
        return None
    return 1000.0 * t["busy_s"] / t["steps"]


def mfu_device(ctx):
    """Model FLOPs of one step over (device-busy seconds of one step x
    chips x peak): what the chip achieves while it is working."""
    busy_ms = device_ms_per_step(ctx)
    if not busy_ms:
        return None
    flops = ctx.flops_per_sample * ctx.result["global_batch"]
    peak = ctx.peak["bf16_flops_per_s"] * ctx.cell.chips
    return 100.0 * flops / (busy_ms / 1000.0) / peak


def host_ms_per_step(ctx):
    """Wall time of a step on the benchmark's clock (measured window)
    less the device-busy time of a step (traced window): what the loop
    around the step costs — dispatch, flush, loader, saves."""
    busy_ms = device_ms_per_step(ctx)
    w = ctx.result["window"]
    if busy_ms is None or not w.n_steps:
        return None
    return 1000.0 * w.wall_s / w.n_steps - busy_ms


def peak_hbm_gib(ctx):
    """``peak_bytes_in_use`` + ``peak_bytes_reserved`` of the fullest chip
    (live arrays + the programs' temporaries; ``drivers/train.py``)."""
    peak = ctx.result["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
