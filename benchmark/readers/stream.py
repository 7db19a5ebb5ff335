"""Per-layer metrics read from the run's telemetry stream (the program's
own step records and events) over the benchmark's window. jax-free.

Every reader takes the run's context (``benchmark/run.py``) and returns a
number, or nothing when there is nothing to read.
"""

from __future__ import annotations

import statistics

from benchmark import window as windows


def input_wait_ms_per_step(ctx):
    """Mean of the loader's own per-step blocked time."""
    waits = [r["input_wait_ms"] for r in ctx.result["window"].steps
             if "input_wait_ms" in r]
    return statistics.fmean(waits) if waits else None


def trainer_clock_samples_per_s(ctx):
    """Throughput as the trainer's own clock tells it: global batch over
    its ``step_time``, which leaves out the data phase and restarts after
    every periodic save — beside ``samples_per_s`` it shows what that
    clock cannot see."""
    w = ctx.result["window"]
    times = [r["step_time"] for r in w.steps if r.get("step_time")]
    return w.global_batch / statistics.fmean(times) if times else None


def ckpt_stall_ms_per_save(ctx):
    """Mean ``stall_ms`` of the window's ``checkpoint_write`` events: how
    long the program says the loop was blocked per save."""
    stalls = [e["stall_ms"] for e in ctx.result["window"].saves
              if "stall_ms" in e]
    return statistics.fmean(stalls) if stalls else None


def ckpt_cost_ms_per_save(ctx):
    """What a save costs on the benchmark's clock: the wall time of every
    log window beyond the median wall of the windows in which no save
    began, summed and divided by the saves begun. Nothing to read where
    every log window begins a save (``eval_freq`` = ``log_every``)."""
    w = ctx.result["window"]
    free = windows.walls_without_a_save(w)
    if not w.saves_started or not free:
        return None
    base = statistics.median(free)
    return 1000.0 * sum(wall - base for wall in w.walls) / len(w.saves_started)
