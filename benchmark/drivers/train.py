"""The training driver: one cell = ``Trainer(TrainConfig(...)).train()``
in this process, on the chips the cell names.

The loop that is measured is the program's own — loader, fused step,
metric flush, checkpointer — never an isolated jitted step. The program
is not edited and has no time-bounded stop, so the run is two calls of
``train()`` on one ``Trainer``:

  call 1  ``max_steps = warmup_windows x log_every``: compile (or cache
          fetch) and warm-up. Its last window gives the steps per second
          the second call is sized from.
  call 2  continues at that step (``start_step``, as a resume does) for
          one ramp window, the traced steps if ``--trace 1`` (the
          program's own ``profile_steps`` window, with its forced flush),
          and as many log windows as fit ``--seconds`` at the warm-up
          rate, plus one.

``window.measure`` then cuts the window out of the stream: from the
closing flush of the ramp (or of the first window clear of the trace) to
the last closing flush within ``--seconds`` of it.

The comparison with the plain reference (``benchmark/correct.py``) is the
benchmark's work, not the program's: it runs after the second call, on a
host copy of the weights the run began with (the step donates its state),
so it is in neither ``setup_s`` nor the window, and the runtime's memory
peaks are read before it.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import shutil
import time
from typing import Optional


def effective(cell, rehearse: bool):
    """(configuration, TrainConfig overrides, mix) as this run uses them:
    the configuration's file, then the mix's overrides, then — only under
    ``--rehearse`` — each one's ``rehearse`` block."""
    config, mix = dict(cell.config), dict(cell.mix)
    tc = {**config["train_config"], **mix.get("train_config", {})}
    if rehearse:
        for target in (config, mix):
            block = dict(target.pop("rehearse", {}))
            tc.update(block.pop("train_config", {}))
            target.update(block)
    if "trace_steps" in mix:
        config["trace_steps"] = mix["trace_steps"]
    return config, tc, mix


class CompileLog:
    """Mono stamps of every program jax asked its backend for (a compile
    or a persistent-cache fetch), and the cache's own hit/miss counts."""

    def __init__(self):
        self.stamps = []
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.stamps.append(time.monotonic())

    def _event(self, event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1


def _closing_stamp(records, step: int) -> Optional[float]:
    for r in reversed(records):
        if r.get("kind") == "step" and r.get("step") == step:
            return r["mono"]
    return None


def run(cell, *, seed: int, seconds: float, trace: bool, rehearse: bool,
        t0: float, t_ready: float) -> dict:
    """``t0``: process start; ``t_ready``: jax's backend is up (both
    ``time.monotonic()``, the clock the stream's ``mono`` stamps use)."""
    import jax

    from benchmark import correct, window
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    config, tc, mix = effective(cell, rehearse)
    chips = cell.chips
    batch = config["per_chip_batch"] * chips
    log_every = int(tc["log_every"])
    eval_freq = int(tc.get("eval_freq", 0))
    warm_windows = int(mix["warmup_windows"])
    if warm_windows < 2:
        raise ValueError("warmup_windows < 2: the first window compiles, "
                         "the rate needs one that does not")
    warm_steps = warm_windows * log_every

    work = os.path.join(cell.root, ".benchmark_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    stream = os.path.join(work, "stream.jsonl")
    profile_dir = os.path.join(work, "profile")

    compiles = CompileLog().install()
    trainer = Trainer(TrainConfig(
        **tc, batch_size=batch, num_workers=chips, seed=seed,
        max_steps=warm_steps, metrics_path=stream,
        train_dir=os.path.join(work, "train_dir"), profile_dir=profile_dir,
    ))
    t_init = time.monotonic()
    try:
        initial = jax.device_get(
            (trainer.state.params, trainer.state.batch_stats))
        trainer.train()                                   # call 1
        records = window.read_stream(stream)
        a = _closing_stamp(records, warm_steps - log_every)
        b = _closing_stamp(records, warm_steps)
        if a is None or b is None or b <= a:
            raise RuntimeError("warm-up left no two closing flushes")
        rate = log_every / (b - a)                        # steps per second

        trace_steps = int(config["trace_steps"]) if trace else 0
        if trace:
            # the program traces steps start+2 .. start+1+trace_steps and
            # stalls in stop_trace right after: measure from the first
            # log boundary beyond that
            stop = warm_steps + 1 + trace_steps
            first_step = (stop // log_every + 1) * log_every
        else:
            first_step = warm_steps + log_every           # one ramp window
        # a mix that saves measures whole save periods, from a save on
        period = math.lcm(log_every, eval_freq) if eval_freq else 0
        if period:
            first_step = -(-first_step // period) * period
        windows = int(seconds * rate / log_every) + 1
        trainer.start_step = warm_steps       # continue, as a resume does
        trainer.config.max_steps = first_step + windows * log_every
        trainer.config.profile_steps = trace_steps
        trainer.train()                                   # call 2
        t_done = time.monotonic()
        records = window.read_stream(stream)
        stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
        newest_ok = _verify_newest(trainer.config.train_dir) if eval_freq else None
        t_check = time.monotonic()
        check = correct.check(
            trainer, dataclasses.replace(cell, config=config), seed, *initial)
        t_checked = time.monotonic()
    finally:
        trainer.close()
        shutil.rmtree(os.path.join(work, "train_dir"), ignore_errors=True)

    w = window.measure(
        records, first_step=first_step, log_every=log_every,
        global_batch=batch, seconds=seconds, eval_freq=eval_freq,
        period=period, compiles=compiles.stamps,
    )
    xplane = None
    if trace:
        found = sorted(glob.glob(os.path.join(
            profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
        xplane = found[-1] if found else None
    setup_s = w.opened - t0 if w.opened else None
    fullest = max(stats, key=peak_bytes)
    return {
        "config": config,
        "global_batch": batch,
        "window": w,
        "records": records,       # the whole stream, for readers of events
        "check": check,
        "checkpoint_verified": newest_ok,
        "xplane": xplane,
        "memory_peak_bytes": peak_bytes(fullest),
        "memory_stats": fullest,
        "end_to_end": {
            "samples_per_s": w.samples_per_s,
            "setup_s": setup_s,
        },
        "correct": bool(check["ok"] and w.ok and newest_ok is not False),
        "phases_s": {
            "trainer_built": t_init - t_ready,
            "warm_up_to_window": (w.opened - t_init) if w.opened else None,
            "after_window": (t_done - w.closed) if w.closed else None,
            "reference_check": t_checked - t_check,
        },
        "cache": {"hits": compiles.cache_hits,
                  "misses": compiles.cache_misses},
    }


def peak_bytes(stats: dict) -> int:
    """Peak bytes one chip held: ``peak_bytes_in_use`` + ``peak_bytes_reserved``.

    On this runtime ``peak_bytes_in_use`` counts live arrays only (weights,
    optimizer state, resident data, batches); what the programs need for
    their temporaries is set aside separately and counted under
    ``peak_bytes_reserved``. The two do not overlap: after a run the
    largest free block is ``bytes_limit`` less their sum, to 1-4 % (a block
    can only be smaller than the free total), and the reserved peak is the
    temporaries the compiler gives the step, to 1 % (``compile_for_chip.py``
    in the sandbox: 7.17 / 8.89 / 8.85 GB; my chip runs, PR 22: 7.15 /
    8.82 / 8.80 GB). The result line's ``memory_stats`` has both counters
    beside the sum; PERF.md section 4 has the table."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def _verify_newest(train_dir: str) -> bool:
    """The program's own integrity check on the newest published save
    (``train()`` has drained the writer by the time it returns)."""
    from pytorch_distributed_nn_tpu.training import checkpoint as ckpt

    step = ckpt.latest_step(train_dir)
    if step is None:
        return False
    return bool(ckpt.verify_checkpoint(ckpt.checkpoint_path(train_dir, step))[0])
