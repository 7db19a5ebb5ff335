"""The measured window, from the telemetry stream's own stamps. jax-free.

The trainer flushes once per ``log_every`` steps: a blocking
``jax.device_get`` of the window's metrics, then one ``step`` record per
step, each stamped with ``time.monotonic()`` as it is written. The stamp
of a window's last record is therefore a host clock read right after a
real fetch: the steps before it have run. That stamp is the benchmark's
clock. Unlike the trainer's own ``step_time`` it subtracts nothing: data
waits, the flush itself, checkpoint stalls and whatever the writer thread
costs the dispatch thread are all between two stamps.

    opening stamp   the closing flush of step ``first_step`` (end of warm-up)
    closing stamps  every ``log_every`` steps after it, while they fall
                    within ``seconds`` of the opening stamp
    wall            last closing stamp - opening stamp
    samples         (last step - first step) x global batch
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, Iterable, List, Optional, Sequence

MIN_WINDOWS = 5


def _list():
    return dataclasses.field(default_factory=list)


def read_stream(path: str) -> List[dict]:
    """The run's JSONL stream; a torn last line is dropped, not an error."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                break
    return records


@dataclasses.dataclass
class Window:
    first_step: int
    last_step: int
    log_every: int
    global_batch: int
    opened: float = 0.0           # mono stamp
    closed: float = 0.0           # mono stamp
    walls: List[float] = _list()  # seconds, one per log window
    steps: List[dict] = _list()   # the step records inside, in order
    saves_started: List[int] = _list()  # steps after which a save began
    saves: List[dict] = _list()   # their checkpoint_write events
    problems: List[str] = _list()   # why the window is not a valid one

    @property
    def n_steps(self) -> int:
        return self.last_step - self.first_step

    @property
    def wall_s(self) -> float:
        return self.closed - self.opened

    @property
    def samples(self) -> int:
        return self.n_steps * self.global_batch

    @property
    def samples_per_s(self) -> Optional[float]:
        return self.samples / self.wall_s if self.wall_s > 0 else None

    @property
    def failed_steps(self) -> int:
        return sum(1 for r in self.steps if not step_ok(r))

    @property
    def failed_saves(self) -> int:
        done = {e.get("step") for e in self.saves}
        return sum(1 for s in self.saves_started if s not in done)

    @property
    def attempted(self) -> int:
        return self.n_steps + len(self.saves_started)

    @property
    def failed(self) -> int:
        return self.failed_steps + self.failed_saves

    @property
    def ok(self) -> bool:
        return not self.problems and self.failed == 0


def step_ok(record: dict) -> bool:
    loss = record.get("loss")
    return (
        isinstance(loss, (int, float)) and math.isfinite(loss)
        and not record.get("skipped_nonfinite")
    )


def measure(
    records: Iterable[dict],
    *,
    first_step: int,
    log_every: int,
    global_batch: int,
    seconds: float,
    eval_freq: int = 0,
    period: int = 0,
    compiles: Sequence[float] = (),
) -> Window:
    """Cut the window out of ``records`` (see the module docstring).

    With ``period`` (steps), the window closes on the last closing flush
    that completes a whole number of periods: a mix that does something
    every so many steps (a save) then measures the same share of it in
    every run, however many log windows happened to fit.

    ``compiles`` are mono stamps at which jax asked its backend for a
    program (compile or cache fetch): one inside the window makes it
    invalid, as does a missing or repeated step record, fewer than
    ``MIN_WINDOWS`` log windows, or a save that began in the window and
    never published.
    """
    by_step: Dict[int, List[dict]] = {}
    saves_by_step: Dict[int, dict] = {}
    for r in records:
        if r.get("kind") == "step" and "step" in r:
            by_step.setdefault(int(r["step"]), []).append(r)
        elif r.get("kind") == "event" and r.get("type") == "checkpoint_write":
            saves_by_step[int(r["step"])] = r
    problems: List[str] = []
    if first_step not in by_step:
        return Window(first_step, first_step, log_every, global_batch, problems=[
            f"no record of step {first_step}: warm-up never ended"])
    opened = by_step[first_step][-1]["mono"]
    stamps = [opened]
    step = first_step
    while True:
        nxt = step + log_every
        if nxt not in by_step:
            # a later flush that did land means this one went missing
            if any(s > nxt for s in by_step):
                problems.append(f"no closing flush at step {nxt}")
            break
        t = by_step[nxt][-1]["mono"]
        if t - opened > seconds:
            break
        stamps.append(t)
        step = nxt
    if period:
        whole = (step - first_step) // period * period
        stamps = stamps[:whole // log_every + 1]
        step = first_step + whole
    last_step = step
    inside = []
    for s in range(first_step + 1, last_step + 1):
        got = by_step.get(s, [])
        if len(got) != 1:
            problems.append(f"step {s} has {len(got)} records")
        inside.extend(got[:1])
    walls = [b - a for a, b in zip(stamps, stamps[1:])]
    if len(walls) < MIN_WINDOWS:
        problems.append(
            f"{len(walls)} log windows closed inside {seconds} s, "
            f"want {MIN_WINDOWS}")
    closed = stamps[-1]
    n_compiles = sum(1 for t in compiles if opened < t <= closed)
    if n_compiles:
        problems.append(f"{n_compiles} compilations inside the window")
    started = []
    if eval_freq:
        # the save of step s begins right after the flush that closes s
        started = [s for s in range(first_step, last_step)
                   if s % eval_freq == 0 and s > 0]
    saves = [saves_by_step[s] for s in started if s in saves_by_step]
    return Window(first_step, last_step, log_every, global_batch, opened,
                  closed, walls, inside, started, saves, problems)


def walls_without_a_save(w: Window) -> List[float]:
    """Walls of the log windows in which no save began (a save begins
    right after the flush that opens the window it falls in). Where the
    writer thread is still busy does not matter: in the runs on the chip
    the tail of a write that reached into such a window left its wall
    where the others were, to 0.03 % (PERF.md) — what a save costs the
    step loop, it costs in the window it begins in."""
    began = set(w.saves_started)
    return [wall for k, wall in enumerate(w.walls)
            if w.first_step + k * w.log_every not in began]
