"""experiments/ — resumable multi-trial sweep orchestration.

The reference system's layer-5 tooling was an lr grid-search harness that
launched a 17-process mpirun per candidate and regex-parsed worker logs
(reference: src/tune.sh + src/tiny_tuning_parser.py). This package is that
layer grown up on top of everything the repo already has:

- :mod:`.spec`      — grid/random sweep specs over ``TrainConfig`` fields
  (compact flag grammar in the :class:`~..resilience.faults.FaultPlan`
  style), per-trial seeds derived as ``SeedSequence((sweep_seed, index))``.
- :mod:`.journal`   — the crash-safe append-only ``sweep.jsonl`` journal:
  manifest-first, torn-tail-tolerant (the observability stream contract),
  folded back into per-trial state for ``--resume``.
- :mod:`.scheduler` — full-grid baseline plus an ASHA-style successive-
  halving rung scheduler; promotions are pure functions of the journal.
- :mod:`.runner`    — N trials as spawned subprocesses (one process per
  trial, never a shared interpreter) under a bounded worker pool,
  per-trial timeout + retry-with-backoff, every trial a
  ``--supervise``-style telemetry run.
- :mod:`.report`    — ranked leaderboard (trailing loss / step rate / MFU
  pulled from the trial telemetry streams, never from logs).

CLI surface: ``cli sweep run/status/report/resume`` (+ ``--selftest``);
``cli tune`` / :func:`~..tuning.lr_sweep` are now thin shims over this
runner. See docs/experiments.md.
"""

from pytorch_distributed_nn_tpu.experiments.journal import (  # noqa: F401
    SWEEP_BASENAME,
    load_journal,
    trial_dir,
)
from pytorch_distributed_nn_tpu.experiments.report import (  # noqa: F401
    leaderboard,
    render_leaderboard,
)
from pytorch_distributed_nn_tpu.experiments.runner import (  # noqa: F401
    RunnerConfig,
    SweepInterrupted,
    SweepRunner,
)
from pytorch_distributed_nn_tpu.experiments.scheduler import (  # noqa: F401
    Rung,
    asha_rungs,
    grid_rungs,
    make_rungs,
    planned_steps,
    promote,
)
from pytorch_distributed_nn_tpu.experiments.spec import (  # noqa: F401
    DEFAULT_SPEC,
    SweepSpec,
    Trial,
    trial_seed,
)
