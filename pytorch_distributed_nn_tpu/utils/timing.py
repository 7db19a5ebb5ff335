"""Per-phase timing + step metrics — now a thin shim over observability/.

Kept for API compatibility: ``PhaseTimer`` and ``MetricsLogger`` are the
surface the trainer (and downstream scripts) always used, but since the
unified telemetry layer landed they are veneers over
``observability.core``:

- :class:`PhaseTimer` still accumulates named wall-clock phases per
  iteration (reference: src/distributed_worker.py:146-173 — fetch-weights /
  forward / backward / comm). Each phase is a span
  (``observability/spans.py``): an event in a profiler trace while one is
  being collected, and an observation of the
  ``phase_seconds{phase=...}`` histogram, so phases show up in the
  Prometheus exposition without a second timing source.
- :class:`MetricsLogger` still appends one JSONL record per step, but the
  stream is now a telemetry stream: a run-manifest header record first,
  ``kind``-tagged records after (observability/core.TelemetrySink). Passing
  an existing :class:`~..observability.core.Telemetry` routes records into
  that run's stream instead of opening a second file.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, Optional

from pytorch_distributed_nn_tpu.observability.spans import Span


class PhaseTimer:
    """Accumulates named wall-clock phases for one iteration.

    ``registry`` receives the ``phase_seconds`` observations; without one
    they go to the installed telemetry's."""

    def __init__(self, registry=None):
        self.durations: Dict[str, float] = {}
        self._registry = registry

    @contextmanager
    def phase(self, name: str):
        s = Span(name, self._registry)
        try:
            with s:
                yield
        finally:
            self.durations[name] = self.durations.get(name, 0.0) + s.seconds

    def reset(self):
        self.durations = {}


class MetricsLogger:
    """Append-only JSONL metrics sink (one record per step).

    ``MetricsLogger(path)`` — legacy standalone mode: opens its own
    telemetry stream at ``path`` (manifest header + ``kind: "step"``
    records; ``analysis.run_metrics.load_metrics`` reads both the old and
    the new format). ``MetricsLogger(telemetry=t)`` — shim mode: records
    go into ``t``'s stream and registry; the caller owns ``t``'s lifetime.
    """

    def __init__(self, path: Optional[str] = None, telemetry=None):
        from pytorch_distributed_nn_tpu.observability.core import Telemetry

        if telemetry is not None:
            self._telemetry = telemetry
            self._owned = False
        elif path:
            self._telemetry = Telemetry.for_run(path)
            self._owned = True
        else:
            self._telemetry = None
            self._owned = False

    def log(self, record: dict):
        if self._telemetry is not None:
            self._telemetry.log_step(record)

    def flush(self, fsync: bool = False):
        if self._telemetry is not None:
            self._telemetry.flush(fsync=fsync)

    def close(self):
        if self._telemetry is not None and self._owned:
            self._telemetry.close()
        self._telemetry = None
