"""Step metrics — a thin shim over observability/.

Kept for API compatibility: ``MetricsLogger`` is the surface the trainer
(and downstream scripts) always used, but since the unified telemetry
layer landed it is a veneer over ``observability.core``: it still
appends one JSONL record per step, but the stream is a telemetry stream:
a run-manifest header record first, ``kind``-tagged records after
(observability/core.TelemetrySink). Passing an existing
:class:`~..observability.core.Telemetry` routes records into that run's
stream instead of opening a second file. Timing is
``observability/spans.py``'s ``span()``.
"""

from __future__ import annotations

from typing import Optional


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
