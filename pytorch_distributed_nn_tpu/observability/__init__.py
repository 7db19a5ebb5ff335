"""observability/: the unified telemetry layer.

One coherent, queryable telemetry system replacing the five uncorrelated
streams the repo had grown (step JSONL, heartbeat.json, ad-hoc retry/
straggler dicts, xplane traces, bare ``logger.info`` lines):

- ``core``       — event bus + metric registry + crash-safe JSONL sink
                   with a run-manifest header record (the producer API).
- ``promexport`` — Prometheus textfile exposition + format validator
                   (written on every supervisor heartbeat tick).
- ``reader``     — stream parsing, run summaries, regression compare,
                   registry replay, cross-rank stream merge with
                   clock-skew alignment (the consumer API).
- ``detect``     — anomaly detectors over the live bus (EWMA step-time
                   regression, stall, straggler/nonfinite bursts,
                   checkpoint-stall breach, SLO burn) + the
                   ``--flightrec`` spec grammar.
- ``flightrec``  — the flight recorder: detector triggers open incident
                   bundles (profiler trace window, event ring, manifest,
                   env, generated report) under ``<train_dir>/incidents``.
- ``tracing``    — serving request-lifecycle tracing: request ids, the
                   admit/queue/batch_form/pad/infer/respond span
                   catalogue, waterfall rendering, slowest-request
                   attribution (schema v2).
- ``slo``        — SLO objectives: spec grammar, multi-window burn-rate
                   evaluation over the live bus AND offline streams,
                   error-budget gauges, edge-triggered breach events.
- ``xplane``     — the per-op device-time table of a trace (``python -m
                   ...observability.xplane``) + incident report generation.
- ``obs_cli``    — the ``cli obs`` command family: summary / tail /
                   compare [--by-version] / trace / slo / export /
                   incidents (+ ``summary --selftest`` and
                   ``slo --selftest`` for CI).

See docs/observability.md for the record schema, the event catalogue,
the flight-recorder trigger grammar and the Prometheus scrape recipe.
"""

from pytorch_distributed_nn_tpu.observability.core import (
    DEFAULT_BUCKETS,
    EVENT_TYPES,
    SCHEMA_VERSION,
    STREAM_BASENAME,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    Telemetry,
    TelemetrySink,
    get_telemetry,
    install,
    run_manifest,
    stream_basename,
    uninstall,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "EVENT_TYPES",
    "SCHEMA_VERSION",
    "STREAM_BASENAME",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "Telemetry",
    "TelemetrySink",
    "get_telemetry",
    "install",
    "run_manifest",
    "stream_basename",
    "uninstall",
]
