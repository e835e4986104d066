"""repro.telemetry — in-loop event tracing, streaming metrics and
run provenance.

Layers (all opt-in; disabled tracing lowers onto the unchanged event
loops bitwise — see docs/observability.md):

- :mod:`repro.telemetry.rail` — the in-loop trace rail: record
  layout, host sink, ``collect()`` scope, ordered-callback flush.
- :mod:`repro.telemetry.spans` — per-request span reassembly and the
  per-cell :class:`TraceRun` container attached to ``ResultSet``.
- :mod:`repro.telemetry.perfetto` — Chrome/Perfetto ``trace_event``
  JSON export and schema validation.
- :mod:`repro.telemetry.metrics` — per-bin per-node time series
  (queue depth, warm occupancy, utilization, SLO attainment,
  goodput) with CSV and Prometheus exporters.
- :mod:`repro.telemetry.profiling` — run-provenance metadata.
"""
from repro.telemetry.rail import (TraceKind, TraceSink, collect,
                                  merge_events)
from repro.telemetry.spans import Span, TraceRun, assemble_spans
from repro.telemetry.perfetto import (events_to_trace, save_trace,
                                      validate_trace)
from repro.telemetry.metrics import (events_summary, timeline,
                                     timeline_to_csv, to_prometheus)
from repro.telemetry.profiling import provenance, spec_hash

__all__ = [
    "TraceKind", "TraceSink", "collect", "merge_events",
    "Span", "TraceRun", "assemble_spans",
    "events_to_trace", "save_trace", "validate_trace",
    "events_summary", "timeline", "timeline_to_csv", "to_prometheus",
    "provenance", "spec_hash",
]
