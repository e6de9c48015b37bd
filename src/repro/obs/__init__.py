"""Observability layer — guard flight recorder, event log, spans
(DESIGN.md §12).

In-trace: :class:`TelemetryConfig` gates a scan-carried telemetry pytree
(per-worker martingale deviations vs thresholds, alive deltas, ξ norm,
resync drift, adversary feedback) written into an on-device ring buffer;
off-state is trace-identical to a build without this package.

Host: :class:`EventLog` (structured JSONL + Perfetto/chrome-trace export),
:func:`trace_span` profiler spans, the train step's named scopes
(:data:`STEP_SCOPES`), provenance meta, and the measured-vs-roofline
comparator.  Rendered by ``scripts/render_trace.py``.
"""
from repro.obs.events import EventLog, write_chrome_trace
from repro.obs.provenance import provenance_meta
from repro.obs.roofline_compare import roofline_rows, spans_by_name
from repro.obs.spans import STEP_SCOPES, trace_span
from repro.obs.telemetry import (
    FRAME_SCHEMA,
    PER_WORKER_KEYS,
    SCALAR_KEYS,
    Telemetry,
    TelemetryConfig,
    TelemetryRing,
    baseline_frame,
    empty_frame,
    guard_frame,
    ring_init,
    ring_push,
    ring_read,
    telemetry_on,
)

__all__ = [
    "EventLog",
    "FRAME_SCHEMA",
    "PER_WORKER_KEYS",
    "SCALAR_KEYS",
    "STEP_SCOPES",
    "Telemetry",
    "TelemetryConfig",
    "TelemetryRing",
    "baseline_frame",
    "empty_frame",
    "guard_frame",
    "provenance_meta",
    "ring_init",
    "ring_push",
    "ring_read",
    "roofline_rows",
    "spans_by_name",
    "telemetry_on",
    "trace_span",
    "write_chrome_trace",
]
