"""txtrace: per-transaction tracing, latency histograms, critical-path
attribution. See tracer.py for the recording model, export.py for the
Perfetto merge, report.py for the host/device breakdown."""

from .export import merge_by_tx, to_chrome_trace, write_chrome_trace
from .report import critical_path, format_line, merge_critical_paths
from .tracer import (
    LATENCY_BUCKETS,
    NULL_TRACER,
    SPAN_ADMISSION,
    SPAN_COMMIT,
    SPAN_DEVICE,
    SPAN_E2E,
    SPAN_GOSSIP_INGEST,
    SPAN_LOCK_WAIT,
    SPAN_ORDER,
    SPAN_PREP,
    SPAN_QUORUM,
    SPAN_SIGN,
    SPAN_TX_INGEST,
    SPAN_VOTE_INGEST,
    NullTracer,
    TraceConfig,
    TraceMetrics,
    Tracer,
    make_tracer,
)

__all__ = [
    "LATENCY_BUCKETS", "NULL_TRACER", "NullTracer", "TraceConfig",
    "TraceMetrics", "Tracer", "make_tracer",
    "SPAN_ADMISSION", "SPAN_COMMIT", "SPAN_DEVICE", "SPAN_E2E",
    "SPAN_GOSSIP_INGEST", "SPAN_LOCK_WAIT", "SPAN_ORDER",
    "SPAN_PREP", "SPAN_QUORUM", "SPAN_SIGN", "SPAN_TX_INGEST",
    "SPAN_VOTE_INGEST",
    "merge_by_tx", "to_chrome_trace", "write_chrome_trace",
    "critical_path", "format_line", "merge_critical_paths",
]
