"""Coral observability layer: shared percentile semantics,
per-request SLO latency records, structured control-plane tracing and
the serving engine's host spans.

Everything in this package is observation-only — importing or enabling
it never changes a simulation outcome (the batched-vs-oracle gauntlet
runs with it on).
"""
from repro.obs.percentiles import (percentile, percentiles,
                                   weighted_percentile,
                                   weighted_percentiles)
from repro.obs.reqlog import (QS, RequestLog, SLOReport, SLOTargets)
from repro.obs.spans import MAX_SPANS, Span, SpanLog
from repro.obs.trace import TRACE_SCHEMA, TraceError, TraceLog, \
    validate_record

__all__ = [
    "percentile", "percentiles", "weighted_percentile",
    "weighted_percentiles", "QS", "RequestLog", "SLOReport",
    "SLOTargets", "MAX_SPANS", "Span", "SpanLog", "TRACE_SCHEMA",
    "TraceError", "TraceLog", "validate_record",
]
