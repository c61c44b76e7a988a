"""Observability subsystem (DESIGN.md §15/§16): request-lifecycle
tracing, unified metrics registry, continuous time-series telemetry,
SLO monitoring, trace export, and latency attribution.

Everything here rides the deterministic :class:`~repro.serving.clock.
VirtualClock`, so traces, timeseries, and alerts are bit-reproducible:
same seed, same bytes — except the tracer's wall-clock spans of the
served path (``Tracer.wall``, ``wall_breakdown``), which time the
program itself and are kept apart from every virtual-time artifact.
"""
from repro.obs.analyze import (attribution, check_conservation,
                               critical_path, flamegraph_folded,
                               format_attribution, format_critical_path,
                               wall_breakdown)
from repro.obs.export import (export_timeseries, export_trace,
                              write_alerts, write_chrome_trace,
                              write_jsonl, write_timeseries)
from repro.obs.metrics import (STALE_AGE_EDGES, FixedHistogram,
                               MetricsRegistry, ScanMetrics, percentile)
from repro.obs.sampler import TimeSeriesSampler, limiter_headroom
from repro.obs.slo import SLO, SLOMonitor
from repro.obs.trace import (BACKGROUND, NULL_TRACER, NullTracer, Tracer,
                             WallSpan)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "BACKGROUND", "WallSpan",
    "MetricsRegistry", "FixedHistogram", "ScanMetrics", "percentile",
    "STALE_AGE_EDGES",
    "TimeSeriesSampler", "limiter_headroom", "SLO", "SLOMonitor",
    "export_trace", "write_jsonl", "write_chrome_trace",
    "export_timeseries", "write_timeseries", "write_alerts",
    "check_conservation", "attribution", "format_attribution",
    "critical_path", "flamegraph_folded", "format_critical_path",
    "wall_breakdown",
]
