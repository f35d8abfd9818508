"""Fleet observatory: event-sourced tracing, a dependency-free metrics
registry with exact cross-shard merge, carbon/SLA attribution rollups,
and wall-clock host spans on the profiler's clock.  See
``docs/observability.md`` for the span schema, metric names and the
overhead gate.
"""
from repro.core.obs.host import span
from repro.core.obs.metrics import (Counter, Gauge, Histogram,
                                    MetricsRegistry, log_bounds, merged,
                                    to_json, to_prometheus)
from repro.core.obs.observer import FleetObserver, ObsConfig, as_observer
from repro.core.obs.pmeter_bridge import observe_pmeter
from repro.core.obs.rollup import CarbonLedgerView, JobRow
from repro.core.obs.trace import (JsonlSink, RingSink, Span, TraceSink,
                                  emit_all, load_jsonl)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "log_bounds",
    "merged", "to_json", "to_prometheus",
    "FleetObserver", "ObsConfig", "as_observer",
    "observe_pmeter",
    "CarbonLedgerView", "JobRow",
    "JsonlSink", "RingSink", "Span", "TraceSink", "emit_all", "load_jsonl",
    "span",
]
