"""Observability tier: device-rate load telemetry, latency histograms,
span tracing and live theory-bound alarms (DESIGN.md §15).

The paper's headline claims — constant lookup time, minimal-disruption
remapping, near-uniform balance — validated continuously on live traffic
instead of only offline in benchmarks: a lock-free ``MetricsRegistry``
over the streaming µs clocks, a ``LoadMonitor`` whose per-shard bincount
rides inside the router's own fused dispatch (certified as
``observability/load_pass``), ring-buffer ``SpanTrace`` over the request
path, JSON/Prometheus exposition, and typed ``BalanceDriftAlarm`` /
``DisruptionBoundAlarm`` when observed behavior drifts from the proven
bounds.
"""
from repro.observability.alarms import (
    BalanceDriftAlarm,
    DisruptionBoundAlarm,
    ObservabilityAlarm,
    deliver,
)
from repro.observability.export import snapshot, to_json, to_prometheus
from repro.observability.load import (
    DisruptionTracker,
    LoadConfig,
    LoadMonitor,
    disruption_bound,
    expected_peak_over_mean,
    route_with_load_impl,
)
from repro.observability.metrics import (
    DEFAULT_BUCKETS_US,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.observability.trace import (
    SPAN_COLLECT,
    SPAN_DISPATCH,
    SPAN_LIFECYCLE_TICK,
    SPAN_READ,
    SPAN_REQUEST,
    Span,
    SpanTrace,
    span,
)

__all__ = [
    "BalanceDriftAlarm",
    "DisruptionBoundAlarm",
    "ObservabilityAlarm",
    "deliver",
    "snapshot",
    "to_json",
    "to_prometheus",
    "DisruptionTracker",
    "LoadConfig",
    "LoadMonitor",
    "disruption_bound",
    "expected_peak_over_mean",
    "route_with_load_impl",
    "DEFAULT_BUCKETS_US",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SPAN_COLLECT",
    "SPAN_DISPATCH",
    "SPAN_LIFECYCLE_TICK",
    "SPAN_READ",
    "SPAN_REQUEST",
    "Span",
    "SpanTrace",
    "span",
]
