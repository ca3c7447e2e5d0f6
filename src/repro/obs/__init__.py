"""Unified observability plane: metrics, tracing, export.

One process-wide plane with three legs, shared by every layer of the
stack (index services, router, compactor, kernel dispatch, serving
engine, benchmarks):

  * ``obs.metrics`` — thread-safe `MetricsRegistry` of counters,
    gauges, and fixed log-bucket latency `Histogram`s cheap enough to
    record per op; percentile (p50/p90/p99) reads come straight off
    the bucket counts, no sample retention.
  * ``obs.trace``   — a gated span API over `jax.profiler`
    annotations: enabled, service-op spans nest over router /
    kernel-dispatch / compactor-thread activity on the profiler's host
    plane, on the device trace's clock.
  * ``obs.export``  — JSON snapshots and Prometheus text exposition
    over any registry.

Service-level metrics live in per-service registries (so K shard
services never alias each other's counters); cross-cutting dispatch
attribution records into ``metrics.default_registry()``.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    StatsView,
    default_registry,
)
from repro.obs.trace import Tracer, TRACER, span, instant
from repro.obs import lockstat
from repro.obs.export import (
    op_latency_rows,
    prometheus_text,
    registry_json,
    write_json,
    write_prometheus,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StatsView",
    "default_registry",
    "Tracer", "TRACER", "span", "instant",
    "lockstat",
    "op_latency_rows", "prometheus_text", "registry_json",
    "write_json", "write_prometheus",
]
