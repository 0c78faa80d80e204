"""End-to-end query observability for the serve path.

Four pieces, wired into every layer of the query path (see
docs/observability.md):

* ``repro.obs.stages``   — ``stage``, the one span primitive: a named host stage that
  opens an ``rnsg.<name>`` profiler annotation, adds its wall time to the
  ``stage_<name>_ms`` histogram, and appends a span to the request's trace;
* ``repro.obs.metrics``  — lock-cheap ``MetricsRegistry`` (counters,
  gauges, fixed-bucket log-scale latency histograms with p50/p90/p99
  extraction) usable from the engine's resolver/dispatcher threads;
* ``repro.obs.trace``    — opt-in per-query ``QueryTrace`` records threaded
  through ``SearchRequest``/``SearchResult``, filled by the stages;
* ``repro.obs.export``   — JSON snapshot, Prometheus text format, and the
  periodic one-line stats log; ``repro.obs.profiler`` captures a device
  trace around a block.
"""
from repro.obs.export import (CORE_FAMILIES, format_stats_line,
                              parse_prometheus, to_prometheus,
                              write_prometheus)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry)
from repro.obs.profiler import device_trace
from repro.obs.stages import DISPATCHER_STAGES, set_batch, stage
from repro.obs.trace import QueryTrace, Span

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram",
           "default_registry",
           "stage", "set_batch", "DISPATCHER_STAGES",
           "QueryTrace", "Span",
           "to_prometheus", "write_prometheus", "parse_prometheus",
           "format_stats_line", "CORE_FAMILIES",
           "device_trace"]
