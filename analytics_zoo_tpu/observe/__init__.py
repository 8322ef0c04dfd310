"""analytics_zoo_tpu.observe — the unified observability layer.

Five parts (see docs/OBSERVABILITY.md):

- ``trace``    — trace/span ids + the bounded span ring (``TRACER``)
- ``metrics``  — labeled counters/gauges/histograms with
  snapshot/delta semantics (``METRICS``), mirrored onto the legacy
  flat ``core.profiling.TIMERS`` names via the ``flat=`` helpers
- ``export``   — Prometheus text dump, JSONL event log, TensorBoard
  bridge
- ``recorder`` — the SLO-watching flight recorder
- ``builds``   — JAX's program builds booked in ``METRICS`` by phase
  (``install_build_listener``, which ``init_zoo_context`` calls)
"""

from analytics_zoo_tpu.observe.builds import install_build_listener
from analytics_zoo_tpu.observe.export import (JsonlEventLog,
                                              parse_prometheus,
                                              publish_to_summary,
                                              to_prometheus)
from analytics_zoo_tpu.observe.metrics import (CATALOG, METRICS,
                                               MetricsRegistry,
                                               MetricsSnapshot, count,
                                               observe, render_series,
                                               set_gauge, time_stage)
from analytics_zoo_tpu.observe.recorder import SLO, FlightRecorder
from analytics_zoo_tpu.observe.trace import (TRACER, Span, Tracer,
                                             find_orphans, span)

__all__ = [
    "TRACER", "Span", "Tracer", "span", "find_orphans",
    "CATALOG", "METRICS", "MetricsRegistry", "MetricsSnapshot",
    "count", "observe", "set_gauge", "time_stage", "render_series",
    "JsonlEventLog", "to_prometheus", "parse_prometheus",
    "publish_to_summary",
    "SLO", "FlightRecorder",
    "install_build_listener",
]
