"""Unified observability plane: metrics registry + event trail.

The reference platform's only observability was the Spark Web UI and
``kubectl top`` polling (SURVEY §5); our reproduction grew three
disjoint stores in response — an ad-hoc step timer,
``BundleServer.metrics_text``'s ad-hoc counters, and a JSONL file
of benchmark results — that could not be correlated. This package is the
single metrics plane they all converge on:

* :mod:`~pyspark_tf_gke_tpu.obs.metrics` — thread-safe
  :class:`MetricsRegistry` with labeled Counter/Gauge/Histogram,
  Prometheus text exposition, and a JSON snapshot;
* :mod:`~pyspark_tf_gke_tpu.obs.events` — bounded append-only JSONL
  :class:`EventLog` for discrete occurrences (checkpoint saved, retry
  fired, engine rebuilt) with monotonic sequence numbers;
* :mod:`~pyspark_tf_gke_tpu.obs.runtime` — process/JAX collectors
  (RSS, device count, live-array bytes), guarded so CPU-only CI runs;
* :mod:`~pyspark_tf_gke_tpu.obs.export` — node-exporter textfile
  writer (atomic rename on an interval thread) and the ``/metrics`` +
  ``/events`` + ``/traces`` HTTP handler logic the serving plane
  mounts;
* :mod:`~pyspark_tf_gke_tpu.obs.trace` — end-to-end request tracing:
  W3C ``traceparent`` propagation, contextvar-carried spans, and a
  bounded flight recorder with sampling + always-on slow capture; the
  one span primitive (``span`` / ``annotate``) that also writes to the
  profiler's trace, and the process-default tracer;
* :mod:`~pyspark_tf_gke_tpu.obs.compiles` — JAX's own trace / lower /
  compile as spans under whatever program span caused them, and
  ``runtime_jit_compiles_total{fun}``.

Naming scheme (enforced by tools/smoke_check.py's duplicate lint and
documented in docs/OBSERVABILITY.md): ``<plane>_<thing>_<unit>`` with
planes ``train_``, ``serve_``, ``runtime_``.

Dependency-free by design: stdlib + the already-present jax only, and
every jax touch is guarded — the registry and event trail must work in
a CPU-only test run and in host-side tools that never attach a device.
"""

from pyspark_tf_gke_tpu.obs.events import (
    EventLog,
    append_jsonl_line,
    get_event_log,
    set_event_log,
)
from pyspark_tf_gke_tpu.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsError,
    MetricsRegistry,
    duplicate_metric_conflicts,
    get_registry,
    platform_families,
    set_registry,
)
from pyspark_tf_gke_tpu.obs.stepstats import (
    StepRecord,
    StepStatsRing,
    flops_per_token,
)
from pyspark_tf_gke_tpu.obs.trace import (
    Span,
    TraceRecorder,
    annotate,
    current_span,
    current_trace_id,
    format_traceparent,
    get_tracer,
    parse_traceparent,
    set_tracer,
    span,
    use_span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsError",
    "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "duplicate_metric_conflicts",
    "get_registry",
    "set_registry",
    "platform_families",
    "EventLog",
    "append_jsonl_line",
    "get_event_log",
    "set_event_log",
    "StepRecord",
    "StepStatsRing",
    "flops_per_token",
    "Span",
    "TraceRecorder",
    "annotate",
    "current_span",
    "current_trace_id",
    "format_traceparent",
    "get_tracer",
    "parse_traceparent",
    "set_tracer",
    "span",
    "use_span",
]
