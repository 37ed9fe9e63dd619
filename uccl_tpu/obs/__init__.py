"""uccl_tpu.obs — unified observability: event tracing + telemetry registry.

The framework-wide telemetry spine (docs/OBSERVABILITY.md). Three layers,
all host-only and jax-free:

* :mod:`uccl_tpu.obs.tracer` — thread-safe ring-buffered event tracer
  (spans + instants, monotonic timestamps, per-thread tracks, bounded
  memory, zero-cost when disabled);
* :mod:`uccl_tpu.obs.counters` — labeled counter/gauge/histogram registry
  + pull sources (absorbs and supersedes ``utils.stats``'s registration
  surface); histograms are the merge-safe fleet latency surface
  (:mod:`uccl_tpu.obs.aggregate` sums N workers' exports);
* :mod:`uccl_tpu.obs.context` — cross-process trace context (trace ids
  minted at request ingress, carried in disagg control notifs, bound
  across processes by Chrome-trace flow events) + the RTT-midpoint
  clock-offset estimator behind ``scripts/trace_merge.py``;
* :mod:`uccl_tpu.obs.chrome_trace` / :mod:`uccl_tpu.obs.export` — the
  Chrome-trace/Perfetto JSON exporter and the Prometheus-text ``/metrics``
  + JSON ``/snapshot`` surfaces (file dump via ``--trace-out`` /
  ``--metrics-out`` on every CLI; live HTTP in ``serve --server``).

Instrumentation idiom::

    from uccl_tpu import obs

    obs.counter("ep_wire_fallback_total").inc(reason="vmem_budget")
    with obs.span("engine.step", track="engine", queued=3):
        ...
    obs.instant("first_token", track=req.track)

Nothing is recorded (one bool check) until ``obs.enable_tracing()`` /
``--trace-out`` turns the tracer on; counters are always live (they are
just dict adds). Independently of that switch, every ``obs.span`` and every
``obs.mark`` (an instant a reader wants beside the device's operations: a
request's ``admit`` and ``first_token``) is also written into any open
``jax.profiler`` session
as ``uccl.<name>`` — on the profiler's clock (obs/tracer.py; about a
microsecond each when no session is open, nothing in a process without
JAX).
"""

from uccl_tpu.obs.counters import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS, REGISTRY, CounterFamily, GaugeFamily,
    HistogramFamily, Registry, bucket_width, counter, escape_label_value,
    gauge, histogram, histogram_quantile, log_buckets, sanitize_name,
)
from uccl_tpu.obs.context import (  # noqa: F401
    TraceContext, estimate_clock_offset, flow_id, new_context,
)
from uccl_tpu.obs.tracer import (  # noqa: F401
    Event, Tracer, complete, flow_end, flow_start, get_tracer, instant,
    mark, set_clock_offset, span,
)
from uccl_tpu.obs.tracer import enable as enable_tracing  # noqa: F401
from uccl_tpu.obs.tracer import disable as disable_tracing  # noqa: F401
from uccl_tpu.obs.tracer import enabled as tracing_enabled  # noqa: F401
from uccl_tpu.obs.export import (  # noqa: F401
    SCHEMA_VERSION, MetricsServer, add_cli_args, dump_at_exit,
    dump_from_args, json_snapshot, prometheus_text, setup_from_args,
    write_metrics, write_trace,
)
from uccl_tpu.obs.chrome_trace import to_chrome_trace  # noqa: F401
from uccl_tpu.obs.flight import (  # noqa: F401
    FlightRecorder, TRIGGERS, install_excepthook, record_exception,
)
from uccl_tpu.obs.flight import enable as enable_flight  # noqa: F401
from uccl_tpu.obs.flight import disable as disable_flight  # noqa: F401
from uccl_tpu.obs.flight import enabled as flight_enabled  # noqa: F401
from uccl_tpu.obs.flight import get_recorder as get_flight  # noqa: F401
from uccl_tpu.obs.flight import (  # noqa: F401
    register_provider as flight_provider,
)
from uccl_tpu.obs.flight import trigger as flight_trigger  # noqa: F401
from uccl_tpu.obs.flight import (  # noqa: F401
    unregister_provider as flight_unregister,
)
from uccl_tpu.obs.slo import (  # noqa: F401
    Alert, BurnRateMonitor, Objective, serving_objectives,
)

__all__ = [
    "REGISTRY", "CounterFamily", "GaugeFamily", "HistogramFamily",
    "Registry", "counter", "gauge", "histogram", "histogram_quantile",
    "bucket_width", "log_buckets", "DEFAULT_LATENCY_BUCKETS",
    "sanitize_name", "escape_label_value", "Event", "Tracer",
    "complete", "get_tracer", "instant", "mark", "span",
    "flow_start", "flow_end", "set_clock_offset",
    "TraceContext", "new_context", "flow_id", "estimate_clock_offset",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "SCHEMA_VERSION", "MetricsServer", "add_cli_args", "dump_at_exit",
    "dump_from_args", "json_snapshot", "prometheus_text", "setup_from_args",
    "write_metrics", "write_trace", "to_chrome_trace",
    "FlightRecorder", "TRIGGERS", "enable_flight", "disable_flight",
    "flight_enabled", "get_flight", "flight_trigger", "flight_provider",
    "flight_unregister", "record_exception", "install_excepthook",
    "Alert", "BurnRateMonitor", "Objective", "serving_objectives",
]
