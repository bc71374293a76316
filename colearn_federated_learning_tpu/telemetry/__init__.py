"""End-to-end round telemetry (see tracer/registry/export/lifecycle).

Public surface:

- :class:`Tracer` / :func:`get_tracer` — nested spans, monotonic timing,
  cross-process trace propagation (``current_context`` + ``adopt``);
  :func:`device_scope` names a part of a compiled program, and
  :func:`program_scopes` (``.runtime``) says which operation of each
  compiled program lies under which names;
- :class:`MetricsRegistry` / :func:`get_registry` — process-wide
  counters, gauges, quantile histograms;
- :mod:`.export` — Chrome-trace/Perfetto JSON writer/loader and the
  ``colearn trace-summary`` text breakdown;
- :class:`RoundTelemetry` — the per-round lifecycle driver shared by the
  span tracer window and the jax profiler window (:class:`RoundProfiler`);
- :mod:`.runtime` — XLA introspection (:class:`CompileTracker` recompile
  detection, AOT cost analysis, HBM gauges) and live export (Prometheus
  endpoint, JSONL event stream, ``colearn top`` renderer);
- :mod:`.flight` — crash flight recorder (heartbeat ring-buffer dumps,
  ``colearn postmortem`` merge with the round WAL);
- :mod:`.health` — durable per-device health ledger (straggler
  attribution, latency sketches, ``colearn health`` renderer);
- :mod:`.arrival` — seeded-EWMA arrival-rate estimation (fleet +
  per-device) feeding the async observatory and ``--async-buffer auto``;
- :mod:`.convergence` — the learning-health plane: per-round update-norm
  / cosine / trend signals from the aggregate, per-cohort drift
  attribution, and the ``colearn converge`` report.
"""

from colearn_federated_learning_tpu.telemetry.tracer import (  # noqa: F401
    Span,
    SpanContext,
    Tracer,
    declared_scopes,
    device_scope,
    get_tracer,
    new_id,
)
from colearn_federated_learning_tpu.telemetry.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from colearn_federated_learning_tpu.telemetry.export import (  # noqa: F401
    default_trace_path,
    load_trace,
    spans_to_chrome,
    summarize_profile,
    summarize_trace,
    trace_spans,
    write_trace,
    write_tracer,
)
from colearn_federated_learning_tpu.telemetry.lifecycle import (  # noqa: F401
    RoundProfiler,
    RoundTelemetry,
)
from colearn_federated_learning_tpu.telemetry.runtime import (  # noqa: F401
    CompileTracker,
    EventLog,
    MetricsExporter,
    Scope,
    compiled_cost,
    program_scopes,
    prometheus_text,
    sample_device_memory,
    tracked_call,
)
from colearn_federated_learning_tpu.telemetry.health import (  # noqa: F401
    DeviceHealth,
    HealthLedger,
    export_gauges,
    feed_transport_retries,
    health_record_keys,
    load_health,
    render_health,
)
from colearn_federated_learning_tpu.telemetry.arrival import (  # noqa: F401
    ArrivalEstimator,
)
from colearn_federated_learning_tpu.telemetry.convergence import (  # noqa: F401,E501
    ConvergenceObservatory,
    cohort_skew,
    device_skew,
    render_convergence_report,
)
from colearn_federated_learning_tpu.telemetry.flight import (  # noqa: F401
    FlightRecorder,
    get_flight_recorder,
    install_flight_recorder,
    load_flight_dumps,
    postmortem_report,
    render_postmortem,
)
