"""Runtime telemetry: metrics registry, span tracing, crash breadcrumbs.

One layer every subsystem reports into (see docs/OBSERVABILITY.md):

  - `metrics.MetricsRegistry`: process-wide counters / gauges /
    bounded-bucket histograms, lock-cheap via per-thread shards merged
    at snapshot time. Owned by the Server (`Server.obs`); snapshot via
    `Server.metrics_snapshot()`. `--sys.metrics` (default on).
  - `spans.Span`: THE phase bracket (`Server._span(name, hist)`): a
    host event on the JAX profiler's clock whenever a profiler session
    runs (no flag: the session is the switch), a registry histogram
    observation when given one, and a `spans.SpanTracer` record
    (Chrome trace-event JSON loadable in Perfetto) under
    `--sys.trace.spans` (default off). `wait=True` marks a span as one
    blocking call, `work=` gives a span a second histogram for its
    time outside every wait beneath it (the host's own).
  - `crash.enable_crash_dumps`: faulthandler with a per-rank dump file,
    plus a last-open-span breadcrumb so an abort is attributable.
  - `flight.FlightTracer`: per-request causal traces across admission
    -> batch -> executor -> device, exported as Perfetto FLOW events —
    one served lookup renders as one connected chain, drawn from the
    phase stamps every `LookupRequest` carries (the always-on
    `serve.*_s` breakdown histograms read the same stamps).
    `--sys.trace.flight` (default off). `flight.FlightRecorder`: the
    bounded per-stream ring of the last executor programs, mirrored to
    a ring file for abort post-mortems (rides `--sys.crash_dumps`).
  - `slo.SLOController`: the closed-loop tail-latency controller that
    adapts the serve micro-batch window toward `--sys.serve.slo_ms`.
    Imported ONLY when a target is set.
  - `reporter.Reporter`: optional periodic one-line summary
    (`--sys.metrics.report`). Imported ONLY when enabled — the hot path
    never pays for it.
"""
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, get_global_registry,
                      observe_global, set_global_registry)
from .spans import Span, SpanTracer  # noqa: F401

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram",
           "SpanTracer", "Span", "get_global_registry",
           "set_global_registry", "observe_global"]
