"""Observability: deterministic tracing, typed metrics, exporters, a
flight recorder, and the analysis layer built on top of them -- the
cost-model-attributed profiler and the SLO burn-rate monitor.

The layer's pieces (see ``docs/architecture.md`` sections 8-9):

  * ``obs.trace``    -- span-tree tracer with an injectable clock; under
    ``serving.clock.VirtualClock`` every timestamp and span count is
    bit-deterministic and exactly CI-gateable.  Disabled by default
    (``NullTracer``): each engine hook is one branch.
  * ``obs.metrics``  -- typed registry (counters / gauges / histograms
    with the shared nearest-rank ``percentile``) unifying the serving
    stack's ad-hoc stats dicts behind back-compat views, with labeled
    dimensions (tenant, plan kind, backend, dtype/qformat, size class).
  * ``obs.export``   -- Chrome-trace-event JSON (Perfetto: one track per
    plan bucket + one per recovery ladder) and Prometheus text
    exposition.
  * ``obs.recorder`` -- bounded ring-buffer flight recorder dumped into
    ``LaunchError`` / chaos post-mortems.
  * ``obs.profile``  -- folds a traced run's span stream into a
    self/child attribution tree and per-kernel launch tables, with the
    cost model's per-launch predictions (attached at dispatch time)
    compared against observed traffic; ``python -m repro.obs.profile``.
  * ``obs.slo``      -- multi-window burn-rate alerting over the
    latency / rejection error budgets, deterministic under a virtual
    clock, exported through ``prometheus_text``.

Quickstart::

    from repro import obs
    from repro.serving.clock import VirtualClock

    trc = obs.Tracer(clock=VirtualClock(),
                     recorder=obs.FlightRecorder(256))
    with obs.installed(trc):
        ...serve...
    obs.dump_chrome_trace(trc, "out.json")       # open in Perfetto
    print(obs.prometheus_text(my_registry))
"""
from repro.obs.export import (chrome_trace, chrome_trace_events,
                              dump_chrome_trace, prometheus_text)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               StatsView, percentile)
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import (NullTracer, Span, SpanNode, Tracer, active,
                             install, installed)

#: analysis-layer symbols resolved lazily (PEP 562): ``repro.obs.profile``
#: is also a ``python -m`` entry point, and an eager package-level import
#: of it would trip runpy's double-import warning on every CLI invocation
_LAZY = {
    "LaunchGroup": "profile", "Profile": "profile",
    "ProfileNode": "profile", "dump_span_stream": "profile",
    "load_span_stream": "profile",
    "BurnRule": "slo", "SLOMonitor": "slo",
}


def __getattr__(name):
    submodule = _LAZY.get(name)
    if submodule is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    value = getattr(importlib.import_module(f"repro.obs.{submodule}"),
                    name)
    globals()[name] = value
    return value

__all__ = [
    "BurnRule", "Counter", "FlightRecorder", "Gauge", "Histogram",
    "LaunchGroup", "MetricsRegistry", "NullTracer", "Profile",
    "ProfileNode", "SLOMonitor", "Span", "SpanNode", "StatsView", "Tracer",
    "active", "chrome_trace", "chrome_trace_events", "dump_chrome_trace",
    "dump_span_stream", "install", "installed", "load_span_stream",
    "percentile", "prometheus_text",
]
