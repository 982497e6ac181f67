"""Unified observability layer: span tracing + metrics registry.

The repo's telemetry before this package was `ServingMetrics` snapshots
and ad-hoc prints; the ROADMAP's traffic-harness and canary-fleet items
both presuppose per-request timelines and SLO attainment counters. This
package is that substrate:

  * `trace.Tracer` — monotonic-clock spans in a lock-light bounded ring,
    exported as Chrome trace-event JSON (Perfetto / chrome://tracing).
    Threaded through both servers (enqueue -> queue wait -> batch
    formation -> dispatch -> complete, one span per decode iteration)
    and the training fit loops (staging, dispatch, health, checkpoint).
    `Tracer.annotate_with(factory)` adds one injected sink: the package
    root hands `TRACER` `jax.profiler.TraceAnnotation`, so every span is
    an event on the device trace's clock under a profiler session.
  * `registry.MetricsRegistry` — the named counter/gauge/reservoir/
    histogram surface everything publishes through (serving metrics,
    PS-transport retries, async-iterator queue depth, training-health
    counters), exported as a Prometheus text route on `ui/server.py`
    (`/metrics`). `Histogram` is the fixed-bucket cumulative kind the
    serving SLO metrics (TTFT, inter-token) scrape as.
  * `trace.FlightRecorder` — arm the tracer when rolling p99 crosses a
    threshold, so SLO violations self-document.
  * `compiles` — what jax traces, lowers and compiles, from the
    listeners the package root registers with `jax.monitoring`: the
    process-wide `compile.*` counters, a `compile.backend` span for
    every backend compile by the function's name, and the
    `mark()` / `dispatched()` bracket by which a dispatch site of a
    training program turns a dispatch that compiled into one
    `train.compile` span and the `train.compile*` counters.
  * `decompose.decompose` — post-hoc span-derived latency
    decomposition: each served request's total attributed to
    queue-wait / prefill / decode / scheduling-gap phases (the
    traffic-harness analyzer; rendered by `tools/obs_report.py`).
  * `fleet.FleetView` / `fleet.merge_traces` /
    `fleet.AutoscaleSignal` — the fleet plane: kind-correct metrics
    federation over N instances (in-process or parsed `/metrics`
    text), clock-anchor trace stitching into one Perfetto file with
    per-instance process groups, and the ROADMAP autoscaling recipe
    as a windowed, hysteresis-bounded, tested detector (rendered by
    `tools/fleet_report.py`).

Hard constraints: stdlib-only (importing or using obs can never pull in
jax or add a device dispatch — pinned by test), and the disabled tracer
costs nanoseconds per call site (pinned by test). `TRACER` is the
process-wide default tracer (disabled until `enable_tracing()`);
`registry.default_registry()` is the process-wide metrics surface.
"""
from __future__ import annotations

from . import compiles, registry
from .decompose import decompose, decompose_requests
from .fleet import (AutoscaleSignal, FleetView, merge_traces,
                    parse_prometheus_text)
from .registry import Histogram, MetricsRegistry, default_registry, fmt
from .trace import FlightRecorder, Span, TraceContext, Tracer

TRACER = Tracer(enabled=False)


def get_tracer():
    """The process-wide tracer (servers and fit loops default to it)."""
    return TRACER


def span(name, **kw):
    """Record a span on the global tracer (no-op while disabled)."""
    return TRACER.span(name, **kw)


def enable_tracing():
    """Turn the global tracer on; returns it (for .save()/.spans())."""
    return TRACER.enable()


def disable_tracing():
    return TRACER.disable()


__all__ = [
    "Tracer", "Span", "TraceContext", "FlightRecorder",
    "MetricsRegistry", "Histogram",
    "default_registry", "fmt", "registry", "compiles",
    "decompose", "decompose_requests",
    "FleetView", "AutoscaleSignal", "merge_traces",
    "parse_prometheus_text",
    "TRACER", "get_tracer", "span", "enable_tracing", "disable_tracing",
]
