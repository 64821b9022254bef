"""Unified runtime telemetry: one span primitive (`span`, and `dispatch`
around program calls) that feeds the profiler's trace, an always-on
clock per layer and the host tracer; a process-wide metrics registry;
Chrome trace-event export; a reader for `jax.profiler` traces
(`python -m keystone_tpu.telemetry device <dir>`).

Span hierarchy (structural, via per-thread stacks):

    pipeline run → optimizer phase → node force → program dispatch
                                                → blocking pull (sync)
                                                → stream chunk
                                                → solver fit → iteration

Quick start:

    from keystone_tpu.telemetry import trace_run
    with trace_run("run.json"):
        pipeline(data).get()
    # -> run.json loads in chrome://tracing / Perfetto

    KEYSTONE_TRACE=run.json python -m keystone_tpu.pipelines MnistRandomFFT
    python -m keystone_tpu.telemetry run.json   # summarize

Metric names, the span model, and the static-vs-observed memory
reconciliation workflow are documented in OBSERVABILITY.md.
"""

from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsDelta,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    metrics_delta,
    registry,
)
from . import ledger
from .spans import (
    LAYERS,
    SpanRecord,
    Tracer,
    capabilities,
    current_tracer,
    record_capability,
    scope_name,
    set_tracer,
    span,
    telemetry_active,
    trace_run,
)
from .export import (
    aggregate_spans,
    compile_summary,
    dispatch_plan_breakdown,
    dispatch_summary,
    load_trace,
    self_times,
    summarize,
    to_chrome_trace,
    write_trace,
)
from .instrument import (
    dispatch,
    estimate_bytes,
    fn_label,
    instrument_node_force,
    record_dispatch,
)
from .compile_events import compiles_snapshot, install_compile_listeners
from .gc_events import install_gc_hook
from .flight import (
    FlightRecorder,
    ensure_flight,
    flight_recorder,
    flight_snapshot,
    reset_flight,
)
from .streaming import QuantileSketch, format_health, health, reset_live
from .watchdog import (
    ConformanceWatchdog,
    active_watchdog,
    arm_watchdog,
    disarm_watchdog,
    request_scope,
)

# Compile accounting is armed with the package: the monitoring hooks are
# passive (they fire only inside jax's own compile path), and installing
# here means no compile anywhere in the process escapes
# `dispatch.programs_compiled` — the same always-on discipline as
# `dispatch`.
install_compile_listeners()
# So is the collector's hook: two clock reads and three counter adds a
# collection (`gc_events`).
install_gc_hook()

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsDelta", "MetricsRegistry",
    "counter", "gauge", "histogram", "ledger", "metrics_delta",
    "registry",
    "LAYERS", "SpanRecord", "Tracer", "capabilities", "current_tracer",
    "record_capability", "scope_name", "set_tracer", "span",
    "telemetry_active", "trace_run",
    "aggregate_spans", "compile_summary", "dispatch_plan_breakdown",
    "dispatch_summary", "load_trace", "self_times",
    "summarize", "to_chrome_trace", "write_trace",
    "dispatch", "estimate_bytes", "fn_label", "instrument_node_force",
    "record_dispatch",
    "compiles_snapshot", "install_compile_listeners",
    "FlightRecorder", "ensure_flight", "flight_recorder",
    "flight_snapshot", "reset_flight",
    "QuantileSketch", "format_health", "health", "reset_live",
    "ConformanceWatchdog", "active_watchdog", "arm_watchdog",
    "disarm_watchdog", "request_scope",
]
