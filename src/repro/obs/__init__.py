"""repro.obs — tracing, metrics, and convergence telemetry.

Zero-dependency instrumentation for the plan/engine/estimator stack.
Three modes via ``REPRO_OBS=off|metrics|trace`` (default ``off``):

==========  ==========================================================
``off``     nothing recorded; no host callbacks staged into jitted
            code (the lowered HLO is byte-identical to uninstrumented);
            host spans reach only a running ``jax.profiler`` session
``metrics`` counters / gauges / histograms (plan-cache hits, retraces,
            probes used, CG iterations, ...)
``trace``   metrics + wall-time spans + convergence telemetry streamed
            off device via ``jax.debug.callback``; artifacts written
            to ``REPRO_OBS_DIR`` (default ``obs_out/``) at exit
==========  ==========================================================

See docs/observability.md for the full tour.  Public surface::

    with obs.span("plan.build"):          # host span, on the profiler too
        ...
    with obs.stage("engine.pivot"):       # jax.named_scope + trace span
        ...
    obs.inc("plan.cache.hits")            # metrics
    obs.emit_curve("slq.sem", curve)      # telemetry (inside traced code)
    obs.export_chrome_trace("trace.json") # Perfetto-loadable
"""
from repro.obs.config import (
    ENV_DIR, ENV_VAR, MODES, configure, metrics_enabled, mode, out_dir,
    trace_enabled,
)
from repro.obs.export import (
    add_metrics_cli, chrome_trace, export_chrome_trace, export_jsonl,
    export_metrics, install_atexit, start_metrics_from_args,
    start_metrics_server, validate_chrome_trace, write_all,
)
from repro.obs.metrics import (
    counter_value, inc, observe, prometheus_text, quantile, set_gauge,
    snapshot,
)
from repro.obs.telemetry import (
    drain as drain_telemetry, emit_curve, emit_point, flush as flush_telemetry,
    running_sem,
)
from repro.obs.trace import dropped_events, events, span, stage

__all__ = [
    "configure", "mode", "out_dir", "metrics_enabled", "trace_enabled",
    "MODES", "ENV_VAR", "ENV_DIR",
    "span", "stage", "events", "dropped_events",
    "inc", "set_gauge", "observe", "counter_value", "snapshot",
    "prometheus_text", "quantile",
    "emit_curve", "emit_point", "running_sem", "drain_telemetry",
    "flush_telemetry",
    "chrome_trace", "export_chrome_trace", "export_jsonl", "export_metrics",
    "validate_chrome_trace", "write_all", "start_metrics_server",
    "add_metrics_cli", "start_metrics_from_args",
    "install_atexit", "reset",
]


def reset() -> None:
    """Clear spans, metrics, and telemetry buffers (test hook)."""
    from repro.obs import metrics as _m, telemetry as _t, trace as _tr
    _tr.reset()
    _m.reset()
    _t.reset()


# REPRO_OBS set in the environment -> dump artifacts at interpreter exit.
if mode() != "off":
    install_atexit()
