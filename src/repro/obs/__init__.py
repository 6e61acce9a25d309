"""Observability layer: engine tracing + fleet-health monitoring.

  trace.py  — host-side span tracer writing Chrome trace-event JSON
              (load in Perfetto / chrome://tracing); a process-global
              tracer slot with a zero-overhead no-op default, wired into
              the `launch.engine` drivers (chunk / compile / dispatch /
              history drain / eval / transfer spans, and each chunk's
              device time), JAX's trace / lower / compile phases, one
              clock anchored to Unix time, and `run_fl --trace`.
  health.py — fleet-health monitors over the engine's FleetState and
              streaming-telemetry reducers: flat-battery counter,
              near-depletion watermark, selection-count Gini, and
              streaming staleness / residual-energy quantiles, checked
              against a declarative `HealthCfg` threshold set
              (`run_fl --health-strict` turns violations into a
              non-zero exit code).
  log.py    — stdlib logging for the runner/benchmark chatter, so
              health WARNINGs are distinguishable from progress lines
              (`--quiet` / `-v`).
"""
from repro.obs.log import configure_logging, get_logger  # noqa: F401
from repro.obs.trace import (NullTracer, Tracer,  # noqa: F401
                             format_span_table, get_tracer, last_tracer,
                             set_tracer, span, tracing, until_ready)
from repro.obs.health import (HealthCfg, HealthReport,  # noqa: F401
                              chunk_sample, finalize_report,
                              format_health_table, gini,
                              with_health_specs)
