"""`repro.obs`: unified tracing, metrics, and profiling.

Three cooperating pieces (see each module's docstring):

  obs.trace    structured span tracer -> Chrome-trace/Perfetto JSONL,
               zero-cost no-op when disabled; spans also land on the
               JAX profiler's host line while a profile records
  obs.metrics  `MetricsHub` counter/gauge/histogram registry that
               absorbs `HitRateMeter` / `ResilienceMeter` /
               `StragglerMonitor`, with per-epoch snapshots and a
               versioned export schema (+ the shared `run_metadata`
               header every BENCH_*.json carries)
  obs.report   trace analyzer: producer/consumer overlap fraction,
               stall attribution by stage, host-sync placement gate,
               per-epoch rollups — also `python -m repro.obs`
"""
from repro.obs.metrics import (OBS_SCHEMA_VERSION, Counter, Gauge,
                               Histogram, MetricsHub, run_metadata)
from repro.obs.trace import (TRACE_SCHEMA_VERSION, Tracer, current, enabled,
                             install, instant, span, uninstall)
from repro.obs.report import analyze, load_trace, to_chrome

__all__ = [
    "OBS_SCHEMA_VERSION", "TRACE_SCHEMA_VERSION",
    "Counter", "Gauge", "Histogram", "MetricsHub", "run_metadata",
    "Tracer", "current", "enabled", "install",
    "instant", "span", "uninstall",
    "analyze", "load_trace", "to_chrome",
]
