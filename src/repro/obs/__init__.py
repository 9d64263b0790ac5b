# repro.obs — the observability layer every serving component reports
# through (docs/OBSERVABILITY.md): span-based request tracing with a
# Chrome/Perfetto trace exporter, a process-wide metric registry
# (counters / gauges / labeled fixed-bucket histograms), JAX compile
# and device-memory visibility, structured JSON-lines event logging,
# and regression gating over the committed BENCH_*.json trajectory.
from repro.obs.export import EventLog, write_chrome_trace, write_metrics
from repro.obs.profiler import (CompileWatcher, compile_region,
                                current_region, device_memory_gauges,
                                profiler_session, span,
                                version_family_gauges)
from repro.obs.registry import (REGISTRY, Counter, Gauge, Histogram,
                                MetricRegistry, default_latency_buckets)
from repro.obs.slo import (AlertState, SLOEngine, SLOSpec, compiles_source,
                           counter_source, default_serving_slos,
                           latency_source)
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "EventLog", "write_chrome_trace", "write_metrics",
    "CompileWatcher", "compile_region", "current_region",
    "device_memory_gauges", "profiler_session", "span",
    "version_family_gauges",
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricRegistry",
    "default_latency_buckets",
    "AlertState", "SLOEngine", "SLOSpec", "compiles_source",
    "counter_source", "default_serving_slos", "latency_source",
    "NULL_TRACER", "NullTracer", "Span", "Tracer",
]
