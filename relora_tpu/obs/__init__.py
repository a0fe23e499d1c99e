"""relora_tpu.obs — unified observability: span tracing, shared metrics
registry, flight recorder, MFU helpers, HBM accounting, and compile
telemetry.

Stdlib-only at import time (``mfu`` / ``memory`` / ``compile`` import jax
lazily, inside calls); safe to import from the serving front-end, the
trainer, and signal handlers.  See docs/observability.md.
"""

from relora_tpu.obs.compile import CompileEvent, CompileWatcher, abstract_signature, signature_diff
from relora_tpu.obs.fleet import (
    FleetCollector,
    SeriesStore,
    histogram_quantile,
    load_series_jsonl,
    parse_prometheus,
)
from relora_tpu.obs.flight import FlightRecorder, configure, default_recorder, dump_on_fault
from relora_tpu.obs.memory import (
    MemoryPoller,
    hbm_peak_gb,
    live_memory_stats,
    plan_for,
    pytree_breakdown,
    pytree_bytes,
    reconcile,
    xla_memory_plan,
)
from relora_tpu.obs.metrics import LATENCY_BUCKETS, Histogram, MetricsRegistry
from relora_tpu.obs.mfu import peak_flops, step_flops_from_cost_analysis
from relora_tpu.obs.slo import (
    SLO,
    Alert,
    AnomalySpec,
    SeriesAnomalyDetector,
    SLOEngine,
    default_slos,
    load_slo_config,
)
from relora_tpu.obs.tracer import (
    NoopTracer,
    Span,
    Tracer,
    chrome_trace_events,
    new_trace_id,
)

__all__ = [
    "CompileEvent",
    "CompileWatcher",
    "abstract_signature",
    "signature_diff",
    "MemoryPoller",
    "hbm_peak_gb",
    "live_memory_stats",
    "plan_for",
    "pytree_breakdown",
    "pytree_bytes",
    "reconcile",
    "xla_memory_plan",
    "FleetCollector",
    "SeriesStore",
    "histogram_quantile",
    "load_series_jsonl",
    "parse_prometheus",
    "SLO",
    "Alert",
    "AnomalySpec",
    "SeriesAnomalyDetector",
    "SLOEngine",
    "default_slos",
    "load_slo_config",
    "FlightRecorder",
    "configure",
    "default_recorder",
    "dump_on_fault",
    "LATENCY_BUCKETS",
    "Histogram",
    "MetricsRegistry",
    "peak_flops",
    "step_flops_from_cost_analysis",
    "NoopTracer",
    "Span",
    "Tracer",
    "chrome_trace_events",
    "new_trace_id",
]
