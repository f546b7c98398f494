"""paddle_tpu.observability — always-on runtime telemetry.

The offline profiler (paddle_tpu.profiler, XPlane capture) answers "why
was this step slow"; this package answers "what is the system doing
RIGHT NOW and what did it do over the last million steps" — the metrics
layer every production trainer/server carries (tokens/s, MFU, comm
bytes, queue depths, latency quantiles, memory watermarks).

    import paddle_tpu.observability as obs

    obs.configure(jsonl_path="telemetry.jsonl")   # or env
    reqs = obs.counter("serving.requests")
    reqs.inc(reason="admitted")                   # labeled series
    obs.histogram("serving.ttft_seconds").observe(0.031)
    print(obs.PrometheusExporter().render())

    with obs.span("myapp.handle", request_id="r1") as sp:
        sp.event("admitted")                      # structured tracing:
        ...                                       # spans + flight
    obs.flight_dump(reason="debug")               # recorder (tracing.py)

    obs.enabled(False)    # every record becomes an early-return and
                          # jit_callback emits NOTHING when tracing

Instrumented out of the box: fleet.DistTrainStep / PipelineTrainStep
(step time, tokens/s, MFU, grad-norm, memory watermarks, per-axis
collective bytes), distributed.collective (per-op call/byte accounting),
inference.ContinuousBatchingPredictor (queue depth, page utilization,
TTFT / per-token latency, admissions/evictions/rejections), the Trainer
loop, the elastic launcher (per-rank heartbeats), and the
fault-tolerance layer (robustness.* counters: anomalies skipped,
checkpoint retries/fallbacks, deadline evictions, shed requests,
watchdog trips, injected faults — docs/ROBUSTNESS.md). The fleet layer
(fleet.py) joins the per-rank files cross-rank: step skew, straggler
detection, comm-wait attribution (docs/OBSERVABILITY.md "Fleet view").
Metric catalog: docs/OBSERVABILITY.md.
"""
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricRegistry, Sample, DEFAULT_BUCKETS,
    enabled, scoped, get_registry, counter, gauge, histogram,
)
from .exporters import (  # noqa: F401
    JsonlExporter, PrometheusExporter, TensorBoardExporter,
)
from .runtime import (  # noqa: F401
    jit_callback, device_memory_stats, configure, maybe_export,
    export_record, telemetry_path, RankHeartbeat, rank_identity,
    set_identity, export_identity, watch_compiles, compile_log, jit_tag,
    watch_gc, gc_log,
)
from .slo import (  # noqa: F401
    Ewma, SLOSpec, SLOEngine, default_serving_slos,
)
from .fleet import (  # noqa: F401
    FleetAggregator, StragglerDetector, RankFileTailer,
)
from .tracing import (  # noqa: F401
    Span, TraceContext, NULL_SPAN, span, start_span, traced,
    current_span, FlightRecorder, flight_recorder, flight_dump,
    flight_dir, set_flight_dir, to_chrome_trace, write_chrome_trace,
    tick, ticks, clear_ticks,
)
from .critpath import (  # noqa: F401
    stage_decomposition, trace_tree,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "Sample",
    "DEFAULT_BUCKETS", "enabled", "scoped", "get_registry", "counter",
    "gauge", "histogram", "JsonlExporter", "PrometheusExporter",
    "TensorBoardExporter", "jit_callback", "device_memory_stats",
    "configure", "maybe_export", "export_record", "telemetry_path",
    "RankHeartbeat", "rank_identity", "set_identity", "export_identity",
    "watch_compiles", "compile_log", "jit_tag", "watch_gc", "gc_log",
    "tick", "ticks",
    "clear_ticks", "Ewma", "SLOSpec", "SLOEngine", "default_serving_slos",
    "FleetAggregator",
    "StragglerDetector", "RankFileTailer",
    "Span", "TraceContext", "NULL_SPAN", "span", "start_span",
    "traced", "current_span", "FlightRecorder", "flight_recorder",
    "flight_dump", "flight_dir", "set_flight_dir", "to_chrome_trace",
    "write_chrome_trace", "stage_decomposition", "trace_tree",
]
