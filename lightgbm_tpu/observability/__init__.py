"""Observability subsystem: metrics registry, structured JSONL event
log, hot-path tracing hooks, training watchdogs, compiled-cost roofline
accounting, the always-on flight recorder, and Prometheus exposition
(docs/Observability.md).

The reference engine's TIMETAG timers print an aggregate table at exit;
production-scale training additionally needs machine-readable per-
iteration telemetry (phase timings, eval results, tree stats, checkpoint
and fault events) that bench.py and the distributed supervisor can
consume, plus watchdogs for the failure modes unique to the XLA runtime
(mid-training recompiles, HBM growth).  The performance-observatory
layer (ISSUE 11) adds WHAT THE CHIP DID to when it did it: compiled-HLO
flop/byte accounting per jitted entry (costmodel.py), a bounded ring of
recent iteration/serving history dumpable from dying processes
(flightrec.py), and a `/metrics` scrape surface (prom.py).

Knobs:
  * `train(metrics_dir=...)` / CLI `metrics_dir=` — JSONL event log
  * `profile_dir=` — brackets training with jax.profiler start/stop_trace
  * `roofline=` — compiled-cost harvesting + per-phase measured MFU
  * `metrics_port=` — Prometheus `GET /metrics` listener
  * `LIGHTGBM_TPU_TIMETAG=1` — the phase timers sync at phase boundaries
    (utils/timer.py; recording itself is always on)
  * `global_timer.set_trace_annotations(True)` — a
    jax.profiler.TraceAnnotation per scope
"""

from .compile_cache import (configure_compile_cache,
                            first_iter_compile_phases)
from .costmodel import (backend_peaks, enable_cost_model,
                        global_cost_model, roofline)
from .events import (EventLogger, emit_event, get_event_logger,
                     set_event_logger)
from .flightrec import (FlightRecorder, dump_flight_record,
                        flight_file_path, flight_recorder)
from .hostio import (AsyncWriter, clear_preemption_hook, flush_host_io,
                     install_sigterm_flush, set_preemption_hook)
from .prom import (parse_prometheus_text, render_prometheus,
                   start_metrics_http)
from .registry import MetricsRegistry, global_registry, process_rank
from .tracing import (SloTracker, SpanAssembler, TraceContext, make_span,
                      new_span_id, new_trace_id)
from .watchdog import (RecompileDetector, sample_device_memory,
                       update_memory_gauges)

__all__ = [
    "AsyncWriter", "configure_compile_cache", "first_iter_compile_phases",
    "backend_peaks", "enable_cost_model", "global_cost_model", "roofline",
    "EventLogger", "emit_event", "get_event_logger", "set_event_logger",
    "FlightRecorder", "dump_flight_record", "flight_file_path",
    "flight_recorder",
    "flush_host_io", "install_sigterm_flush",
    "set_preemption_hook", "clear_preemption_hook",
    "MetricsRegistry", "global_registry", "process_rank",
    "parse_prometheus_text", "render_prometheus", "start_metrics_http",
    "SloTracker", "SpanAssembler", "TraceContext", "make_span",
    "new_span_id", "new_trace_id",
    "RecompileDetector", "sample_device_memory", "update_memory_gauges",
]
