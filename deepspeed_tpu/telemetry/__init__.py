"""Unified telemetry layer: per-step StepRecords, shared metric
primitives, JSONL/Prometheus/monitor export, and budgeted auto-capture
overlap reports.  See docs/OBSERVABILITY.md.

``capture`` (and only it) pulls ``jax`` via utils/trace — it is loaded
lazily so that jax-free consumers (serving/metrics.py imports the
registry; PR-2's invariant is that serving/ never imports jax) stay
jax-free.
"""

from deepspeed_tpu.telemetry.export import (EXPORT_TAGS, JsonlExporter,
                                            Telemetry, events_from_record,
                                            read_jsonl, render_prometheus,
                                            write_prometheus_textfile)
from deepspeed_tpu.telemetry.flight import (FLIGHT_REASONS, FlightRecorder,
                                            Watchdog, dump_bundle,
                                            make_span_recorder)
from deepspeed_tpu.telemetry.record import (SCHEMA_VERSION, StepRecord,
                                            collect_hbm_stats,
                                            detect_peak_flops_per_sec,
                                            record_keys)
from deepspeed_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                              MetricsRegistry)
from deepspeed_tpu.telemetry.slo import (SLO_BLOCK_KEYS, SLO_LEDGER_KEYS,
                                         SLO_SCENARIO_KEYS,
                                         SLO_TARGET_KEYS, SLOLedger,
                                         SLOSpec)
from deepspeed_tpu.telemetry.tracing import (EVENT_NAMES, NULL_SPAN,
                                             NULL_TRACER, SPAN_NAMES,
                                             STAGE_NAMES, Span, Tracer)

_LAZY = ("AutoCapture", "build_capture_report")


def __getattr__(name):
    if name in _LAZY:
        from deepspeed_tpu.telemetry import capture

        return getattr(capture, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "AutoCapture", "Counter", "EVENT_NAMES", "EXPORT_TAGS",
    "FLIGHT_REASONS", "FlightRecorder", "Gauge", "Histogram",
    "JsonlExporter", "MetricsRegistry", "NULL_SPAN", "NULL_TRACER",
    "SCHEMA_VERSION", "SLOLedger", "SLOSpec", "SLO_BLOCK_KEYS",
    "SLO_LEDGER_KEYS", "SLO_SCENARIO_KEYS", "SLO_TARGET_KEYS",
    "SPAN_NAMES", "STAGE_NAMES", "Span", "StepRecord", "Telemetry", "Tracer",
    "Watchdog",
    "build_capture_report", "collect_hbm_stats",
    "detect_peak_flops_per_sec", "dump_bundle", "events_from_record",
    "make_span_recorder", "read_jsonl", "record_keys", "render_prometheus",
    "write_prometheus_textfile",
]
