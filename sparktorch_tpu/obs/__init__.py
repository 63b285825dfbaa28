"""sparktorch_tpu.obs — the unified telemetry subsystem.

One bus (:class:`Telemetry`) shared by every trainer, the parameter
server and inference: nestable timed spans, monotonic
counters, histogram metrics with p50/p95/p99 roll-ups, gauges. Sinks
stream JSONL events; :func:`render_prometheus` serves the same state
from the param server's ``/metrics`` route; gang heartbeats give
multi-process runs per-rank liveness and step skew.
"""

from sparktorch_tpu.obs.telemetry import (
    Span,
    Telemetry,
    format_key,
    get_telemetry,
    set_telemetry,
    wall_ts,
)
from sparktorch_tpu.obs.history import MetricsHistory
from sparktorch_tpu.obs.alerts import AlertManager, AlertRule
from sparktorch_tpu.obs.blackbox import (
    FlightRecorder,
    attach_recorder,
    collect_postmortem,
    read_postmortem,
)
from sparktorch_tpu.obs.goodput import (
    GoodputLedger,
    LedgerSpan,
    device_peaks,
    mfu_honest,
)
from sparktorch_tpu.obs.health import (
    HealthConfig,
    TrainHealthLedger,
    health_alert_rules,
    tree_checksum,
)
from sparktorch_tpu.obs.replay import load_bundle, replay_bundle
from sparktorch_tpu.obs.sinks import JsonlSink, read_jsonl, write_jsonl
from sparktorch_tpu.obs.prom import (
    CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE,
    parse_prometheus,
    render_prometheus,
)
from sparktorch_tpu.obs.heartbeat import (
    HEARTBEAT_DIR_ENV,
    HeartbeatEmitter,
    gang_report,
    read_heartbeats,
)
from sparktorch_tpu.obs.log import get_logger
from sparktorch_tpu.obs.xprof import (
    GangAnalysis,
    TraceAnalysis,
    TraceParseError,
    analyze_and_publish,
    analyze_trace,
    merge_analyses,
)
from sparktorch_tpu.obs.collector import (
    FleetCollector,
    ScrapeError,
    mint_run_id,
    run_tag,
    scrape_json,
    scrape_text,
    snapshot_histogram,
)
from sparktorch_tpu.obs.rpctrace import (
    RpcTracer,
    SpanContext,
    critical_path,
    critical_summary,
    stitch_spans,
    tracer_for,
    write_chrome_trace,
)

__all__ = [
    "Span",
    "Telemetry",
    "format_key",
    "get_telemetry",
    "set_telemetry",
    "wall_ts",
    "MetricsHistory",
    "AlertManager",
    "AlertRule",
    "FlightRecorder",
    "attach_recorder",
    "collect_postmortem",
    "read_postmortem",
    "GoodputLedger",
    "LedgerSpan",
    "device_peaks",
    "mfu_honest",
    "HealthConfig",
    "TrainHealthLedger",
    "health_alert_rules",
    "tree_checksum",
    "load_bundle",
    "replay_bundle",
    "JsonlSink",
    "read_jsonl",
    "write_jsonl",
    "PROMETHEUS_CONTENT_TYPE",
    "parse_prometheus",
    "render_prometheus",
    "HEARTBEAT_DIR_ENV",
    "HeartbeatEmitter",
    "gang_report",
    "read_heartbeats",
    "get_logger",
    "GangAnalysis",
    "TraceAnalysis",
    "TraceParseError",
    "analyze_and_publish",
    "analyze_trace",
    "merge_analyses",
    "FleetCollector",
    "ScrapeError",
    "mint_run_id",
    "run_tag",
    "scrape_json",
    "scrape_text",
    "snapshot_histogram",
    "RpcTracer",
    "SpanContext",
    "critical_path",
    "critical_summary",
    "stitch_spans",
    "tracer_for",
    "write_chrome_trace",
]
