"""Profiling/tracing hooks (XLA profiler), adapted over the telemetry bus.

The reference ships no profiler hooks at all (SURVEY §5 "Tracing:
none"). Here: a trace context for whole runs and per-step annotations
that show up in the TPU trace viewer, attached at the step loop — the
hook point the survey names (the equivalent of ``distributed.py:141``).

Both hooks are thin adapters over :mod:`sparktorch_tpu.obs`: a
profiled run records a ``tracing.profile`` span (so the trace capture
cost itself is attributed) and step annotations bump a counter — the
existing call-site contract is unchanged.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax

from sparktorch_tpu.obs import get_telemetry


def trace_viewer_url(log_dir: str, host: str = "localhost",
                     port: int = 6006) -> str:
    """Ready-to-open TensorBoard/xprof deep link for a captured trace.

    The profile plugin lists runs by the path fragment under the
    logdir, so the URL pins the run to the trace just written; serving
    it is one command (``tensorboard --logdir <dir>`` or
    ``xprof --logdir <dir>``), which rides alongside on the event as
    ``view_cmd``. A regression in a JSONL stream or a ``/telemetry``
    scrape then links straight to its trace instead of a bare
    directory name (ROADMAP: trace-viewer deep links)."""
    import os
    import urllib.parse

    run = os.path.basename(os.path.normpath(log_dir)) or "."
    return (f"http://{host}:{port}/#profile"
            f"&run={urllib.parse.quote(run, safe='')}")


@contextlib.contextmanager
def profile_run(log_dir: Optional[str], telemetry=None,
                analyze: bool = True) -> Iterator[dict]:
    """Capture an XLA profiler trace for the enclosed block when
    ``log_dir`` is set; no-op otherwise. View with TensorBoard or
    xprof. The capture holds the device planes and, on the host, the
    annotations (every ``Telemetry.span`` by its path, the
    ``train_step`` steps, the runtime's own events); no Python call
    stacks.

    At stop time the capture is ALSO machine-read (``analyze=True``):
    :func:`sparktorch_tpu.obs.xprof.analyze_and_publish` slices the
    Chrome trace by the per-step annotations, attributes collective vs
    compute time, and publishes ``xprof.*`` metrics onto the bus — so
    the trace becomes queryable (``/metrics``, JSONL dumps) instead of
    TensorBoard-only. Yields a handle dict whose ``"analysis"`` key
    holds the :class:`TraceAnalysis` after exit (None when profiling
    is off, analysis is disabled, or the runtime emitted no trace)."""
    handle: dict = {"analysis": None}
    if not log_dir:
        yield handle
        return
    import time

    tele = telemetry or get_telemetry()
    tele.counter("tracing.profile_runs")
    # Baseline for the truncation detector: the delta of this counter
    # across the capture is how many step annotations the trace SHOULD
    # contain; fewer markers found means the profiler's event buffer
    # overflowed and dropped them (silent under-reporting otherwise).
    steps_before = tele.counter_value("tracing.annotated_steps")
    # Deliberately NOT a span: a span here would sit on the thread-
    # local stack for the whole run and re-path every trainer span
    # underneath it — metric names must not depend on whether
    # profiling happens to be on. A plain histogram attributes the
    # capture's wall cost instead.
    t0 = time.perf_counter()
    # Python tracer off, host tracer at the level of annotations: the
    # bus's spans, the ``train_step`` step annotations and the device
    # planes, which is all ``obs.xprof`` reads. At the profiler's
    # defaults one BERT-base ``train_distributed`` call wrote 7.9 M
    # Python events and its first chunk took 23.6 s (chip run, PR 23).
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield handle
    finally:
        jax.profiler.stop_trace()
        # log_dir is NOT a label: label values must stay simple tokens
        # (the name{k=v,...} flat-key spelling reserves ',' and '='),
        # and a filesystem path can contain both. The trace location
        # travels on the event instead.
        tele.observe("tracing.profile_s", time.perf_counter() - t0)
        url = trace_viewer_url(log_dir)
        # The URL ALSO lands in the snapshot's info section, so a
        # /telemetry scrape (param server or gang exporter) links
        # straight to the latest trace, not just the JSONL stream.
        tele.info("tracing.trace_url", url)
        tele.event("profile_trace", log_dir=log_dir, trace_url=url,
                   view_cmd=f"tensorboard --logdir {log_dir}")
        if analyze:
            # Failure-safe by contract (a missing/torn capture logs
            # and bumps xprof.analyze_failures, never raises).
            from sparktorch_tpu.obs.xprof import analyze_and_publish

            expected = int(
                tele.counter_value("tracing.annotated_steps") - steps_before
            )
            handle["analysis"] = analyze_and_publish(
                log_dir, telemetry=tele,
                expected_steps=expected if expected > 0 else None,
            )


def step_annotation(step: int, telemetry=None):
    """Per-step trace annotation; shows step boundaries in the trace
    viewer. Also counts dispatched steps on the bus (one cheap counter
    bump — safe on the hot path)."""
    (telemetry or get_telemetry()).counter("tracing.annotated_steps")
    return jax.profiler.StepTraceAnnotation("train_step", step_num=step)
