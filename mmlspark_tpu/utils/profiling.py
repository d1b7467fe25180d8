"""Profiling hooks: jax.profiler traces around pipeline work, plus the
unified telemetry plane's public names.

The reference's only tracing is the Timer stage's wall-clock logging
(pipeline-stages/src/main/scala/Timer.scala:14-123) — no sampling profiler
exists (SURVEY.md §5). The TPU build keeps Timer and adds the natural
upgrade the survey calls for: XLA-level traces via ``jax.profiler``,
viewable in TensorBoard/Perfetto, capturing compilation, device compute,
and host↔device transfers.

The structured side — metric registry with latency histograms, trace
spans, the flight recorder, and the retrace watchdog — lives in
:mod:`mmlspark_tpu.core.telemetry` (docs/OBSERVABILITY.md) and is
re-exported here so call sites have ONE observability import next to
the jax.profiler hooks.
"""

from __future__ import annotations

import contextlib
import os

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.core.perf import (  # noqa: F401 — re-exports
    DevicePeak,
    PerfAnalytics,
    ProgramCost,
    SloMonitor,
    SloTargets,
    analyze_jit_cost,
    device_peak,
    export_chrome_trace,
    parse_slo_spec,
)
from mmlspark_tpu.core.telemetry import (  # noqa: F401 — re-exports
    Counter,
    FlightRecorder,
    Gauge,
    Histogram,
    MetricRegistry,
    Region,
    RetraceWatchdog,
    Span,
    SpanTracer,
    default_registry,
    watch_retrace,
)

_log = get_logger("profiling")


@contextlib.contextmanager
def trace_profile(log_dir: str, create_perfetto_link: bool = False):
    """Context manager writing a jax.profiler trace under ``log_dir``.

    Usage::

        with trace_profile("/tmp/trace"):
            model.transform(ds)   # device work captured
    """
    import jax

    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(
        log_dir, create_perfetto_link=create_perfetto_link
    ):
        yield log_dir
    _log.info("profiler trace written under %s", log_dir)
