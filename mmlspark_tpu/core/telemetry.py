"""Unified telemetry plane: metric registry, trace spans, flight
recorder, retrace watchdog.

The reference's only observability was the Timer stage's wall-clock
logging (SURVEY.md §5). This module is the shared layer every plane of
the reproduction records into — the serving engine emits one span per
request lifecycle, the trainer records step-time/loss/grad-norm
histograms, and ``bench.py``/the CLI persist ``events.jsonl`` +
``metrics.json`` under ``--telemetry-dir`` — following the lineage's
production systems (TensorFlow ships structured runtime metrics and
tracing as core infrastructure, arXiv:1605.08695 §9).

Four pieces, deliberately dependency-free (stdlib only; jax is touched
lazily and only by the watchdog's shape formatter):

- :class:`MetricRegistry` with :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` primitives. Histograms use DETERMINISTIC
  log-bucketed bins: same samples -> same quantiles, independent of
  arrival order, with bounded relative error (one bucket's growth
  factor) and exact count/sum/min/max.
- :class:`Span` + :class:`SpanTracer`: structured events (name, attrs,
  tick, monotonic wall time) grouped by span id; and
  :meth:`SpanTracer.region`, the one way a hot path marks a host
  INTERVAL: a ``jax.profiler`` annotation on the device trace's clock
  and one recorder event on the recorder's.
- :class:`FlightRecorder`: a bounded ring buffer of those events that
  can dump the last N as JSON-lines on demand
  (:meth:`FlightRecorder.dump`) and automatically when a
  :class:`FriendlyError` escapes a guarded block
  (:meth:`FlightRecorder.dump_on_friendly_error`) — the post-mortem
  answer to "why was this request slow / what happened right before
  the failure".
- :class:`RetraceWatchdog`: wraps a jitted callable (reusing
  ``testing/compile_guard.py``'s program counting) and logs every NEW
  XLA compilation with the triggering abstract shapes/dtypes — silent
  retraces are the classic TPU serving regression and this makes them
  loud at the moment they happen.

``utils/profiling.py`` re-exports everything here next to
``trace_profile``, so call sites have one observability import.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Iterator

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.core.metrics_contracts import MetricData
from mmlspark_tpu.testing.compile_guard import backend_compiles

_log = get_logger("telemetry")


def atomic_write_text(path: str, text: str) -> None:
    """Torn-write-safe text dump: write to a tmp file in the target
    directory, fsync, then ``os.replace`` onto the final name — the
    same commit-point idiom as ``AtomicCheckpointStore``
    (train/resilience.py), so a kill mid-dump leaves either the
    previous file or the complete new one, never a half-written
    telemetry bundle."""
    path = os.path.abspath(path)
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path),
        prefix=os.path.basename(path) + ".", suffix=".tmp",
    )
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # the commit point
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def atomic_write_json(path: str, doc, **dump_kwargs) -> None:
    """:func:`atomic_write_text` for a JSON document."""
    atomic_write_text(path, json.dumps(doc, **dump_kwargs))


# --------------------------------------------------------------------------
# metric primitives
# --------------------------------------------------------------------------


class Counter:
    """Monotonic counter. ``inc`` only; resets belong to a new registry."""

    def __init__(self, name: str):
        self.name = name
        self._value = 0

    def inc(self, n: int = 1) -> None:
        self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-write-wins scalar (queue depth, utilization, ...)."""

    def __init__(self, name: str):
        self.name = name
        self._value: float | None = None

    def set(self, value: float) -> None:
        self._value = float(value)

    @property
    def value(self) -> float | None:
        return self._value


class Histogram:
    """Log-bucketed latency/size histogram with deterministic quantiles.

    Buckets are fixed at construction: bucket ``i`` covers
    ``(lo * growth**(i-1), lo * growth**i]``, values ``<= lo`` land in
    bucket 0 and values above the top edge in the last (overflow)
    bucket. Quantiles walk the cumulative counts and return the
    bucket's geometric midpoint, clamped into the exactly-tracked
    ``[min, max]`` — so two histograms fed the same samples in ANY
    order report identical p50/p95/p99, and the relative error is
    bounded by one ``growth`` factor (default 10%).
    """

    def __init__(self, name: str, *, lo: float = 1e-3, hi: float = 1e8,
                 growth: float = 1.1):
        if not (lo > 0 and hi > lo and growth > 1.0):
            raise FriendlyError(
                f"histogram '{name}' needs 0 < lo < hi and growth > 1, "
                f"got lo={lo} hi={hi} growth={growth}"
            )
        self.name = name
        self.lo = lo
        self.growth = growth
        self._log_growth = math.log(growth)
        self.n_buckets = 2 + math.ceil(math.log(hi / lo) / self._log_growth)
        self._counts = [0] * self.n_buckets
        self.count = 0
        self.sum = 0.0
        self.min: float | None = None
        self.max: float | None = None

    def _bucket(self, value: float) -> int:
        if value <= self.lo:
            return 0
        idx = 1 + int(math.ceil(math.log(value / self.lo) / self._log_growth
                                - 1e-12))
        return min(idx, self.n_buckets - 1)

    def record(self, value: float) -> None:
        value = float(value)
        self._counts[self._bucket(value)] += 1
        self.count += 1
        self.sum += value
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)

    def percentile(self, p: float) -> float | None:
        """Deterministic quantile estimate; None while empty."""
        if not self.count:
            return None
        rank = max(1, math.ceil(p / 100.0 * self.count))
        seen = 0
        for i, c in enumerate(self._counts):
            seen += c
            if seen >= rank:
                if i == 0:
                    est = self.lo
                else:
                    # geometric midpoint of the bucket's edges
                    est = self.lo * self.growth ** (i - 0.5)
                return min(max(est, self.min), self.max)
        return self.max  # unreachable; defensive

    @property
    def mean(self) -> float | None:
        return (self.sum / self.count) if self.count else None

    def bucket_bounds(self) -> list[float | str]:
        """Upper edge of each bucket, aligned with :meth:`bucket_counts`.
        Bucket 0's edge is ``lo`` (values ``<= lo``), the overflow
        bucket's is the string ``"+Inf"`` (JSON has no Infinity; the
        spelling matches Prometheus' ``le`` label)."""
        edges: list[float | str] = [self.lo]
        for i in range(1, self.n_buckets - 1):
            edges.append(self.lo * self.growth ** i)
        edges.append("+Inf")
        return edges

    def bucket_counts(self) -> list[int]:
        """Per-bucket observation counts (NOT cumulative), aligned with
        :meth:`bucket_bounds`."""
        return list(self._counts)

    def summary(self) -> dict:
        # buckets export only the OCCUPIED range (trailing empties after
        # the last non-zero are dropped, leading empties kept so edges
        # still align by index) — a default histogram has ~530 bins and
        # dashboards only want the populated ones
        last = 0
        for i, c in enumerate(self._counts):
            if c:
                last = i + 1
        bounds = self.bucket_bounds()[:last]
        return {
            "count": self.count,
            "sum": round(self.sum, 6),
            "mean": round(self.mean, 6) if self.count else None,
            "min": self.min,
            "max": self.max,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
            "p99": self.percentile(99),
            "buckets": {
                "bounds": [
                    b if isinstance(b, str) else round(b, 9)
                    for b in bounds
                ],
                "counts": self._counts[:last],
            },
        }


class MetricRegistry:
    """Name -> metric map; get-or-create with type checking.

    One process-wide default lives behind :func:`default_registry`;
    subsystems that need isolation (one registry per ``ServeEngine``,
    per ``SPMDTrainer``) construct their own.
    """

    def __init__(self):
        self._metrics: dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, name: str, cls, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, **kwargs)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise FriendlyError(
                    f"metric '{name}' is already registered as "
                    f"{type(m).__name__}, not {cls.__name__}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get_or_create(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get_or_create(name, Gauge)

    def histogram(self, name: str, **kwargs) -> Histogram:
        return self._get_or_create(name, Histogram, **kwargs)

    def get(self, name: str):
        return self._metrics.get(name)

    def names(self) -> list[str]:
        # locked: a MetricsServer scrape thread iterates while the
        # serving loop may be registering a new metric
        with self._lock:
            return sorted(self._metrics)

    def to_dict(self) -> dict:
        """Flat JSON-able view: counters/gauges as scalars, histograms
        expanded to ``<name>_{count,mean,p50,p95,p99}``."""
        out: dict[str, Any] = {}
        for name in self.names():
            m = self._metrics[name]
            if isinstance(m, Histogram):
                s = m.summary()
                for k in ("count", "mean", "p50", "p95", "p99"):
                    out[f"{name}_{k}"] = s[k]
            else:
                out[name] = m.value
        return out

    def prom_series(
        self, labels: dict | None = None,
    ) -> Iterator[tuple[str, str, list[str]]]:
        """Per-metric exposition pieces: ``(prom_name, type, sample
        lines)``, with ``labels`` rendered (escaped) on EVERY sample
        line. The building block both :meth:`to_prometheus` and the
        hub's merged label-based exposition
        (:class:`mmlspark_tpu.core.tracehub.TelemetryHub`) assemble
        from — the hub groups series from N registries by name, emits
        one ``# TYPE`` header per name, and distinguishes sources by
        ``{replica="0"}``-style labels instead of name prefixes."""
        for name in self.names():
            m = self._metrics[name]
            pname = _prom_name(name)
            lbl = _prom_labels(labels)
            if isinstance(m, Counter):
                # counters whose dotted name already carries the
                # conventional suffix (train.retries_total) must not
                # come out double-suffixed
                if not pname.endswith("_total"):
                    pname += "_total"
                yield pname, "counter", [f"{pname}{lbl} {m.value}"]
            elif isinstance(m, Gauge):
                if m.value is None:
                    continue
                yield pname, "gauge", [f"{pname}{lbl} {_prom_num(m.value)}"]
            elif isinstance(m, Histogram):
                lines: list[str] = []
                cum = 0
                bounds = m.bucket_bounds()
                for edge, c in zip(bounds, m.bucket_counts()):
                    cum += c
                    if c == 0 and edge != "+Inf":
                        continue  # occupied edges + +Inf keep it short
                    le = edge if isinstance(edge, str) else _prom_num(edge)
                    blbl = _prom_labels(labels, {"le": le})
                    lines.append(f"{pname}_bucket{blbl} {cum}")
                if bounds[-1] != "+Inf" or not m.bucket_counts():
                    blbl = _prom_labels(labels, {"le": "+Inf"})
                    lines.append(f"{pname}_bucket{blbl} {m.count}")
                lines.append(f"{pname}_sum{lbl} {_prom_num(m.sum)}")
                lines.append(f"{pname}_count{lbl} {m.count}")
                yield pname, "histogram", lines

    def to_prometheus(self, labels: dict | None = None) -> str:
        """Prometheus text exposition (format 0.0.4) for live scraping.

        Dotted metric names become underscore-separated
        (``serve.ttft_ms`` -> ``serve_ttft_ms``); counters get the
        conventional ``_total`` suffix; histograms emit CUMULATIVE
        ``_bucket{le="..."}`` series (one per occupied log-bucket edge
        plus ``+Inf``) with ``_sum`` and ``_count`` — real
        distributions, not three precomputed quantiles. ``labels``
        stamps every sample line (values escaped per the exposition
        format) — the hub's per-source dimension
        (docs/OBSERVABILITY.md "Prometheus scraping")."""
        out: list[str] = []
        for pname, mtype, lines in self.prom_series(labels):
            out.append(f"# TYPE {pname} {mtype}")
            out.extend(lines)
        return "\n".join(out) + ("\n" if out else "")

    def snapshot(self, model: str | None = None,
                 group: str | None = None) -> list[MetricData]:
        """Structured records: scalars via ``MetricData.create``-style
        rows, histograms as ``MetricData.create_table`` summaries."""
        out: list[MetricData] = []
        for name in self.names():
            m = self._metrics[name]
            if isinstance(m, Histogram):
                out.append(MetricData.create_table(name, m.summary(), model))
            elif m.value is not None:
                out.append(MetricData(name=name, value=float(m.value),
                                      model=model, group=group))
        return out


def _prom_name(name: str) -> str:
    """Registry names are dotted; Prometheus names are
    ``[a-zA-Z_:][a-zA-Z0-9_:]*``."""
    cleaned = "".join(
        c if c.isalnum() or c in "_:" else "_" for c in name
    )
    if cleaned and cleaned[0].isdigit():
        cleaned = "_" + cleaned
    return cleaned or "_"


def _prom_num(value: float) -> str:
    """Shortest faithful rendering: integers without the trailing
    ``.0``, floats via repr (round-trippable)."""
    f = float(value)
    return str(int(f)) if f.is_integer() and abs(f) < 1e15 else repr(f)


def _prom_escape_label_value(value) -> str:
    """Label-VALUE escaping per the text exposition format 0.0.4:
    backslash, double-quote and newline must be escaped inside the
    quoted value (``model="a\\"b"`` would otherwise tear the line).
    Everything else passes through verbatim."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _prom_labels(labels: dict | None, extra: dict | None = None) -> str:
    """``{replica="0",le="+Inf"}``-style rendering (escaped, insertion
    order preserved); empty string when there are no labels."""
    items = {**(labels or {}), **(extra or {})}
    if not items:
        return ""
    inner = ",".join(
        f'{_prom_name(str(k))}="{_prom_escape_label_value(v)}"'
        for k, v in items.items()
    )
    return "{" + inner + "}"


class NamespacedRegistry:
    """A prefixing view over a shared :class:`MetricRegistry`.

    Every ``counter``/``gauge``/``histogram`` name is prefixed with
    ``namespace`` before reaching the inner registry, so N subsystems
    can share ONE registry — and therefore one Prometheus exposition —
    with zero name collisions. Unlike :class:`ServeMetrics`'s
    ``namespace=`` argument (which prefixes only the ``serve.*`` names
    it creates itself), this view also covers metrics that third
    parties register against the handed-in registry (``perf.*`` from
    PerfAnalytics, ``slo.*`` from SloMonitor, retrace counters) — the
    mechanism the multi-model engine uses to give every deployment its
    ``model{name}.``-prefixed metric tree (serve/multimodel.py).

    Read-side methods (``to_dict``/``to_prometheus``/``snapshot``)
    delegate to the WHOLE inner registry: any view is a handle on the
    one shared exposition.
    """

    def __init__(self, inner: MetricRegistry, namespace: str):
        self._inner = inner
        self.namespace = namespace

    def counter(self, name: str) -> Counter:
        return self._inner.counter(f"{self.namespace}{name}")

    def gauge(self, name: str) -> Gauge:
        return self._inner.gauge(f"{self.namespace}{name}")

    def histogram(self, name: str, **kwargs) -> Histogram:
        return self._inner.histogram(f"{self.namespace}{name}", **kwargs)

    def get(self, name: str):
        return self._inner.get(f"{self.namespace}{name}")

    def names(self) -> list[str]:
        return self._inner.names()

    def to_dict(self) -> dict:
        return self._inner.to_dict()

    def to_prometheus(self, labels: dict | None = None) -> str:
        return self._inner.to_prometheus(labels)

    def prom_series(self, labels: dict | None = None):
        return self._inner.prom_series(labels)

    def snapshot(self, model: str | None = None,
                 group: str | None = None):
        return self._inner.snapshot(model=model, group=group)


_DEFAULT_REGISTRY = MetricRegistry()


def default_registry() -> MetricRegistry:
    """The process-wide registry (ad-hoc call sites; subsystems that
    need isolation build their own)."""
    return _DEFAULT_REGISTRY


# --------------------------------------------------------------------------
# spans + flight recorder
# --------------------------------------------------------------------------


class FlightRecorder:
    """Bounded ring buffer of structured events.

    Each event is one flat dict: ``t`` (monotonic seconds), ``name``,
    optional ``tick`` / ``span`` / ``span_name``, and a nested
    ``attrs`` dict. The buffer keeps the LAST ``capacity`` events
    (``dropped`` counts evictions) so a long-running engine's recorder
    is always a post-mortem of the recent past, never an unbounded log.
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise FriendlyError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._events: deque[dict] = deque(maxlen=capacity)
        self.dropped = 0
        self._lock = threading.Lock()
        # wall-clock anchor: unix epoch seconds at monotonic zero, so
        # any event's absolute time is t0_unix + ev["t"]. Events keep
        # carrying ONLY monotonic seconds (cheap, ordering-safe); the
        # anchor is stamped once here and exported by dump() headers and
        # trace exports, which is what lets events.jsonl from different
        # processes — or an engine restored from a snapshot — be
        # correlated on one timeline.
        self.t0_unix = time.time() - time.monotonic()

    def record(self, name: str, *, tick: int | None = None,
               span: int | None = None, span_name: str | None = None,
               **attrs) -> None:
        ev: dict[str, Any] = {"t": time.monotonic(), "name": name}
        if tick is not None:
            ev["tick"] = tick
        if span is not None:
            ev["span"] = span
        if span_name is not None:
            ev["span_name"] = span_name
        if attrs:
            ev["attrs"] = attrs
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def dump(self, path: str | None = None) -> str:
        """The last N events as JSON-lines; written to ``path`` when
        given, returned either way. The first line is a header record
        (``{"header": "flight_recorder", "t0_unix": ..., ...}``)
        carrying the wall-clock anchor — consumers add ``t0_unix`` to
        any event's monotonic ``t`` for absolute time."""
        events = self.events()
        header = json.dumps({
            "header": "flight_recorder",
            "t0_unix": round(self.t0_unix, 6),
            "events": len(events),
            "dropped": self.dropped,
            "capacity": self.capacity,
        })
        lines = "\n".join(
            [header] + [json.dumps(ev, default=str) for ev in events]
        ) + "\n"
        if path is not None:
            atomic_write_text(path, lines)
            _log.info("flight recorder: %d events -> %s",
                      len(self._events), path)
        return lines

    @contextlib.contextmanager
    def dump_on_friendly_error(
        self, path: str | None = None,
        exc_types: tuple = (FriendlyError,),
    ) -> Iterator["FlightRecorder"]:
        """Re-raise any :class:`FriendlyError` escaping the block after
        dumping the ring buffer — the black-box recorder contract: the
        crash itself triggers the evidence dump."""
        try:
            yield self
        except exc_types as e:
            dumped = self.dump(path)
            if path is None:
                _log.error(
                    "flight recorder dump on %s (last %d events):\n%s",
                    type(e).__name__, len(self._events), dumped,
                )
            raise


class Span:
    """One traced unit of work (a serve request, a train step group).

    Not a context manager on purpose: serving spans live across many
    engine ticks, so the lifecycle is explicit — ``event()`` per phase,
    ``end()`` exactly once with the terminal status.
    """

    def __init__(self, recorder: FlightRecorder, name: str, span_id: int,
                 tick: int | None = None, **attrs):
        self._recorder = recorder
        self.name = name
        self.id = span_id
        self.t0 = time.monotonic()
        self.ended = False
        self._recorder.record("start", tick=tick, span=span_id,
                              span_name=name, **attrs)

    def event(self, name: str, *, tick: int | None = None, **attrs) -> None:
        self._recorder.record(name, tick=tick, span=self.id,
                              span_name=self.name, **attrs)

    def end(self, status: str = "ok", *, tick: int | None = None,
            **attrs) -> None:
        if self.ended:
            return
        self.ended = True
        self._recorder.record(
            status, tick=tick, span=self.id, span_name=self.name,
            duration_ms=round((time.monotonic() - self.t0) * 1e3, 3),
            **attrs,
        )


class Region:
    """One host interval of a hot path, recorded twice: as a
    ``jax.profiler`` annotation on the device trace's clock while a
    profiler session runs, and as ONE flight-recorder event on exit.
    Made by :meth:`SpanTracer.region`; ``t0`` and ``t1`` (monotonic
    seconds) and ``ms`` are the caller's to read once the block has
    ended, so a loop that needs the interval takes no clock of its own.
    """

    __slots__ = ("name", "tick", "t0", "t1", "_tracer", "_annotation",
                 "_attrs", "_stack", "_compiles0", "_dropped")

    def __init__(self, tracer: "SpanTracer", name: str, tick: int | None,
                 annotation, attrs: dict):
        self.name, self.tick = name, tick
        self.t0 = self.t1 = None
        self._tracer, self._annotation, self._attrs = tracer, annotation, attrs
        self._dropped = False

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    def count(self, **counts) -> None:
        """Numbers (or a late ``request=``) the block learned on its way,
        for the event's ``attrs``."""
        self._attrs.update(counts)

    def drop(self) -> None:
        """Record no event on exit: the interval turned out to hold no
        work (the trainer's pull that found the epoch's end)."""
        self._dropped = True

    def __enter__(self) -> "Region":
        self._stack = self._tracer._open_regions()
        if self._stack:
            self._attrs["parent"] = self._stack[-1].name
        self._stack.append(self)
        self._compiles0 = backend_compiles()
        self._annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.monotonic()
        self._annotation.__exit__(exc_type, exc, tb)
        self._stack.pop()
        if self._dropped:
            return False
        attrs = self._attrs
        compiles = backend_compiles() - self._compiles0
        if compiles:
            attrs["compiles"] = compiles
        if exc_type is not None:
            attrs["error"] = exc_type.__name__
        self._tracer.recorder.record(
            self.name, tick=self.tick, t0=self.t0,
            ms=round(self.ms, 3), **attrs,
        )
        return False   # transparent: whatever was raised goes on


class SpanTracer:
    """Hands out :class:`Span` objects with process-unique ids over one
    :class:`FlightRecorder`, and :class:`Region` intervals over the same
    recorder."""

    def __init__(self, recorder: FlightRecorder):
        self.recorder = recorder
        self._next_id = 0
        self._lock = threading.Lock()
        self._local = threading.local()

    def span(self, name: str, *, tick: int | None = None, **attrs) -> Span:
        with self._lock:
            sid = self._next_id
            self._next_id += 1
        return Span(self.recorder, name, sid, tick=tick, **attrs)

    def _open_regions(self) -> list:
        """This thread's open regions, outermost first."""
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def region(self, name: str, *, tick: int | None = None,
               request: int | None = None, **attrs) -> Region:
        """The one way a hot path marks a host interval::

            with tracer.region("serve.pool_write", request=rid) as r:
                ...
                r.count(dispatches=n)

        On entry a ``jax.profiler.TraceAnnotation(name)`` opens with
        ``tick`` and ``request`` as its metadata: inside a profiler
        session the interval lies in the trace's host plane, on the
        device's clock, where idle gaps of the chip can be laid under
        it. On exit ONE recorder event ``name`` is written, with
        ``tick`` and, in ``attrs``: ``t0`` (the start,
        ``time.monotonic()``), ``ms``, ``parent`` (the enclosing region
        of this thread; absent at the top), ``request`` (the id that
        the intervals of one request share), ``compiles`` (backend
        compiles the process made inside; absent when 0), ``error``
        (the type of an exception that passed through; it is never
        swallowed), the keyword ``attrs`` and whatever ``count()`` set.
        The recorder's event is there with the profiler off.

        ``name`` is ``<layer>.<what>``: the dot keeps it apart from the
        lifecycle events (``prefill``, ``decode``, ``tick``, ``step``
        ...) that readers of the recorder filter on."""
        import jax

        meta = {}
        if tick is not None:
            meta["tick"] = tick
        if request is not None:
            meta["request"] = attrs["request"] = request
        return Region(self, _region_name(name), tick,
                      jax.profiler.TraceAnnotation(name, **meta), attrs)

    def step_region(self, name: str, *, tick: int) -> Region:
        """A :meth:`region` that is one step of a training loop: its
        annotation is ``jax.profiler.StepTraceAnnotation(name,
        step_num=tick)``, which the profiler's own step view reads."""
        import jax

        return Region(self, _region_name(name), tick,
                      jax.profiler.StepTraceAnnotation(name, step_num=tick),
                      {})


def _region_name(name: str) -> str:
    if "." not in name:
        raise FriendlyError(
            f"a region is named '<layer>.<what>' (got {name!r}): a bare "
            "name could pass for one of the recorder's lifecycle events"
        )
    return name


# --------------------------------------------------------------------------
# retrace watchdog
# --------------------------------------------------------------------------


def _describe_abstract(args: tuple, kwargs: dict, limit: int = 12) -> str:
    """``bf16[4,64,2,16]``-style rendering of a call's array leaves —
    the abstract signature jax traced, which is exactly what decides
    whether a call hits the jit cache."""
    import numpy as np

    try:
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:  # noqa: BLE001 — formatting must never raise
        leaves = [a for a in args if hasattr(a, "shape")]
    parts = []
    for leaf in leaves[:limit]:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            parts.append(repr(leaf)[:32])
            continue
        # NB: the fallback must stay lazy — np.asarray() as an eager
        # getattr default would force a device->host sync per leaf on
        # every watchdog-wrapped dispatch
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            try:
                dtype = np.asarray(leaf).dtype
            except Exception:  # noqa: BLE001 — formatting must never raise
                dtype = "?"
        parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
    if len(leaves) > limit:
        parts.append(f"... +{len(leaves) - limit} leaves")
    return ", ".join(parts)


class RetraceWatchdog:
    """Wrap a jitted callable; log every NEW XLA compilation.

    Counting reuses the same ``jitted._cache_size()`` contract
    ``testing/compile_guard.py`` pins invariants with
    (:func:`mmlspark_tpu.testing.compile_guard.jit_cache_size`): the
    cache size is sampled after each call, and growth means the call's
    abstract shapes/dtypes missed the cache — programs within the
    ``expected_programs`` budget log at INFO (expected warm-up: 1 for a
    truly-fused step, the ladder/bucket count for a program family like
    the serve engine's fused decode blocks), every later one at WARNING
    (a retrace the design probably forbids), all with the triggering
    signature. Optionally mirrors into a registry counter and a
    flight-recorder event, so a retrace shows up in the same
    ``events.jsonl`` timeline as the request that caused it.
    """

    def __init__(self, fn: Callable, label: str, *,
                 registry: MetricRegistry | None = None,
                 recorder: FlightRecorder | None = None,
                 expected_programs: int = 1):
        from mmlspark_tpu.testing.compile_guard import jit_cache_size

        self._fn = fn
        self._size_of = jit_cache_size
        self.label = label
        self.compilations = 0  # programs seen by THIS wrapper
        self.expected_programs = max(1, expected_programs)
        self._counter = (
            registry.counter(f"retrace.{label}")
            if registry is not None else None
        )
        self._recorder = recorder
        self._seen = max(0, jit_cache_size(fn))

    @property
    def retraces(self) -> int:
        """Compilations beyond the expected program budget."""
        return max(0, self.compilations - self.expected_programs)

    def _cache_size(self) -> int:
        """compile_guard-compatible counting passthrough."""
        return self._size_of(self._fn)

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        n = self._size_of(self._fn)
        if n > self._seen:
            new = n - self._seen
            self.compilations += new
            self._seen = n
            sig = _describe_abstract(args, kwargs)
            level = (
                _log.info
                if self.compilations <= self.expected_programs
                else _log.warning
            )
            level(
                "retrace[%s]: %d new XLA program(s) compiled (total %d) "
                "for abstract signature (%s)",
                self.label, new, n, sig,
            )
            if self._counter is not None:
                self._counter.inc(new)
            if self._recorder is not None:
                self._recorder.record(
                    "retrace", label=self.label, new_programs=new,
                    total_programs=n, signature=sig,
                )
        return out


def watch_retrace(fn: Callable, label: str, *,
                  registry: MetricRegistry | None = None,
                  recorder: FlightRecorder | None = None) -> RetraceWatchdog:
    """Functional spelling of :class:`RetraceWatchdog` (``jax.jit``-like
    wrap-at-definition call sites read better with a function)."""
    return RetraceWatchdog(fn, label, registry=registry, recorder=recorder)
