"""Unified telemetry plane (core/telemetry): deterministic histogram
quantiles, the flight recorder's ring-buffer + dump-on-error contract,
span lifecycles, and the retrace watchdog — plus the serve wiring
(``--telemetry-dir`` artifacts, ``record_reject`` wall-clock fix,
``snapshot()`` table records)."""

import json
import logging
import random
import time

import numpy as np
import pytest

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.telemetry import (
    FlightRecorder,
    Histogram,
    MetricRegistry,
    RetraceWatchdog,
    SpanTracer,
)
from mmlspark_tpu.serve.metrics import ServeMetrics

# -- histogram primitives ---------------------------------------------------


def test_histogram_percentiles_are_order_independent():
    """Same samples in ANY arrival order -> byte-identical summaries;
    that determinism is the whole point of log-bucketed bins."""
    rng = random.Random(7)
    samples = [rng.lognormvariate(2.0, 1.5) for _ in range(500)]
    summaries = []
    for _ in range(3):
        rng.shuffle(samples)
        h = Histogram("t")
        for v in samples:
            h.record(v)
        summaries.append(h.summary())
    assert summaries[0] == summaries[1] == summaries[2]


def test_histogram_relative_error_bounded_by_growth():
    rng = random.Random(3)
    samples = [rng.uniform(0.5, 400.0) for _ in range(2000)]
    h = Histogram("t", growth=1.1)
    for v in samples:
        h.record(v)
    for p in (50, 95, 99):
        exact = float(np.percentile(samples, p))
        est = h.percentile(p)
        assert abs(est - exact) / exact < 0.12, (p, est, exact)
    # count/sum/min/max are exact, not bucketed
    assert h.count == len(samples)
    assert h.min == min(samples) and h.max == max(samples)
    assert h.sum == pytest.approx(sum(samples))


def test_histogram_edges_and_empty():
    h = Histogram("t")
    assert h.percentile(50) is None and h.mean is None
    h.record(0.0)  # underflow bucket: values <= lo
    assert h.percentile(50) == 0.0  # clamped into exact [min, max]
    h2 = Histogram("t2")
    h2.record(1e12)  # overflow bucket: clamped to exact max
    assert h2.percentile(99) == 1e12
    with pytest.raises(FriendlyError):
        Histogram("bad", lo=0.0)
    with pytest.raises(FriendlyError):
        Histogram("bad", growth=1.0)


def test_registry_get_or_create_and_type_conflict():
    r = MetricRegistry()
    c = r.counter("a")
    c.inc(3)
    assert r.counter("a") is c and r.counter("a").value == 3
    r.gauge("g").set(2.5)
    r.histogram("h").record(10.0)
    with pytest.raises(FriendlyError, match="already registered"):
        r.histogram("a")
    d = r.to_dict()
    assert d["a"] == 3 and d["g"] == 2.5
    # histograms expand to <name>_{count,mean,p50,p95,p99}
    assert d["h_count"] == 1 and d["h_p50"] == 10.0
    json.dumps(d)
    names = {m.name for m in r.snapshot(model="m", group="test")}
    assert names == {"a", "g", "h"}


# -- flight recorder + spans ------------------------------------------------


def test_flight_recorder_ring_keeps_last_n():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("ev", tick=i)
    evs = rec.events()
    assert len(evs) == 8
    assert [e["tick"] for e in evs] == list(range(12, 20))
    assert rec.dropped == 12
    lines = rec.dump().strip().splitlines()
    # line 0 is the dump header carrying the wall-clock anchor
    header = json.loads(lines[0])
    assert header["header"] == "flight_recorder"
    assert header["events"] == 8 and header["dropped"] == 12
    assert abs((rec.t0_unix + time.monotonic()) - time.time()) < 1.0
    assert len(lines) == 9 and json.loads(lines[1])["tick"] == 12


def test_flight_recorder_dumps_on_friendly_error(tmp_path):
    rec = FlightRecorder()
    rec.record("before", tick=1, detail="context")
    path = tmp_path / "crash.jsonl"
    with pytest.raises(FriendlyError, match="boom"):
        with rec.dump_on_friendly_error(str(path)):
            rec.record("during", tick=2)
            raise FriendlyError("boom")
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0]["header"] == "flight_recorder"
    assert [e["name"] for e in lines[1:]] == ["before", "during"]
    # non-matching exceptions pass through without a dump
    with pytest.raises(ValueError):
        with rec.dump_on_friendly_error(str(tmp_path / "no.jsonl")):
            raise ValueError("not friendly")
    assert not (tmp_path / "no.jsonl").exists()


def test_span_lifecycle_and_idempotent_end():
    rec = FlightRecorder()
    tracer = SpanTracer(rec)
    s = tracer.span("request", tick=0, id=7)
    s.event("queued", tick=0, queue_depth=1)
    s.end("completed", tick=3, generated=4)
    s.end("completed", tick=9)  # second end is a no-op
    evs = rec.events()
    assert [e["name"] for e in evs] == ["start", "queued", "completed"]
    assert all(e["span"] == s.id and e["span_name"] == "request"
               for e in evs)
    assert evs[-1]["attrs"]["duration_ms"] >= 0.0
    assert tracer.span("request").id != s.id  # process-unique ids


# -- regions ----------------------------------------------------------------


def test_region_records_one_event_with_its_interval_and_its_parent():
    rec = FlightRecorder()
    tracer = SpanTracer(rec)
    before = time.monotonic()
    with tracer.region("serve.admit_one", tick=4, request=7, slot=2) as one:
        with tracer.region("serve.pool_write", request=7) as write:
            time.sleep(0.002)
            write.count(dispatches=146, bytes=1 << 20)
        with tracer.region("serve.first_token", request=7):
            pass
    with tracer.region("serve.decode", tick=4):
        pass
    evs = rec.events()
    # ONE event a region, written when it ends: inner ones first
    assert [e["name"] for e in evs] == [
        "serve.pool_write", "serve.first_token", "serve.admit_one",
        "serve.decode"]
    w, f, a, d = (e["attrs"] for e in evs)
    assert evs[2]["tick"] == 4 and "tick" not in evs[0]
    assert w["parent"] == f["parent"] == "serve.admit_one"
    assert "parent" not in a and "parent" not in d   # absent at the top
    assert w["request"] == f["request"] == a["request"] == 7
    assert "request" not in d
    assert (w["dispatches"], w["bytes"], a["slot"]) == (146, 1 << 20, 2)
    assert before <= a["t0"] <= w["t0"] <= f["t0"] <= d["t0"]
    assert w["ms"] >= 2.0 and a["ms"] >= w["ms"] + f["ms"]
    # the event is stamped at the region's end, on the recorder's clock
    assert evs[0]["t"] == pytest.approx(w["t0"] + w["ms"] / 1e3, abs=1e-3)
    assert "compiles" not in a and "error" not in a
    # the caller reads the interval off the region and takes no clock
    assert one.ms == pytest.approx(a["ms"], abs=1e-3)
    assert one.t1 - one.t0 == pytest.approx(one.ms / 1e3)
    # no span id: the walkers of a request's lifecycle pass regions by
    assert all("span" not in e and "span_name" not in e for e in evs)


def test_region_is_transparent_to_exceptions_and_can_be_dropped():
    rec = FlightRecorder()
    tracer = SpanTracer(rec)

    class WindowClosed(Exception):
        pass

    with pytest.raises(WindowClosed):
        with tracer.region("train.step", tick=1):
            with tracer.region("train.log", tick=1):
                raise WindowClosed
    evs = rec.events()
    assert [(e["name"], e["attrs"]["error"]) for e in evs] == [
        ("train.log", "WindowClosed"), ("train.step", "WindowClosed")]
    assert evs[0]["attrs"]["parent"] == "train.step"
    # the stack unwound: the next region has no parent
    with tracer.region("train.feed", tick=2) as feed:
        feed.drop()          # an interval that held no work leaves no event
    with tracer.region("train.feed", tick=2):
        pass
    assert [e["name"] for e in rec.events()[2:]] == ["train.feed"]
    assert "parent" not in rec.events()[2]["attrs"]
    # a bare name could pass for a lifecycle event that readers filter on
    for taken in ("prefill", "decode", "tick", "step", "dispatch"):
        with pytest.raises(FriendlyError, match="layer"):
            tracer.region(taken)


def test_region_counts_compiles_made_inside_it():
    import jax
    import jax.numpy as jnp

    rec = FlightRecorder()
    tracer = SpanTracer(rec)
    fn = jax.jit(lambda x: jnp.sum(x * 3 + 1))
    with tracer.region("test.first"):
        fn(jnp.zeros((5,), jnp.float32)).block_until_ready()
    with tracer.region("test.again"):
        fn(jnp.ones((5,), jnp.float32)).block_until_ready()
    with tracer.region("test.outer"):
        with tracer.region("test.new_shape"):
            # an eager operation on a new shape compiles too, and no
            # watchdog watches it
            (jnp.zeros((6, 3), jnp.float32)[1, 1:3] * 2).block_until_ready()
    by_name = {e["name"]: e["attrs"] for e in rec.events()}
    assert by_name["test.first"]["compiles"] >= 1
    assert "compiles" not in by_name["test.again"]
    assert by_name["test.new_shape"]["compiles"] >= 1
    assert (by_name["test.outer"]["compiles"]
            == by_name["test.new_shape"]["compiles"])


def test_regions_nest_in_the_trace_and_the_two_clocks_differ_by_a_constant(
        tmp_path):
    """Inside a profiler session every region is also an interval of the
    trace's host plane, as ``benchmark/trace_reduce.read_xplane`` reads
    it, and ``start_ns / 1e9 - t0`` is one constant: the offset that
    joins the trace's clock to the recorder's."""
    import glob

    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    rec = FlightRecorder()
    tracer = SpanTracer(rec)
    x = jnp.ones((32, 32))
    with jax.profiler.trace(str(tmp_path)):
        for tick in range(6):
            with tracer.step_region("train.step", tick=tick):
                with tracer.region("train.feed", tick=tick):
                    time.sleep(0.003)
                with tracer.region("train.dispatch", tick=tick):
                    (x @ x).block_until_ready()
            with tracer.region("serve.admit", tick=tick):
                with tracer.region("serve.admit_one", request=tick):
                    time.sleep(0.001)
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = sorted(trace_reduce.read_xplane(path, chips=1).spans)
    events = sorted(rec.events(), key=lambda e: e["attrs"]["t0"])
    assert [sp[2] for sp in spans] == [e["name"] for e in events]
    assert len(spans) == 6 * 5
    # nesting, on the trace's clock, is what ``parent`` says
    for start, end, name in spans:
        inside = [sp for sp in spans
                  if sp[0] <= start and end <= sp[1] and sp[2] != name]
        want = {"train.feed": "train.step", "train.dispatch": "train.step",
                "serve.admit_one": "serve.admit"}.get(name)
        assert [sp[2] for sp in inside] == ([want] if want else [])
    parents = {e["name"]: e["attrs"].get("parent") for e in events}
    assert parents == {"train.step": None, "train.feed": "train.step",
                       "train.dispatch": "train.step", "serve.admit": None,
                       "serve.admit_one": "serve.admit"}
    offsets = [sp[0] / 1e9 - e["attrs"]["t0"]
               for sp, e in zip(spans, events)]
    assert max(offsets) - min(offsets) < 1e-3
    # and the lengths agree
    for (start, end, _), e in zip(spans, events):
        assert (end - start) / 1e6 == pytest.approx(e["attrs"]["ms"],
                                                    abs=1.0)


# -- retrace watchdog -------------------------------------------------------


def test_retrace_watchdog_fires_once_per_new_shape(caplog):
    import jax
    import jax.numpy as jnp

    reg = MetricRegistry()
    rec = FlightRecorder()
    fn = jax.jit(lambda x: jnp.sum(x * 2))
    dog = RetraceWatchdog(fn, "unit", registry=reg, recorder=rec)

    with caplog.at_level(logging.INFO, logger="mmlspark_tpu.telemetry"):
        dog(jnp.zeros((4,), jnp.float32))   # first program: INFO
        assert dog.compilations == 1 and dog.retraces == 0
        dog(jnp.ones((4,), jnp.float32))    # cache hit: silent
        assert dog.compilations == 1
        dog(jnp.zeros((8,), jnp.float32))   # NEW shape: the retrace
    assert dog.compilations == 2 and dog.retraces == 1
    warnings = [r for r in caplog.records
                if r.levelno == logging.WARNING and "retrace" in r.message]
    assert len(warnings) == 1
    assert "float32[8]" in warnings[0].message  # triggering signature
    assert reg.counter("retrace.unit").value == 2
    retrace_evs = [e for e in rec.events() if e["name"] == "retrace"]
    assert len(retrace_evs) == 2
    assert "float32[8]" in retrace_evs[-1]["attrs"]["signature"]
    # compile_guard's counting contract passes through the wrapper
    assert dog._cache_size() == 2


# -- serve wiring -----------------------------------------------------------


def test_record_reject_counts_toward_wall_clock():
    """A run that ends in rejections still happened: wall_s (tokens/sec's
    denominator) must span reject-only activity."""
    m = ServeMetrics(model="m", slots=2)
    m.record_reject()
    time.sleep(0.01)
    m.record_reject()
    d = m.to_dict()
    assert d["rejected"] == 2
    assert d["wall_s"] > 0.0


def test_snapshot_emits_non_scalar_metrics_as_tables():
    m = ServeMetrics(model="m", slots=2)
    m.prefill_buckets = {"8": 3, "16": 1}
    records = m.snapshot()
    tables = {r.name: r for r in records if r.group == "table"}
    assert "serve.prefill_buckets" in tables
    assert tables["serve.prefill_buckets"].value == {"8": 3, "16": 1}


def test_demo_writes_complete_spans_and_percentiles(tmp_path):
    """The acceptance path: ``serve --demo --telemetry-dir`` persists one
    COMPLETE span per request in events.jsonl and percentile keys in
    metrics.json (in-process here; tools/check_metrics_schema.py runs
    the same contract through the real CLI)."""
    from mmlspark_tpu.serve.demo import run_demo

    n_requests = 3
    out = run_demo(slots=2, n_requests=n_requests, max_new_tokens=3,
                   arrivals_per_tick=2, vocab=32, d_model=16, heads=2,
                   depth=1, cache_len=32, seed=0,
                   telemetry_dir=str(tmp_path))

    events = [json.loads(ln) for ln in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    spans = {}
    for e in events:
        if e.get("span_name") == "request":
            spans.setdefault(e["span"], []).append(e["name"])
    assert len(spans) == n_requests
    for names in spans.values():
        # full lifecycle: queued -> admitted -> prefill[bucket] ->
        # decode ticks -> terminal status with duration
        assert names[0] == "start"
        assert {"queued", "admitted", "prefill"} <= set(names)
        assert names[-1] in ("completed", "expired")
    # the watchdog's warm-up compilations ride the same timeline
    assert any(e.get("name") == "retrace" for e in events)

    metrics = json.loads((tmp_path / "metrics.json").read_text())
    for key in ("ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99",
                "per_token_ms_p50", "per_token_ms_p95", "per_token_ms_p99",
                "tick_ms_p50", "tick_ms_p95", "tick_ms_p99"):
        assert isinstance(metrics[key], (int, float)), key
    assert metrics == json.loads(json.dumps(out, default=str))
