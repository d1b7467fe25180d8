"""Device-level performance analytics (core/perf): XLA cost analysis
with its interpreter fallback, MFU / bandwidth attribution arithmetic,
the Chrome/Perfetto trace exporter's validity + determinism, SLO window
arithmetic on synthetic clocks, and the serving integration — analytics
and SLO monitoring enabled must keep the one-host-sync-per-block
contract and the compile_guard pins unchanged on BOTH the single-device
and the 2x2-mesh engine (the ISSUE 8 acceptance bar)."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.perf import (
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
from mmlspark_tpu.core.telemetry import (
    FlightRecorder,
    Histogram,
    MetricRegistry,
    SpanTracer,
)
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


# -- cost analysis: real programs and the unavailable fallback -------------


def test_analyze_jit_cost_real_program_no_compile():
    """Lowering a real jitted fn yields analytic flops WITHOUT
    populating the executable cache — cost analysis must never count as
    a compile against the guard pins."""
    fn = jax.jit(lambda x: jnp.sum(x @ x.T))
    cost = analyze_jit_cost(fn, jnp.zeros((8, 8), jnp.float32))
    assert cost.source == "xla"
    assert cost.flops is not None and cost.flops > 0
    assert cost.bytes_accessed is not None and cost.bytes_accessed > 0
    assert fn._cache_size() == 0  # traced, never backend-compiled


class _RaisingJit:
    def lower(self, *a, **kw):
        raise RuntimeError("backend says no")


class _EmptyLowered:
    def cost_analysis(self):
        return {}


class _EmptyCostJit:
    def lower(self, *a, **kw):
        return _EmptyLowered()


def test_analyze_jit_cost_degrades_to_unavailable():
    """A backend whose lowering raises, or whose cost model answers
    nothing, degrades to source="unavailable" — never an exception."""
    c1 = analyze_jit_cost(_RaisingJit(), np.zeros((2, 2)))
    assert c1 == ProgramCost.unavailable()
    c2 = analyze_jit_cost(_EmptyCostJit(), np.zeros((2, 2)))
    assert c2.source == "unavailable"
    assert c2.flops is None and c2.bytes_accessed is None


def test_perf_analytics_with_unavailable_cost_yields_none_mfu():
    pa = PerfAnalytics(
        n_devices=1, peak=DevicePeak(1e12, 1e11, "table", "test")
    )
    pa.register_program("decode[T=4]", ProgramCost.unavailable())
    pa.record_dispatch("decode[T=4]", 0.01, tokens=4)
    pa.record_tick(0.02)
    s = pa.summary()
    assert s["mfu"] is None and s["hbm_bw_util_pct"] is None
    fam = s["families"]["decode[T=4]"]
    assert fam["cost_source"] == "unavailable"
    assert fam["mfu"] is None and fam["dispatches"] == 1
    # the time split still works: 0.01s device of 0.02s tick
    assert s["device_time_pct"] == 50.0
    assert s["device_time_s"] == 0.01 and s["host_time_s"] == 0.01


def test_perf_analytics_mfu_and_bandwidth_arithmetic():
    """Exact attribution: flops x dispatches / device_s against the
    declared peak."""
    reg = MetricRegistry()
    pa = PerfAnalytics(
        registry=reg, n_devices=1,
        peak=DevicePeak(1e12, 1e11, "table", "test"),
    )
    pa.register_program("decode[T=8]", ProgramCost(1e9, 1e9, "xla"))
    pa.register_program("decode[T=8]", ProgramCost(5e55, 5e55, "xla"))
    pa.record_dispatch("decode[T=8]", 0.01, tokens=8)  # 1e11 flop/s
    assert pa.summary()["mfu"] == pytest.approx(0.1)
    assert pa.summary()["hbm_bw_util_pct"] == pytest.approx(100.0)
    # registration is first-wins: the 5e55 re-register was ignored
    assert pa.summary()["families"]["decode[T=8]"]["flops"] == 1e9
    assert not pa.wants_program("decode[T=8]")
    assert pa.wants_program("prefill[16]")
    # gauges landed in the shared registry
    d = reg.to_dict()
    assert d["perf.decode[T=8].mfu"] == pytest.approx(0.1)
    assert d["perf.mfu"] == pytest.approx(0.1)
    # a dispatch for a family never registered still attributes time
    pa.record_dispatch("mystery", 0.02)
    assert pa.summary()["families"]["mystery"]["cost_source"] == (
        "unavailable"
    )
    assert pa.device_seconds() == pytest.approx(0.03)


def test_device_peak_env_override_and_table_prefix(monkeypatch):
    monkeypatch.delenv("MMLTPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MMLTPU_PEAK_HBM_BYTES_PER_S", raising=False)

    class FakeTpu:
        device_kind = "TPU v5p chip"

    p = device_peak(FakeTpu())
    assert p.source == "table" and p.flops_per_s == 459e12

    # the CPU backend of this suite hits the table's labelled cpu entry
    assert device_peak().source == "nominal"

    monkeypatch.setenv("MMLTPU_PEAK_FLOPS", "2e12")
    p2 = device_peak(FakeTpu())
    assert p2.source == "env"
    assert p2.flops_per_s == 2e12
    assert p2.hbm_bytes_per_s == 2765e9  # unset half keeps the table


# -- SLO monitor: window arithmetic on a synthetic clock -------------------


def test_slo_monitor_burns_sheds_and_recovers():
    rec = FlightRecorder()
    reg = MetricRegistry()
    t = {"now": 0.0}
    mon = SloMonitor(
        SloTargets(ttft_p99_ms=50.0, window_s=10.0, min_samples=3),
        recorder=rec, registry=reg, clock=lambda: t["now"],
    )
    # below min_samples: two terrible samples cannot trip the alert
    mon.observe_ttft(500.0)
    mon.observe_ttft(600.0)
    st = mon.evaluate(tick=0)
    assert not st["burning"] and not mon.should_shed
    # third sample crosses min_samples -> violation + shed + ONE event
    mon.observe_ttft(700.0)
    st = mon.evaluate(tick=1)
    assert st["burning"] and mon.should_shed
    assert st["violations"][0]["slo"] == "ttft_p99_ms"
    assert st["violations"][0]["value"] == 700.0
    mon.evaluate(tick=2)  # still burning: no second violation event
    names = [e["name"] for e in rec.events()
             if e["name"].startswith("slo_")]
    assert names == ["slo_violation"]
    assert mon.violations_total == 2  # but every burning tick counts
    assert reg.to_dict()["slo.burning"] == 1
    # samples age out of the 10s window -> recovered, shed clears
    t["now"] = 11.0
    st = mon.evaluate(tick=3)
    assert not st["burning"] and not mon.should_shed
    assert st["window"]["ttft_samples"] == 0
    names = [e["name"] for e in rec.events()
             if e["name"].startswith("slo_")]
    assert names == ["slo_violation", "slo_recovered"]
    assert reg.to_dict()["slo.burning"] == 0


def test_slo_monitor_error_rate_budget_and_per_token():
    t = {"now": 0.0}
    mon = SloMonitor(
        SloTargets(error_rate=0.2, per_token_p99_ms=5.0,
                   window_s=100.0, min_samples=5),
        clock=lambda: t["now"],
    )
    for _ in range(4):
        mon.observe_finish(True)
    mon.observe_finish(False)
    st = mon.evaluate()
    assert not st["burning"]  # 1/5 = 0.2 is AT budget, not over it
    mon.observe_finish(False)
    st = mon.evaluate()
    assert st["burning"]
    assert [v["slo"] for v in st["violations"]] == ["error_rate"]
    assert st["violations"][0]["value"] == pytest.approx(2 / 6, abs=1e-4)
    # per-token joins as a second simultaneous violation
    for _ in range(5):
        mon.observe_per_token(9.0)
    st = mon.evaluate()
    assert {v["slo"] for v in st["violations"]} == {
        "error_rate", "per_token_p99_ms"
    }


def test_slo_monitor_state_before_first_evaluate():
    mon = SloMonitor(SloTargets(ttft_p99_ms=10.0))
    st = mon.state()
    assert st["declared"] is True and st["burning"] is False
    assert st["targets"]["ttft_p99_ms"] == 10.0
    with pytest.raises(FriendlyError, match="SloTargets"):
        SloMonitor({"ttft_p99_ms": 10.0})


def test_parse_slo_spec():
    t = parse_slo_spec(
        " ttft_p99_ms=50, per_token_p99_ms=5 ,error_rate=0.05,"
        "window_s=30,min_samples=2"
    )
    assert t.ttft_p99_ms == 50.0 and t.per_token_p99_ms == 5.0
    assert t.error_rate == 0.05 and t.window_s == 30.0
    assert t.min_samples == 2 and t.declared()
    with pytest.raises(FriendlyError, match="unknown SLO key"):
        parse_slo_spec("latency=5")
    with pytest.raises(FriendlyError, match="needs a number"):
        parse_slo_spec("ttft_p99_ms=fast")
    with pytest.raises(FriendlyError, match="key=value"):
        parse_slo_spec("ttft_p99_ms")
    with pytest.raises(FriendlyError, match="declares no target"):
        parse_slo_spec("window_s=30")


# -- histogram bucket export + Prometheus exposition -----------------------


def test_histogram_bucket_bounds_align_with_counts():
    h = Histogram("t", lo=1.0, hi=100.0, growth=2.0)
    bounds, counts = h.bucket_bounds(), h.bucket_counts()
    assert len(bounds) == len(counts) == h.n_buckets
    assert bounds[0] == 1.0 and bounds[-1] == "+Inf"
    assert bounds[1:-1] == [2.0 ** i for i in range(1, h.n_buckets - 1)]
    h.record(0.5)    # underflow -> bucket 0
    h.record(5.0)
    h.record(1e9)    # overflow -> the +Inf bucket
    counts = h.bucket_counts()
    assert counts[0] == 1 and counts[-1] == 1
    assert sum(counts) == h.count == 3
    # summary exports the full range while the overflow bucket is hot
    sb = h.summary()["buckets"]
    assert sb["counts"] == counts
    assert len(sb["bounds"]) == len(sb["counts"])
    assert sb["bounds"][-1] == "+Inf"
    # ...and trims trailing empties when it is not
    h2 = Histogram("t2", lo=1.0, hi=100.0, growth=2.0)
    h2.record(1.5)
    sb2 = h2.summary()["buckets"]
    assert 0 < len(sb2["counts"]) < h2.n_buckets
    assert len(sb2["bounds"]) == len(sb2["counts"])
    assert sb2["counts"][-1] == 1 and sum(sb2["counts"]) == 1
    json.dumps(h.summary())  # "+Inf" keeps the dict JSON-serializable


def test_prometheus_exposition_format():
    r = MetricRegistry()
    r.counter("serve.submitted").inc(3)
    r.gauge("perf.mfu").set(0.25)
    r.gauge("empty.gauge")  # never set -> skipped
    h = r.histogram("serve.ttft_ms")
    for v in (1.0, 10.0, 100.0):
        h.record(v)
    text = r.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE serve_submitted_total counter" in lines
    assert "serve_submitted_total 3" in lines
    assert "# TYPE perf_mfu gauge" in lines
    assert "perf_mfu 0.25" in lines
    assert not any("empty_gauge" in ln and not ln.startswith("#")
                   for ln in lines)
    # histogram: cumulative buckets ending at +Inf == count
    buckets = [ln for ln in lines
               if ln.startswith("serve_ttft_ms_bucket{")]
    assert buckets, text
    vals = [float(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert vals == sorted(vals)  # cumulative -> non-decreasing
    assert buckets[-1].startswith('serve_ttft_ms_bucket{le="+Inf"}')
    assert vals[-1] == 3.0
    assert "serve_ttft_ms_count 3" in lines
    assert "serve_ttft_ms_sum 111" in lines


# -- trace export: validity + deterministic ordering -----------------------


def _synthetic_recorder():
    rec = FlightRecorder()
    tracer = SpanTracer(rec)
    s = tracer.span("request", tick=0, id=3)
    s.event("queued", tick=0)
    s.event("admitted", tick=0, slot=0)
    rec.record("dispatch", tick=0, family="prefill[8]", ms=2.0, tokens=1)
    rec.record("dispatch", tick=1, family="decode[T=4]", ms=1.5,
               tokens=4)
    rec.record("tick", tick=1, ms=4.0, tokens=4)
    rec.record("retrace", tick=1, signature="f32[4]")
    s.end("completed", tick=1, generated=4)
    s2 = tracer.span("request", tick=1, id=4)  # never ends: open slice
    s2.event("queued", tick=1)
    return rec


def test_chrome_trace_layout_and_determinism(tmp_path):
    rec = _synthetic_recorder()
    doc = export_chrome_trace(rec, path=str(tmp_path / "trace.json"))
    # byte-identical re-export: ordering is fully deterministic
    doc2 = export_chrome_trace(rec)
    assert json.dumps(doc, sort_keys=True) == json.dumps(
        doc2, sort_keys=True
    )
    on_disk = json.loads((tmp_path / "trace.json").read_text())
    assert json.dumps(on_disk, sort_keys=True) == json.dumps(
        doc, sort_keys=True
    )

    evs = doc["traceEvents"]
    assert doc["otherData"]["t0_unix"] == pytest.approx(
        rec.t0_unix, abs=1e-3
    )
    for e in evs:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e)
        assert e["ph"] in ("M", "X", "i")
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float))
    # metadata leads, then strictly ts-ordered events
    n_meta = sum(1 for e in evs if e["ph"] == "M")
    assert all(e["ph"] == "M" for e in evs[:n_meta])
    rest = evs[n_meta:]
    assert all(rest[i]["ts"] <= rest[i + 1]["ts"]
               for i in range(len(rest) - 1))
    # request tracks: closed span carries its terminal status, open
    # span exports a zero-duration slice
    req = {e["name"]: e for e in rest
           if e["ph"] == "X" and e["name"].startswith("request ")}
    assert set(req) == {"request 3 [completed]", "request 4"}
    assert req["request 3 [completed]"]["pid"] == 1
    assert req["request 3 [completed]"]["dur"] > 0
    assert req["request 4"]["dur"] == 0.0
    # engine tracks: dispatch slices named by family, the tick slice,
    # and everything else as instants
    fams = {e["name"] for e in rest if e["ph"] == "X" and e["pid"] == 2
            and e["tid"] == 1}
    assert fams == {"prefill[8]", "decode[T=4]"}
    assert any(e["name"] == "tick 1" and e["ph"] == "X" and
               e["tid"] == 0 for e in rest)
    assert any(e["name"] == "retrace" and e["ph"] == "i" and
               e["tid"] == 2 for e in rest)
    # timestamps anchor to the unix epoch (microseconds)
    assert abs(rest[0]["ts"] / 1e6 - time.time()) < 3600


def test_chrome_trace_from_real_engine(lm):
    m, v, ids = lm
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=2)
    rids = [engine.submit(np.asarray(ids[0, :4]), max_new_tokens=5)
            for _ in range(2)]
    res = engine.run()
    assert all(res[r].status == "completed" for r in rids)
    doc = export_chrome_trace(engine.recorder)
    evs = doc["traceEvents"]
    req = [e for e in evs if e["ph"] == "X"
           and e["name"].startswith("request ")]
    assert len(req) == 2
    assert all("[completed]" in e["name"] for e in req)
    assert any(e["ph"] == "X" and e["name"].startswith("decode[T=")
               for e in evs)
    assert any(e["ph"] == "X" and e["name"].startswith("prefill[")
               for e in evs)


# -- serving integration: the contracts hold WITH analytics + SLO ----------


def test_analytics_keep_sync_and_compile_contracts(lm, monkeypatch):
    """THE acceptance bar: with cost analytics AND an SLO monitor
    enabled, one request decoding 16 tokens through T=8 blocks still
    pays at most one synced fetch per block, and the compile-count pins
    hold — the once-per-family lowering fires inside this window and
    must not sync or compile."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :4])
    engine = ServeEngine(
        m, v, slots=1, cache_len=32, decode_block=8,
        slo="ttft_p99_ms=60000,per_token_p99_ms=60000,error_rate=0.99",
    )
    rid = engine.submit(prompt, max_new_tokens=17)  # 1 prefill + 16 dec

    syncs = {"n": 0}
    real_device_get = jax.device_get
    real_asarray = np.asarray

    def counting_device_get(x, *a, **kw):
        syncs["n"] += 1
        return real_device_get(x, *a, **kw)

    def counting_asarray(x, *a, **kw):
        if isinstance(x, jax.Array):
            syncs["n"] += 1
        return real_asarray(x, *a, **kw)

    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        monkeypatch.setattr(jax, "device_get", counting_device_get)
        monkeypatch.setattr(np, "asarray", counting_asarray)
        res = engine.run()[rid]
        monkeypatch.undo()

    np.testing.assert_array_equal(
        np.asarray(res.tokens), ref_tokens(m, v, prompt, 17)
    )
    assert syncs["n"] <= 2, f"host syncs: {syncs['n']} (> 1 per block)"

    d = engine.metrics.to_dict()
    fams = d["perf_families"]
    decode_fams = [f for f in fams if f.startswith("decode[T=")]
    assert decode_fams and any(f.startswith("prefill[") for f in fams)
    for f in fams.values():
        assert f["dispatches"] >= 1
    # the CPU backend's cost model answers, so MFU is a number here
    assert all(f["cost_source"] == "xla" for f in fams.values())
    assert isinstance(d["mfu"], float)
    assert isinstance(d["device_time_pct"], float)
    assert d["slo"]["declared"] is True and d["slo_burning"] == 0


def test_analytics_keep_contracts_sharded(lm, monkeypatch):
    """Same bar on the 2x2 (data, model) mesh: the sharded programs'
    cost analysis rides the existing sync points too."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :4])
    engine = ServeEngine(
        m, v, slots=2, cache_len=32, decode_block=4,
        mesh={"data": 2, "model": 2},
        slo="ttft_p99_ms=60000,error_rate=0.99",
    )
    rid = engine.submit(prompt, max_new_tokens=9)  # 1 prefill + 8 dec

    syncs = {"n": 0}
    real_device_get = jax.device_get
    real_asarray = np.asarray

    def counting_device_get(x, *a, **kw):
        syncs["n"] += 1
        return real_device_get(x, *a, **kw)

    def counting_asarray(x, *a, **kw):
        if isinstance(x, jax.Array):
            syncs["n"] += 1
        return real_asarray(x, *a, **kw)

    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        monkeypatch.setattr(jax, "device_get", counting_device_get)
        monkeypatch.setattr(np, "asarray", counting_asarray)
        res = engine.run()[rid]
        monkeypatch.undo()

    np.testing.assert_array_equal(
        np.asarray(res.tokens), ref_tokens(m, v, prompt, 9)
    )
    assert syncs["n"] <= 2, f"host syncs: {syncs['n']} (> 1 per block)"
    fams = engine.metrics.to_dict()["perf_families"]
    assert any(f.startswith("decode[T=") for f in fams)
    assert all(f["cost_source"] == "xla" for f in fams.values())


def test_slo_shed_suppresses_admissions_but_completes(lm):
    """An impossible TTFT target trips shedding while a request is in
    flight (queue holds, nothing admitted) — but an idle engine always
    admits, so every request still completes."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    engine = ServeEngine(
        m, v, slots=1, cache_len=32, max_queue=8, decode_block=2,
        slo="ttft_p99_ms=0.000001,min_samples=1,window_s=600",
    )
    rids = [engine.submit(row[:4], max_new_tokens=8) for _ in range(3)]
    res = engine.run()
    assert all(res[r].status == "completed" for r in rids)
    d = engine.metrics.to_dict()
    assert d["slo_violations_total"] > 0
    assert d["slo_shed_ticks_total"] > 0
    assert d["slo"]["burning"] is True
    names = {e["name"] for e in engine.recorder.events()}
    assert "slo_violation" in names and "slo_shed" in names


def test_engine_rejects_bad_slo_spec(lm):
    m, v, _ = lm
    with pytest.raises(FriendlyError, match="unknown SLO key"):
        ServeEngine(m, v, slots=1, cache_len=32, slo="latency=5")
