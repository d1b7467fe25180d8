"""Fleet-wide distributed tracing (core/tracehub) — the TelemetryHub
merge plane and its live surface.

The contract under test (docs/OBSERVABILITY.md "Distributed
tracing"): N flight recorders + metric registries merge into ONE
globally-ordered timeline, ONE deterministic Perfetto trace whose
``trace_id``-bound flow arrows cross replica tracks (hand-offs,
failover replays, hedge twins), ONE label-based Prometheus exposition
(``{replica="0"}`` labels instead of name-prefix namespacing), and a
detector sweep that alerts exactly once per standing condition. The
hub reads host-side state only: attaching it adds ZERO new XLA
programs and zero extra host syncs per decode block, on a single
device and on a 2x2 mesh — pinned under ``serve_compile_guard``.
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import Fault, FaultInjector
from mmlspark_tpu.core.telemetry import (
    FlightRecorder,
    MetricRegistry,
    SpanTracer,
    _prom_escape_label_value,
)
from mmlspark_tpu.core.tracehub import (
    ALERT_KINDS,
    MetricsServer,
    TelemetryHub,
    _RegistryView,
)
from mmlspark_tpu.serve import DisaggFleet, ReplicaSet, ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


# -- registry views ---------------------------------------------------------


def test_registry_view_prefix_strip_exclude_and_readonly():
    inner = MetricRegistry()
    inner.counter("modellm.serve.completed").inc(4)
    inner.counter("multimodel.faults_injected").inc(1)
    inner.counter("replica0.serve.completed").inc(2)
    inner.gauge("perf.mfu").set(0.5)

    # prefix view: restricted to the namespace, names stripped
    v = _RegistryView(inner, prefix="modellm.")
    assert v.names() == ["serve.completed"]
    assert v.get("serve.completed").value == 4
    assert v.to_dict() == {"serve.completed": 4}

    # strip view: EVERY name survives, the prefix folds away where
    # present (perf.* passes through untouched)
    s = _RegistryView(inner, strip_prefix="replica0.")
    assert "serve.completed" in s.names() and "perf.mfu" in s.names()
    assert s.get("serve.completed").value == 2

    # exclusion filters on ORIGINAL names — "multimodel." must not be
    # caught by a "model" prefix match
    e = _RegistryView(inner, exclude_prefixes=("modellm.",))
    assert "multimodel.faults_injected" in e.names()
    assert not any(n.startswith("modellm.") for n in e.names())

    with pytest.raises(FriendlyError, match="read-only"):
        v.counter("new.metric")


def test_hub_rejects_unknown_thresholds():
    with pytest.raises(FriendlyError, match="unknown detector"):
        TelemetryHub(thresholds={"typo_threshold": 1})


# -- source registration / generations --------------------------------------


def test_add_source_idempotent_and_generation_bump():
    hub = TelemetryHub()
    rec = FlightRecorder()
    s1 = hub.add_source("replica0", recorder=rec)
    assert hub.add_source("replica0", recorder=rec) is s1
    assert s1.display == "replica0" and "gen" not in s1.labels
    # a NEW recorder under the same name is a rebuilt engine: next
    # generation, disambiguated display + gen label
    s2 = hub.add_source("replica0", recorder=FlightRecorder())
    assert s2 is not s1
    assert s2.display == "replica0#1" and s2.labels["gen"] == "1"
    with pytest.raises(FriendlyError, match="recorder"):
        hub.add_source("empty")


# -- merged timeline --------------------------------------------------------


def test_merged_events_interleave_and_dump_header(tmp_path):
    hub = TelemetryHub()
    a, b = FlightRecorder(), FlightRecorder()
    hub.add_source("a", recorder=a)
    hub.add_source("b", recorder=b)
    for i in range(4):
        (a if i % 2 == 0 else b).record("ev", tick=i)
    merged = hub.merged_events()
    ours = [ev for ev in merged if ev["src"] in ("a", "b")]
    # wall-clock order == recording order, regardless of which
    # recorder each event landed on
    assert [ev["tick"] for ev in ours] == [0, 1, 2, 3]
    assert [ev["src"] for ev in ours] == ["a", "b", "a", "b"]
    assert all("wall" in ev and "t" in ev for ev in ours)

    path = tmp_path / "events.jsonl"
    hub.dump_events(str(path))
    lines = path.read_text().splitlines()
    header = json.loads(lines[0])
    assert header["header"] == "telemetry_hub"
    assert set(header["t0_unix"]) == {"hub", "a", "b"}
    assert header["events"] == len(lines) - 1
    assert header["dropped"] == 0


def test_request_chains_span_inheritance_and_control_events():
    hub = TelemetryHub()
    r0, r1 = FlightRecorder(), FlightRecorder()
    hub.add_source("sup", recorder=r0)
    hub.add_source("rep", recorder=r1)
    r0.record("routed", trace="g0", replica=1)
    span = SpanTracer(r1).span("request", id=0, trace="g0")
    span.event("prefill")
    span.end("completed")
    chains = hub.request_chains()
    names = [ev["name"] for ev in chains["g0"]]
    # the control event joins the span's events: the routed hop plus
    # the full lifecycle, span events INHERITING the start's trace id
    assert names == ["routed", "start", "prefill", "completed"]
    assert {ev["src"] for ev in chains["g0"]} == {"sup", "rep"}


# -- merged prometheus ------------------------------------------------------


def test_merged_prom_one_type_header_with_labels():
    hub = TelemetryHub()
    ra, rb = MetricRegistry(), MetricRegistry()
    ra.counter("serve.completed").inc(3)
    rb.counter("serve.completed").inc(5)
    hub.add_source("r0", registry=ra, labels={"replica": "0"})
    hub.add_source("r1", registry=rb, labels={"replica": "1"})
    prom = hub.to_prometheus()
    assert prom.count("# TYPE serve_completed_total counter") == 1
    assert 'serve_completed_total{replica="0"} 3' in prom
    assert 'serve_completed_total{replica="1"} 5' in prom


def test_prom_label_value_escaping_round_trip():
    """Backslash/quote/newline in a label value survive the exposition:
    escape -> parse-back -> the original string, and the emitted line
    never tears (one sample per physical line)."""
    evil = 'mo"del\\v1\nline2'
    escaped = _prom_escape_label_value(evil)
    assert "\n" not in escaped
    # the format's own unescape rules invert the escape exactly
    unescaped = (
        escaped.replace("\\n", "\n")
        .replace('\\"', '"')
        .replace("\\\\", "\\")
    )
    assert unescaped == evil

    reg = MetricRegistry()
    reg.counter("serve.completed").inc(1)
    hub = TelemetryHub()
    hub.add_source("m", registry=reg, labels={"model": evil})
    prom = hub.to_prometheus()
    sample = [ln for ln in prom.splitlines()
              if ln.startswith("serve_completed_total{")]
    assert len(sample) == 1
    inside = sample[0].split("{", 1)[1].rsplit("}", 1)[0]
    assert inside == f'model="{escaped}"'


# -- detectors --------------------------------------------------------------


def test_detectors_fire_once_per_condition():
    hub = TelemetryHub(thresholds={"queue_high": 4})
    reg = MetricRegistry()
    reg.counter("retrace.serve.decode").inc(40)
    h = reg.histogram("serve.tick_ms")
    for _ in range(25):
        h.record(1.0)
    h.record(5000.0)  # p99 blows past 50x p50
    rec = FlightRecorder()
    for _ in range(3):
        rec.record("dispatch", family="decode[T=2]", ms=1.0)
    hub.add_source(
        "r0", recorder=rec, registry=reg,
        stats=lambda: {"queue_depth": 9, "decode_blocks": 2},
    )
    # uneven SLO burn needs >= 2 sources disagreeing
    ra, rb = MetricRegistry(), MetricRegistry()
    ra.gauge("slo.burning").set(1)
    rb.gauge("slo.burning").set(0)
    hub.add_source("r1", registry=ra)
    hub.add_source("r2", registry=rb)

    kinds = {a["kind"] for a in hub.detect()}
    assert kinds == {
        "retrace_storm", "tick_p99_drift", "queue_watermark",
        "host_sync_regression", "slo_burn_spread",
    }
    # every alert raised its counter and landed on the hub's recorder
    for kind in kinds:
        assert hub.registry.counter(f"alerts.{kind}").value == 1
    alert_events = [ev for ev in hub.recorder.events()
                    if ev["name"] == "alert"]
    assert len(alert_events) == len(kinds)
    # a standing condition fires ONCE per hub lifetime — a scrape loop
    # re-running detect() must not re-count it
    assert hub.detect() == []
    assert hub.registry.counter("alerts.retrace_storm").value == 1


def test_detectors_quiet_on_healthy_source():
    hub = TelemetryHub()
    reg = MetricRegistry()
    reg.counter("retrace.serve.decode").inc(3)
    rec = FlightRecorder()
    rec.record("dispatch", family="decode[T=2]", ms=1.0)
    hub.add_source(
        "r0", recorder=rec, registry=reg,
        stats=lambda: {"queue_depth": 1, "decode_blocks": 1},
    )
    assert hub.detect() == []
    assert all(
        hub.registry.counter(f"alerts.{k}").value == 0
        for k in ALERT_KINDS
    )


# -- live surface -----------------------------------------------------------


def test_metrics_server_endpoints_on_ephemeral_port():
    hub = TelemetryHub()
    reg = MetricRegistry()
    reg.counter("serve.completed").inc(2)
    hub.add_source("r0", registry=reg, labels={"replica": "0"})
    with MetricsServer(hub, port=0) as server:
        assert server.port > 0
        base = f"http://{server.host}:{server.port}"

        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert 'serve_completed_total{replica="0"} 2' in body
        assert "# TYPE alerts_retrace_storm_total counter" in body

        health = json.loads(
            urllib.request.urlopen(f"{base}/healthz").read())
        assert health["status"] == "ok"
        assert "r0" in health["sources"]
        assert set(health["alerts"]) == set(ALERT_KINDS)

        doc = json.loads(
            urllib.request.urlopen(f"{base}/traces").read())
        assert doc["otherData"]["generator"].endswith("TelemetryHub")

        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/nope")
        assert err.value.code == 404
    # closed: the port no longer answers
    with pytest.raises(OSError):
        urllib.request.urlopen(f"{base}/healthz", timeout=0.5)


# -- zero-overhead pin ------------------------------------------------------


def _drive_with_hub(m, v, ids, mesh):
    """Serve a batch with the hub attached and SCRAPED MID-RUN; the
    engine's compile pins and the one-host-sync-per-block invariant
    must hold exactly as they do without the hub."""
    eng = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                      decode_block=4, mesh=mesh)
    hub = TelemetryHub()
    hub.attach_engine(eng)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7)]
    with serve_compile_guard(eng, min_decode=1, min_prefill=1):
        for p in prompts:
            eng.submit(p, 6)
        done = 0
        while done < len(prompts):
            done += len(eng.step())
            # the read-side merge runs between ticks, like a scrape
            hub.to_prometheus()
            hub.merged_events()
        hub.export_trace()
        assert hub.detect() == []
    # one device_get per fused decode block — the hub's own
    # host-sync detector agrees with the raw event count
    syncs = sum(
        1 for ev in eng.recorder.events()
        if ev["name"] == "dispatch"
        and str(ev.get("attrs", {}).get("family", "")).startswith("decode")
    )
    assert syncs == sum(eng.metrics.decode_blocks.values())
    assert hub.registry.counter("alerts.host_sync_regression").value == 0


def test_hub_zero_new_programs_single_device(lm):
    m, v, ids = lm
    _drive_with_hub(m, v, ids, mesh=None)


@pytest.mark.slow  # ci.sh's tracing gate runs the full file unfiltered
def test_hub_zero_new_programs_2x2_mesh(lm):
    m, v, ids = lm
    _drive_with_hub(m, v, ids, mesh={"data": 2, "model": 2})


# -- fleet flows: hand-off, failover, hedge ---------------------------------


def _flow_events(doc):
    return [e for e in doc["traceEvents"] if e["ph"] in ("s", "t", "f")]


def test_fleet_handoff_flow_arrows_and_deterministic_export(lm, tmp_path):
    m, v, ids = lm
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, retry_backoff_s=0.0)
    hub = TelemetryHub()
    hub.attach_fleet(fleet)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [fleet.submit(p, 6) for p in prompts]
    results = fleet.run()
    assert all(results[g].status == "completed" for g in gids)

    doc = hub.export_trace()
    pid_of = {s.display: s.pid for s in hub.sources()}
    flows = _flow_events(doc)
    # every request prefilled on one replica and decoded on another:
    # one flow chain per trace id, arrows CROSSING the two tracks
    by_trace = {}
    for ev in flows:
        by_trace.setdefault(ev["id"], []).append(ev)
    assert set(by_trace) == {f"f{g}" for g in gids}
    for trace, evs in by_trace.items():
        phases = [e["ph"] for e in sorted(evs, key=lambda e: e["ts"])]
        assert phases[0] == "s" and phases[-1] == "f", (trace, phases)
        pids = {e["pid"] for e in evs}
        assert pid_of["prefill0"] in pids and pid_of["decode1"] in pids
        finish = [e for e in evs if e["ph"] == "f"]
        assert all(e.get("bp") == "e" for e in finish)
        # arrows anchor on request tracks, not engine-plane tracks
        assert all(e["tid"] >= 10 for e in evs)

    # the merged chain holds both sides of the hand-off
    chains = hub.request_chains()
    for g in gids:
        srcs = {ev["src"] for ev in chains[f"f{g}"]}
        assert {"fleet", "prefill0", "decode1"} <= srcs
        names = {ev["name"] for ev in chains[f"f{g}"]}
        assert "handoff_routed" in names and "handed_off" in names

    # byte-identical re-export: same hub state, same bytes
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    hub.export_trace(path=str(p1))
    hub.export_trace(path=str(p2))
    assert p1.read_bytes() == p2.read_bytes()


def test_failover_replay_joins_the_original_trace(lm):
    """Kill replica 0 mid-run: the replayed request's span on the
    REBUILT engine (a new hub generation) carries the same trace id as
    the original submit, so the chain and the flow arrows survive the
    failover."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.decode", "kill", tick=3,
                               replica=0)])
    rs = ReplicaSet(m, v, replicas=2, slots=4, cache_len=32,
                    max_queue=8, decode_block=2,
                    snapshot_every_ticks=2, faults=inj,
                    retry_backoff_s=0.0)
    hub = TelemetryHub()
    hub.attach_replicaset(rs)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7)]
    gids = [rs.submit(p, 8) for p in prompts]
    results = rs.run()
    assert rs.replica_failovers_total == 1
    assert all(results[g].status == "completed" for g in gids)

    displays = [s.display for s in hub.sources()]
    assert "replica0#1" in displays  # the rebuilt engine's generation
    chains = hub.request_chains()
    replayed = [
        t for t, evs in chains.items()
        if any(ev["src"].startswith("replica0#") for ev in evs)
    ]
    assert replayed, f"no chain reached the rebuilt replica: {displays}"
    for t in replayed:
        srcs = {ev["src"] for ev in chains[t]}
        # the SAME trace id spans the supervisor's routing, a pre-kill
        # source, and the post-failover rebuild
        assert "supervisor" in srcs and "replica0#1" in srcs
    # the rebuilt replica's fragment joins the flow chain
    doc = hub.export_trace()
    flow_traces = {e["id"] for e in _flow_events(doc)}
    assert set(replayed) <= flow_traces


def test_hedge_twin_shares_the_trace(lm):
    m, v, ids = lm

    class _FakeClock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = _FakeClock()
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, hedge_ms=50.0,
                    clock=clk, snapshot_every_ticks=None,
                    retry_backoff_s=0.0)
    hub = TelemetryHub()
    hub.attach_replicaset(rs)
    gid = rs.submit(np.asarray(ids[0, :6]), 12)
    rs.step()
    clk.t = 0.2  # stale enough to hedge
    results = rs.run()
    assert rs.hedges_total == 1
    assert results[gid].status == "completed"
    chain = hub.request_chains()[f"g{gid}"]
    # both copies of the request ran under ONE trace id, on different
    # replicas, and the hedge control event names that id too
    assert {"replica0", "replica1"} <= {ev["src"] for ev in chain}
    assert "hedge" in {ev["name"] for ev in chain}
    starts = [ev for ev in chain if ev["name"] == "start"]
    assert len(starts) >= 2
    doc = hub.export_trace()
    hedge_flow = [e for e in _flow_events(doc) if e["id"] == f"g{gid}"]
    assert {e["pid"] for e in hedge_flow} == {
        s.pid for s in hub.sources()
        if s.display in ("replica0", "replica1")
    }
