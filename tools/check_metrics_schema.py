#!/usr/bin/env python
"""Telemetry schema gate: run the real ``serve --demo`` CLI with
``--telemetry-dir`` and assert every emitted artifact keeps its contract.

Three surfaces, all produced by ONE subprocess run at smoke scale:

- stdout: exactly one JSON line (the CLI's parseable-output contract),
  carrying every historical ``ServeMetrics.to_dict()`` key plus the
  telemetry plane's percentile keys with the right types;
- ``metrics.json``: the same dict persisted under ``--telemetry-dir``;
- ``events.jsonl``: the flight recorder's timeline — a header line
  carrying the ``t0_unix`` wall-clock anchor, every submitted request
  as one COMPLETE span (start -> queued -> admitted -> prefill ->
  terminal status), and the ``tick``/``dispatch`` event names the
  trace exporter keys on;
- ``trace.json`` (+ the explicit ``--trace-out`` path): valid Chrome
  trace-event JSON — per-request slices, tick + dispatch tracks,
  ts-ordered (Perfetto-loadable; docs/OBSERVABILITY.md "Trace
  export");
- ``metrics.prom``: the Prometheus text exposition with real
  histogram ``_bucket`` series.

A second run at ``--replicas 2`` pins the replicated-serving contract
(docs/SERVING.md "Replicated serving"): the JSON line becomes
``ReplicaSet.metrics_dict()`` — control-plane totals plus one
``per_replica.replica{i}`` nested dict — and the telemetry bundle is
the supervisor's recorder/registry (failover/hedge/drain counters in
the exposition, ``routed`` events in the timeline).

``--train`` runs the TRAINING surface instead: two seeded fault
drills through the ``train`` CLI (docs/TRAINING.md) pin the trainer's
metric/event schema — the resilience counters
(``train.retries_total``, ``train.anomalies_skipped``,
``train.checkpoints``, ``train.checkpoint_failures``), the step-time
and loss histograms, the flight-recorder timeline (``step`` /
``checkpoint`` / ``restore`` / ``anomaly`` / ``retry`` / ``restart``)
and the ``train_*`` Prometheus exposition.

Exits non-zero with a pointed message on the first violation, so
``tools/ci.sh`` catches schema drift before a dashboard does
(docs/OBSERVABILITY.md). Usage::

    python tools/check_metrics_schema.py               # serve surfaces
    python tools/check_metrics_schema.py --disagg      # fleet surface
    python tools/check_metrics_schema.py --train       # training surface
    python tools/check_metrics_schema.py --multi-model # model-zoo surface
    python tools/check_metrics_schema.py --tracing     # distributed tracing

Replicated/disagg/multi-model runs write the MERGED TelemetryHub
bundle (docs/OBSERVABILITY.md "Distributed tracing"): the
``events.jsonl`` header is ``telemetry_hub`` naming every source, the
exposition uses ``{replica="0",role="prefill"}`` labels instead of
name prefixes, ``metrics.json`` carries a ``hub`` summary block with
the full ``alerts.*`` catalog, and ``trace.json`` holds
``trace_id``-bound flow arrows. ``--tracing`` is the acceptance drill:
a seeded ``--disagg --faults`` run must produce ONE merged trace where
a handed-off request's flow arrow crosses the prefill -> decode
replica tracks AND a killed replica's failover replay links to the
original submit via the same trace id (a ``#1``-generation track).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

N_REQUESTS = 4

# key -> allowed types in the flat metrics dict. ``type(None)`` appears
# where an empty/degenerate run may legitimately report null; the demo
# run below always populates them, so None is rejected for those.
NUM = (int, float)
REQUIRED_METRIC_KEYS: dict[str, tuple] = {
    # the pre-telemetry ServeMetrics.to_dict() contract — every key
    # dashboards already consume must survive
    "model": (str,),
    "slots": (int,),
    "ticks": (int,),
    "submitted": (int,),
    "rejected": (int,),
    "completed": (int,),
    "expired": (int,),
    "tokens_generated": (int,),
    "queue_depth_mean": NUM,
    "queue_depth_max": NUM,
    "ttft_ticks_mean": NUM,
    "ttft_ms_mean": NUM,
    "per_token_ms": NUM,
    "slot_utilization_mean": NUM,
    "slot_utilization_peak": NUM,
    "tokens_per_sec": NUM,
    "wall_s": NUM,
    "decode_live_kv_tokens": (int,),
    "decode_dense_kv_tokens": (int,),
    "decode_flop_utilization": NUM,
    "prefill_buckets": (dict,),
    # chunked prefill + async host loop (docs/PERFORMANCE.md "Chunked
    # prefill & async host loop"): always present — a monolithic/sync
    # engine reports prefill_chunk=0, the counters 0 and async_host=0,
    # so dashboards can alert on host_idle_fraction growth without
    # existence checks. host_idle_fraction is null only on a run with
    # no ticks; the demo run below always populates it
    "prefill_chunk": (int,),
    "chunked_prefills_total": (int,),
    "async_host": (int,),
    "overlapped_dispatches_total": (int,),
    "host_sync_wait_s": NUM,
    "host_idle_fraction": NUM,
    # the telemetry plane's additions
    "ttft_ms_p50": NUM,
    "ttft_ms_p95": NUM,
    "ttft_ms_p99": NUM,
    "per_token_ms_p50": NUM,
    "per_token_ms_p95": NUM,
    "per_token_ms_p99": NUM,
    "tick_ms_p50": NUM,
    "tick_ms_p95": NUM,
    "tick_ms_p99": NUM,
    # fused decode blocks (tests/test_decode_block.py)
    "decode_block": (int,),
    "tokens_per_tick": NUM,
    "decode_blocks": (dict,),
    # mesh-sharded serving (docs/SERVING.md "Sharded serving"): the
    # topology keys are ALWAYS present — {} / 1 / total-bytes on a
    # single-device engine, so dashboards need no existence checks
    "mesh_shape": (dict,),
    "mesh_devices": (int,),
    "cache_pool_bytes_per_device": (int,),
    # quantized decode (docs/PERFORMANCE.md "Quantized decode"): the
    # pool's KV store dtype — "bf16" or "int8" — always present so
    # dashboards can attribute cache_pool_bytes_per_device deltas
    "kv_dtype": (str,),
    # resilience plane (docs/SERVING.md "Failure semantics"): terminal
    # statuses beyond completed/expired plus the fault-handling
    # counters — always present (0 on a fault-free run) so dashboards
    # can alert on them without existence checks
    "failed": (int,),
    "stalled": (int,),
    "retries_total": (int,),
    "faults_injected_total": (int,),
    "quarantined_total": (int,),
    "preemptions_total": (int,),
    "degraded_mode": (int,),
    "faults_by_kind": (dict,),
    # replica control plane (docs/SERVING.md "Replicated serving"):
    # checkpoint/cancel accounting — 0 on an unsupervised run, so
    # dashboards can alert on snapshot failures without existence checks
    "snapshots_total": (int,),
    "snapshot_failures_total": (int,),
    "cancelled_total": (int,),
    # integrity plane (docs/OBSERVABILITY.md "Integrity"): checksum
    # verification failures on hand-off adopt / snapshot restore —
    # always present (0 on a clean run) so SDC dashboards can alert
    # without existence checks
    "integrity_handoff_checksum_failures_total": (int,),
    "integrity_snapshot_checksum_failures_total": (int,),
    "integrity_checksum_failures_total": (int,),
    # device-level performance analytics (docs/OBSERVABILITY.md
    # "Device-level performance analytics"): the demo run's backend has
    # a working XLA cost model, so the utilization figures must be real
    # numbers — None would mean the cost-analysis path silently broke
    "mfu": NUM,
    "hbm_bw_util_pct": NUM,
    "device_time_s": NUM,
    "host_time_s": NUM,
    "device_time_pct": NUM,
    "perf_families": (dict,),
    "perf_peak": (dict,),
    # SLO plane (docs/OBSERVABILITY.md "Declaring SLOs"): the scalars
    # dashboards alert on are always present; the full window state
    # rides under "slo"
    "slo_burning": (int,),
    "slo_violations_total": (int,),
    "slo_shed_ticks_total": (int,),
    "slo": (dict,),
    # paged KV cache (docs/SERVING.md "Paged KV cache"): always present
    # — a dense-pool run reports the int keys as 0 and
    # page_utilization as null, a --paged run populates all of them
    "page_size": (int,),
    "pages_total": (int,),
    "pages_free": (int,),
    "page_utilization": NUM + (type(None),),
    "prefix_cache_hits_total": (int,),
    "prefix_cache_entries": (int,),
    "cow_copies_total": (int,),
    "prefix_tokens_saved_total": (int,),
    # demo envelope
    "n_requests": (int,),
    "decode_compiles": (int,),
    "prefill_compiles": (int,),
    "prefill_bucket_count": (int,),
}

# the --replicas JSON line is ReplicaSet.metrics_dict() (docs/SERVING.md
# "Replicated serving"): control-plane totals + one nested dict per
# replica — a different schema from the single-engine line above
REQUIRED_REPLICA_KEYS: dict[str, tuple] = {
    "replicas": (int,),
    "hedge_ms": NUM + (type(None),),
    "supervisor_ticks": (int,),
    "submitted": (int,),
    "completed": (int,),
    "failed": (int,),
    "expired": (int,),
    "stalled": (int,),
    "tokens_generated": (int,),
    "tokens_per_sec": NUM,
    "wall_s": NUM,
    "replica_failovers_total": (int,),
    "hedges_total": (int,),
    "hedge_wasted_tokens_total": (int,),
    "drains_total": (int,),
    "integrity_snapshot_checksum_failures_total": (int,),
    "per_replica": (dict,),
}

REQUIRED_PER_REPLICA_KEYS: dict[str, tuple] = {
    "state": (str,),
    "failovers": (int,),
    "ticks": (int,),
    "submitted": (int,),
    "completed": (int,),
    "failed": (int,),
    "expired": (int,),
    "tokens_generated": (int,),
    "retries_total": (int,),
    "quarantined_total": (int,),
    "snapshots_total": (int,),
    "snapshot_failures_total": (int,),
    "cancelled_total": (int,),
    "degraded_mode": (int,),
    "queue_depth": (int,),
    "decode_compile_count": (int,),
    "prefill_compile_count": (int,),
    # chunked-prefill/async rollups per replica: a fleet where only the
    # prefill role chunks must show WHERE the chunking happened
    "chunked_prefills_total": (int,),
    "overlapped_dispatches_total": (int,),
    "host_idle_fraction": NUM + (type(None),),
}

# the --disagg JSON line is DisaggFleet.metrics_dict() (docs/SERVING.md
# "Disaggregated fleet"): fleet totals (hand-off plane, fleet-wide
# prefix index, autoscaler) + per-role aggregates + per-replica dicts
REQUIRED_FLEET_KEYS: dict[str, tuple] = {
    "disagg": (bool,),
    "prefill_replicas": (int,),
    "decode_replicas": (int,),
    "fleet_ticks": (int,),
    "submitted": (int,),
    "completed": (int,),
    "failed": (int,),
    "expired": (int,),
    "stalled": (int,),
    "tokens_generated": (int,),
    "tokens_per_sec": NUM + (type(None),),
    "wall_s": NUM,
    "ttft_ms_p99": NUM,
    "handoffs_total": (int,),
    "handoff_fallbacks_total": (int,),
    "fleet_prefix_hits_total": (int,),
    "fleet_prefix_entries": (int,),
    "fleet_prefill_tokens_saved_total": (int,),
    "replica_failovers_total": (int,),
    "drains_total": (int,),
    "integrity_snapshot_checksum_failures_total": (int,),
    "integrity_handoff_checksum_failures_total": (int,),
    "scale_ups_total": (int,),
    "scale_downs_total": (int,),
    "parked_prefill": (int,),
    "parked_decode": (int,),
    "autoscale": (dict, type(None)),
    "per_role": (dict,),
    "per_replica": (dict,),
}

REQUIRED_FLEET_ROLE_KEYS: dict[str, tuple] = {
    "replicas": (int,),
    "submitted": (int,),
    "tokens_generated": (int,),
    "queue_depth": (int,),
    "handoffs_out_total": (int,),
    "handoffs_adopted_total": (int,),
    "handoff_fallbacks_total": (int,),
}

# a fleet replica carries every ReplicaSet per-replica key plus its
# role and the hand-off counters
REQUIRED_FLEET_PER_REPLICA_KEYS: dict[str, tuple] = {
    **REQUIRED_PER_REPLICA_KEYS,
    "role": (str,),
    "handoffs_out_total": (int,),
    "handoffs_adopted_total": (int,),
    "handoff_fallbacks_total": (int,),
}

#: engine-emitted event names the trace exporter keys on — renaming
#: any of these breaks trace.json's tick/dispatch tracks, so the gate
#: pins their presence in a demo run's events.jsonl
REQUIRED_EVENT_NAMES = {"dispatch", "tick"}

#: the hub's full alert catalog (core/tracehub.ALERT_KINDS) — every
#: ``alerts.*`` counter must exist from tick zero, in the exposition
#: AND the metrics.json ``hub`` block, so dashboards never need
#: existence checks before alerting on them
HUB_ALERT_KINDS = (
    "retrace_storm", "host_sync_regression", "queue_watermark",
    "tick_p99_drift", "slo_burn_spread",
)

# the train CLI's one-line contract (docs/TRAINING.md "Observability"):
# SPMDTrainer's registry flattened by MetricRegistry.to_dict() plus the
# demo's run summary. Counters are ints; histogram leaves are the
# _count/_mean/_p50/_p95/_p99 five-key spelling the serve surface uses.
REQUIRED_TRAIN_KEYS: dict[str, tuple] = {
    # resilience counters — the keys the drill dashboards key on
    "train.retries_total": (int,),
    "train.anomalies_skipped": (int,),
    "train.checkpoints": (int,),
    "train.checkpoint_failures": (int,),
    "train.faults_injected_total": (int,),
    # integrity plane (docs/TRAINING.md "Integrity audits"): audit /
    # SDC-detection counters — always present (0 with audits off) so
    # corruption dashboards need no existence checks
    "train.integrity.audits": (int,),
    "train.integrity.checksum_failures": (int,),
    "train.integrity.sdc_suspected": (int,),
    "train.integrity.replay_transient_sdc": (int,),
    "train.integrity.replay_software_nondeterminism": (int,),
    # the degrade ladder's current rung
    "train.grad_accum": NUM,
    # step-time / throughput / loss / grad-norm histograms
    "train.step_ms_count": (int,),
    "train.step_ms_mean": NUM,
    "train.step_ms_p50": NUM,
    "train.step_ms_p95": NUM,
    "train.step_ms_p99": NUM,
    "train.tokens_per_sec_count": (int,),
    "train.tokens_per_sec_mean": NUM,
    "train.tokens_per_sec_p50": NUM,
    "train.tokens_per_sec_p95": NUM,
    "train.tokens_per_sec_p99": NUM,
    "train.loss_count": (int,),
    "train.loss_mean": NUM,
    "train.loss_p50": NUM,
    "train.loss_p95": NUM,
    "train.loss_p99": NUM,
    "train.grad_norm_count": (int,),
    "train.grad_norm_mean": NUM,
    "train.grad_norm_p50": NUM,
    "train.grad_norm_p95": NUM,
    "train.grad_norm_p99": NUM,
    # run summary
    "steps_total": (int,),
    "final_loss": NUM,
    "restarts": (int,),
    "epochs": (int,),
    "batch_size": (int,),
    "history_len": (int,),
    "checkpoint_steps": (list,),
    "checkpoint_dir": (str,),
    "model_config": (dict,),
    "faults_injected": (dict,),
}

# timeline names the trainer emits (docs/TRAINING.md): the drill run
# must show the quarantine/retry plane, the kill run the resume plane.
REQUIRED_TRAIN_DRILL_EVENTS = {
    "step", "checkpoint", "anomaly", "retry", "fault_injected",
}
REQUIRED_TRAIN_KILL_EVENTS = {"step", "checkpoint", "restore", "restart"}
# the corrupt drill must light up the full SDC pipeline: suspicion,
# quarantine, and the deterministic-replay adjudication
REQUIRED_TRAIN_INTEGRITY_EVENTS = {
    "integrity.sdc_suspected", "integrity.replica_quarantined",
    "integrity.replay",
}


def fail(msg: str) -> "None":
    print(f"check_metrics_schema: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def check_metrics_dict(d: dict, source: str) -> None:
    for key, types in REQUIRED_METRIC_KEYS.items():
        if key not in d:
            fail(f"{source}: missing key {key!r}")
        if not isinstance(d[key], types):
            fail(
                f"{source}: key {key!r} has type "
                f"{type(d[key]).__name__}, expected one of "
                f"{[t.__name__ for t in types]} (value: {d[key]!r})"
            )


def check_events(path: str, n_requests: int) -> int:
    try:
        lines = open(path, encoding="utf-8").read().splitlines()
    except OSError as e:
        fail(f"events.jsonl unreadable: {e}")
    if not lines:
        fail("events.jsonl is empty")
    # line 1 is the dump header carrying the wall-clock anchor that
    # correlates traces across processes (docs/OBSERVABILITY.md)
    try:
        header = json.loads(lines[0])
    except json.JSONDecodeError as e:
        fail(f"events.jsonl header line is not JSON: {e}")
    if header.get("header") != "flight_recorder":
        fail(f"events.jsonl must open with the dump header, got {header}")
    if not isinstance(header.get("t0_unix"), (int, float)):
        fail(f"dump header lacks a numeric t0_unix anchor: {header}")
    spans: dict[int, list[str]] = {}
    names_seen: set[str] = set()
    for i, line in enumerate(lines[1:], 2):
        try:
            ev = json.loads(line)
        except json.JSONDecodeError as e:
            fail(f"events.jsonl line {i} is not JSON: {e}")
        if "t" not in ev or "name" not in ev:
            fail(f"events.jsonl line {i} lacks 't'/'name': {ev}")
        names_seen.add(ev["name"])
        if ev.get("span_name") == "request":
            spans.setdefault(ev["span"], []).append(ev["name"])
    missing_names = REQUIRED_EVENT_NAMES - names_seen
    if missing_names:
        fail(
            f"events.jsonl lacks engine event names {missing_names} "
            "(the trace exporter's tick/dispatch tracks key on them)"
        )
    if len(spans) != n_requests:
        fail(
            f"events.jsonl holds {len(spans)} request spans, expected "
            f"one per submitted request ({n_requests})"
        )
    for sid, names in spans.items():
        if names[0] != "start":
            fail(f"span {sid} does not open with 'start': {names}")
        missing = {"queued", "admitted", "prefill"} - set(names)
        if missing:
            fail(f"span {sid} lacks lifecycle events {missing}: {names}")
        if names[-1] not in ("completed", "expired", "failed", "stalled"):
            fail(f"span {sid} never reached a terminal status: {names}")
    return len(lines) - 1


def check_trace(path: str, n_requests: int) -> int:
    """One schema pass over an emitted Chrome trace-event JSON: valid
    structure, metadata naming, one complete request slice per
    submitted request, and populated tick + dispatch tracks."""
    try:
        doc = json.load(open(path, encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        fail(f"trace json unreadable at {path}: {e}")
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        fail(f"{path}: traceEvents must be a non-empty list")
    if not isinstance(doc.get("otherData", {}).get("t0_unix"),
                      (int, float)):
        fail(f"{path}: otherData.t0_unix anchor missing")
    for i, ev in enumerate(events):
        for key in ("name", "ph", "pid", "tid", "ts"):
            if key not in ev:
                fail(f"{path}: event {i} lacks {key!r}: {ev}")
        if ev["ph"] not in ("M", "X", "i"):
            fail(f"{path}: event {i} has unknown phase {ev['ph']!r}")
        if ev["ph"] == "X" and not isinstance(ev.get("dur"),
                                              (int, float)):
            fail(f"{path}: complete slice {i} lacks numeric dur: {ev}")
    meta_names = {
        ev["args"]["name"] for ev in events
        if ev["ph"] == "M" and ev["name"] == "process_name"
    }
    if {"serve.requests", "serve.engine"} - meta_names:
        fail(f"{path}: process metadata incomplete, got {meta_names}")
    req_slices = [
        ev for ev in events
        if ev["ph"] == "X" and ev["pid"] == 1
        and ev["name"].startswith("request ")
    ]
    if len(req_slices) != n_requests:
        fail(
            f"{path}: {len(req_slices)} request slices, expected one "
            f"per submitted request ({n_requests})"
        )
    tick_slices = [
        ev for ev in events
        if ev["ph"] == "X" and ev["pid"] == 2
        and ev["name"].startswith("tick ")
    ]
    dispatch_slices = [
        ev for ev in events
        if ev["ph"] == "X" and ev["pid"] == 2
        and ("decode[" in ev["name"] or "prefill[" in ev["name"])
    ]
    if not tick_slices:
        fail(f"{path}: no tick slices on the engine track")
    if not dispatch_slices:
        fail(f"{path}: no program-dispatch slices on the engine track")
    ts_order = [ev["ts"] for ev in events]
    meta_count = sum(1 for ev in events if ev["ph"] == "M")
    if ts_order[meta_count:] != sorted(ts_order[meta_count:]):
        fail(f"{path}: trace events are not ts-ordered")
    return len(events)


def check_hub_bundle(tdir: str, label: str,
                     want_sources: tuple) -> list:
    """Shared assertions on a TelemetryHub-merged ``--telemetry-dir``
    bundle: the ``telemetry_hub`` events header naming every expected
    source, the pre-registered ``alerts_*`` counters in the labeled
    exposition, the ``hub`` summary block in ``metrics.json``, and the
    supervisor/fleet compat dump. Returns the merged event lines."""
    epath = os.path.join(tdir, "events.jsonl")
    try:
        lines = open(epath, encoding="utf-8").read().splitlines()
    except OSError as e:
        fail(f"{label} events.jsonl unreadable: {e}")
    try:
        header = json.loads(lines[0])
    except (IndexError, json.JSONDecodeError) as e:
        fail(f"{label} events.jsonl header unreadable: {e}")
    if header.get("header") != "telemetry_hub":
        fail(
            f"{label} events.jsonl must open with the telemetry_hub "
            f"header (the MERGED bundle), got {header}"
        )
    missing = set(want_sources) - set(header.get("sources", []))
    if missing:
        fail(f"{label} hub header lacks sources {sorted(missing)}: "
             f"{header.get('sources')}")
    anchors = header.get("t0_unix")
    if not isinstance(anchors, dict) or not all(
            isinstance(v, (int, float)) for v in anchors.values()):
        fail(f"{label} hub header lacks per-source t0_unix anchors: "
             f"{anchors!r}")
    for ev_line in lines[1:]:
        try:
            ev = json.loads(ev_line)
        except json.JSONDecodeError as e:
            fail(f"{label} events.jsonl malformed line: {e}")
        for key in ("src", "wall", "t", "name"):
            if key not in ev:
                fail(f"{label} merged event lacks {key!r}: {ev}")
    prom = open(os.path.join(tdir, "metrics.prom"),
                encoding="utf-8").read()
    for kind in HUB_ALERT_KINDS:
        if f"alerts_{kind}_total" not in prom:
            fail(f"{label} metrics.prom lacks the pre-registered "
                 f"alerts_{kind}_total counter")
    mpath = os.path.join(tdir, "metrics.json")
    try:
        hub = json.load(open(mpath, encoding="utf-8")).get("hub")
    except (OSError, json.JSONDecodeError) as e:
        fail(f"{label} metrics.json unreadable: {e}")
    if not isinstance(hub, dict):
        fail(f"{label} metrics.json lacks the hub summary block")
    if set(hub.get("alerts", {})) != set(HUB_ALERT_KINDS):
        fail(f"{label} hub block's alert catalog is incomplete: "
             f"{sorted(hub.get('alerts', {}))}")
    missing = set(want_sources) - set(hub.get("sources", []))
    if missing:
        fail(f"{label} hub block lacks sources {sorted(missing)}")
    # the control plane's own recorder survives as a compat dump in
    # the old single-recorder format
    for compat in ("supervisor.events.jsonl",):
        cpath = os.path.join(tdir, compat)
        if not os.path.exists(cpath):
            continue
        chead = json.loads(open(cpath, encoding="utf-8").readline())
        if chead.get("header") != "flight_recorder":
            fail(f"{label} {compat} lost the flight_recorder format: "
                 f"{chead}")
    return lines


def load_flow_chains(tdir: str, label: str) -> dict:
    """``trace_id -> [(ph, source name, tid)]`` from a merged
    trace.json's flow arrows (``ph`` s/t/f), ts-ordered."""
    tpath = os.path.join(tdir, "trace.json")
    try:
        doc = json.load(open(tpath, encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        fail(f"{label} trace.json unreadable: {e}")
    pname = {
        ev["pid"]: ev["args"]["name"]
        for ev in doc["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "process_name"
    }
    chains: dict = {}
    for ev in sorted(
            (e for e in doc["traceEvents"] if e.get("ph") in "stf"),
            key=lambda e: e["ts"]):
        if ev.get("cat") != "request" or "id" not in ev:
            fail(f"{label} flow event lacks cat/id binding: {ev}")
        if ev["ph"] == "f" and ev.get("bp") != "e":
            fail(f"{label} flow finish not bound to enclosing slice "
                 f"(bp != 'e'): {ev}")
        chains.setdefault(ev["id"], []).append(
            (ev["ph"], pname.get(ev["pid"], f"pid{ev['pid']}"),
             ev["tid"])
        )
    for trace, hops in chains.items():
        phases = [ph for ph, _, _ in hops]
        if phases[0] != "s" or phases[-1] != "f":
            fail(f"{label} flow chain {trace} malformed: {phases}")
    return chains


def check_replica_mode(env: dict, repo: str) -> None:
    """Second smoke run with ``--replicas 2``: the JSON line switches to
    ``ReplicaSet.metrics_dict()`` and the telemetry bundle to the
    SUPERVISOR's recorder/registry (docs/OBSERVABILITY.md "Replicated
    serving metrics") — pin both shapes."""
    with tempfile.TemporaryDirectory() as tdir:
        cmd = [
            sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4",
            "serve", "--demo", "--slots", "2",
            "--requests", str(N_REQUESTS), "--max-new-tokens", "4",
            "--replicas", "2", "--hedge-ms", "50",
            # chunked + async through the SUPERVISOR: every replica
            # engine inherits the flags, and the hub bundle's detect()
            # pass below must stay quiet on this healthy async run
            "--prefill-chunk", "8", "--async-host",
            "--telemetry-dir", tdir,
        ]
        res = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            env=env, cwd=repo,
        )
        if res.returncode != 0:
            fail(f"serve --demo --replicas 2 exited {res.returncode}:\n"
                 f"{res.stderr}")
        out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
        if len(out_lines) != 1:
            fail(
                f"--replicas stdout must be exactly ONE JSON line, got "
                f"{len(out_lines)}:\n{res.stdout}"
            )
        try:
            md = json.loads(out_lines[0])
        except json.JSONDecodeError as e:
            fail(f"--replicas stdout line is not JSON: {e}")
        for key, types in REQUIRED_REPLICA_KEYS.items():
            if key not in md:
                fail(f"--replicas stdout: missing key {key!r}")
            if not isinstance(md[key], types):
                fail(
                    f"--replicas stdout: key {key!r} has type "
                    f"{type(md[key]).__name__}, expected one of "
                    f"{[t.__name__ for t in types]} (value: {md[key]!r})"
                )
        if md["replicas"] != 2:
            fail(f"--replicas 2 must report replicas == 2, got "
                 f"{md['replicas']!r}")
        if set(md["per_replica"]) != {"replica0", "replica1"}:
            fail(f"per_replica must hold replica0/replica1, got "
                 f"{sorted(md['per_replica'])}")
        for rname, sub in md["per_replica"].items():
            for key, types in REQUIRED_PER_REPLICA_KEYS.items():
                if key not in sub:
                    fail(f"per_replica.{rname}: missing key {key!r}")
                if not isinstance(sub[key], types):
                    fail(
                        f"per_replica.{rname}: key {key!r} has type "
                        f"{type(sub[key]).__name__}, expected one of "
                        f"{[t.__name__ for t in types]}"
                    )
        if md["completed"] != N_REQUESTS:
            fail(
                f"--replicas smoke run must complete all {N_REQUESTS} "
                f"requests, got {md['completed']}"
            )
        # the bundle is the supervisor's: control-plane counters in the
        # exposition, routed events in the timeline
        ppath = os.path.join(tdir, "metrics.prom")
        if not os.path.exists(ppath):
            fail("--replicas --telemetry-dir did not produce metrics.prom")
        prom = open(ppath, encoding="utf-8").read()
        for needle in ("serve_replica_failovers_total", "serve_hedges_total",
                       "serve_hedge_wasted_tokens_total",
                       "serve_drains_total",
                       # per-replica engine series fold into ONE family
                       # told apart by labels, not name prefixes
                       'serve_completed_total{replica="0"}',
                       'serve_completed_total{replica="1"}',
                       'serve_ttft_ms_count{replica="0"}'):
            if needle not in prom:
                fail(f"--replicas metrics.prom lacks {needle!r}")
        # the replicas split the traffic, but the fleet as a whole must
        # have chunked SOMETHING — a zero sum means the supervisor
        # dropped the engine kwargs
        if not sum(
            sub["chunked_prefills_total"]
            for sub in md["per_replica"].values()
        ) > 0:
            fail(
                "--replicas with --prefill-chunk: no replica reports "
                "chunked_prefills_total > 0"
            )
        lines = check_hub_bundle(
            tdir, "--replicas",
            ("hub", "supervisor", "replica0", "replica1"),
        )
        # the healthy-async-run contract (docs/PERFORMANCE.md "Chunked
        # prefill & async host loop"): pipelining must not smear the
        # tick-time distribution — the hub's tick_p99_drift detector
        # (write_bundle runs one detect() pass) stays QUIET
        hub_block = json.load(
            open(os.path.join(tdir, "metrics.json"), encoding="utf-8")
        ).get("hub", {})
        drift = hub_block.get("alerts", {}).get("tick_p99_drift")
        if drift != 0:
            fail(
                "--replicas --async-host: a healthy async run must keep "
                f"the tick_p99_drift detector quiet, got {drift!r}"
            )
        if not os.path.exists(
                os.path.join(tdir, "supervisor.events.jsonl")):
            fail("--replicas bundle lacks the supervisor.events.jsonl "
                 "compat dump")
        names = {json.loads(line)["name"] for line in lines[1:]}
        if "routed" not in names:
            fail(
                "--replicas events.jsonl lacks 'routed' control-plane "
                f"events (names seen: {sorted(names)})"
            )


def check_disagg_mode(env: dict, repo: str) -> None:
    """Disaggregated-fleet smoke run (``--disagg``): the JSON line
    switches to ``DisaggFleet.metrics_dict()`` (docs/SERVING.md
    "Disaggregated fleet") — fleet totals + per-role aggregates +
    per-replica dicts — and the telemetry bundle is the FLEET's
    recorder/registry (hand-off routings in the timeline, the fleet
    counters in the exposition). Pin all three shapes."""
    with tempfile.TemporaryDirectory() as tdir:
        cmd = [
            sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4",
            "serve", "--demo", "--slots", "2",
            "--requests", str(N_REQUESTS), "--max-new-tokens", "4",
            "--disagg", "--prefill-replicas", "1",
            "--decode-replicas", "2",
            "--autoscale", "max_decode=3,queue_high=8",
            # chunked backlogs on the PREFILL role (docs/SERVING.md
            # "Disaggregated serving"): the per-replica rollup below
            # must attribute the chunking to the prefill replica
            "--prefill-chunk", "8",
            "--telemetry-dir", tdir,
        ]
        res = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            env=env, cwd=repo,
        )
        if res.returncode != 0:
            fail(f"serve --demo --disagg exited {res.returncode}:\n"
                 f"{res.stderr}")
        out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
        if len(out_lines) != 1:
            fail(
                f"--disagg stdout must be exactly ONE JSON line, got "
                f"{len(out_lines)}:\n{res.stdout}"
            )
        try:
            md = json.loads(out_lines[0])
        except json.JSONDecodeError as e:
            fail(f"--disagg stdout line is not JSON: {e}")
        for key, types in REQUIRED_FLEET_KEYS.items():
            if key not in md:
                fail(f"--disagg stdout: missing key {key!r}")
            if not isinstance(md[key], types):
                fail(
                    f"--disagg stdout: key {key!r} has type "
                    f"{type(md[key]).__name__}, expected one of "
                    f"{[t.__name__ for t in types]} (value: {md[key]!r})"
                )
        if md["disagg"] is not True:
            fail("--disagg must report disagg == true")
        if (md["prefill_replicas"], md["decode_replicas"]) != (1, 2):
            fail(
                f"--prefill-replicas 1 --decode-replicas 2 must report "
                f"(1, 2), got ({md['prefill_replicas']}, "
                f"{md['decode_replicas']})"
            )
        if md["completed"] != N_REQUESTS:
            fail(
                f"--disagg smoke run must complete all {N_REQUESTS} "
                f"requests, got {md['completed']}"
            )
        if md["handoffs_total"] < 1:
            fail("--disagg run never routed a hand-off payload")
        if set(md["per_role"]) != {"prefill", "decode"}:
            fail(f"per_role must hold prefill/decode, got "
                 f"{sorted(md['per_role'])}")
        for role, sub in md["per_role"].items():
            for key, types in REQUIRED_FLEET_ROLE_KEYS.items():
                if key not in sub:
                    fail(f"per_role.{role}: missing key {key!r}")
                if not isinstance(sub[key], types):
                    fail(
                        f"per_role.{role}: key {key!r} has type "
                        f"{type(sub[key]).__name__}, expected one of "
                        f"{[t.__name__ for t in types]}"
                    )
        if md["per_role"]["prefill"]["handoffs_out_total"] < 1:
            fail("the prefill role reported zero hand-offs out")
        if md["per_role"]["decode"]["handoffs_adopted_total"] < 1:
            fail("the decode role reported zero adopted hand-offs")
        if not md["per_replica"]:
            fail("--disagg per_replica is empty")
        for rname, sub in md["per_replica"].items():
            for key, types in REQUIRED_FLEET_PER_REPLICA_KEYS.items():
                if key not in sub:
                    fail(f"per_replica.{rname}: missing key {key!r}")
                if not isinstance(sub[key], types):
                    fail(
                        f"per_replica.{rname}: key {key!r} has type "
                        f"{type(sub[key]).__name__}, expected one of "
                        f"{[t.__name__ for t in types]}"
                    )
        # --prefill-chunk on a fleet: ONLY the prefill role fills, so
        # the chunk counter must land on prefill replicas and stay 0 on
        # decode replicas (which adopt finished KV, never filling)
        for rname, sub in md["per_replica"].items():
            if sub["role"] == "prefill" and sub["submitted"] > 0:
                if not sub["chunked_prefills_total"] > 0:
                    fail(
                        f"per_replica.{rname}: a prefill-role replica "
                        "that admitted requests under --prefill-chunk "
                        "must report chunked_prefills_total > 0"
                    )
            if sub["role"] == "decode":
                if sub["chunked_prefills_total"] != 0:
                    fail(
                        f"per_replica.{rname}: a decode-role replica "
                        "adopting hand-offs must report "
                        "chunked_prefills_total == 0, got "
                        f"{sub['chunked_prefills_total']}"
                    )
        # the bundle is the fleet's: hand-off/index/autoscale counters
        # in the exposition, routing events in the timeline
        ppath = os.path.join(tdir, "metrics.prom")
        if not os.path.exists(ppath):
            fail("--disagg --telemetry-dir did not produce metrics.prom")
        prom = open(ppath, encoding="utf-8").read()
        for needle in ("serve_fleet_handoffs_total",
                       "serve_fleet_prefix_hits_total",
                       "serve_fleet_prefill_tokens_saved_total",
                       "serve_scale_ups_total", "serve_scale_downs_total",
                       "serve_replica_failovers_total",
                       "serve_drains_total",
                       # per-engine series labeled by replica AND role
                       'serve_completed_total{replica="0",role="prefill"}',
                       'serve_ttft_ms_count{replica="1",role="decode"}'):
            if needle not in prom:
                fail(f"--disagg metrics.prom lacks {needle!r}")
        lines = check_hub_bundle(
            tdir, "--disagg",
            ("hub", "fleet", "prefill0", "decode1", "decode2"),
        )
        if not os.path.exists(
                os.path.join(tdir, "supervisor.events.jsonl")):
            fail("--disagg bundle lacks the supervisor.events.jsonl "
                 "compat dump")
        names = {json.loads(line)["name"] for line in lines[1:]}
        for needle in ("routed", "handoff_routed"):
            if needle not in names:
                fail(
                    f"--disagg events.jsonl lacks {needle!r} "
                    f"control-plane events (names seen: {sorted(names)})"
                )
        # every hand-off is a multi-fragment request: the merged trace
        # must stitch it with a flow arrow crossing replica tracks
        chains = load_flow_chains(tdir, "--disagg")
        crossed = [
            t for t, hops in chains.items()
            if {src for _, src, _ in hops} >= {"prefill0"}
            and any(src.startswith("decode") for _, src, _ in hops)
        ]
        if not crossed:
            fail(
                "--disagg trace.json has no flow arrow crossing the "
                f"prefill0 -> decode tracks (chains: {chains})"
            )
    print(
        f"check_metrics_schema: OK — --disagg line carries "
        f"{len(REQUIRED_FLEET_KEYS)} fleet keys, "
        f"{len(REQUIRED_FLEET_ROLE_KEYS)} per-role keys and "
        f"{len(REQUIRED_FLEET_PER_REPLICA_KEYS)} per-replica keys; "
        f"hand-off plane routed {md['handoffs_total']} payloads; fleet "
        f"counters present in the exposition"
    )


#: engine-level keys on a ``--models`` JSON line
#: (``MultiModelEngine.metrics_dict()`` + the demo's run config)
REQUIRED_MULTIMODEL_KEYS = {
    "multimodel": (bool,),
    "deployments": (int,),
    "device_budget": (int, type(None)),
    "ticks": (int,),
    "submitted": (int,),
    "completed": (int,),
    "failed": (int,),
    "rejected": (int,),
    "per_model": (dict,),
    "registry": (dict,),
    "models_spec": (str,),
}

#: keys every per-model nested dict carries regardless of kind
REQUIRED_MULTIMODEL_MODEL_KEYS = {
    "kind": (str,),
    "model": (str,),
    "submitted": (int,),
    "completed": (int,),
    "failed": (int,),
    "rejected": (int,),
    "tokens_generated": (int,),
}


def check_multimodel_mode(env: dict, repo: str) -> None:
    """Multi-model smoke run (``--multi-model``): one engine hosting an
    LM plus two stateless deployments (one ONNX-imported), driven
    through the real ``serve --models`` CLI (docs/SERVING.md
    "Multi-model serving"). Pins the JSON line's engine totals +
    per-model nested dicts + the shared registry's ``model{name}.``
    namespaces, the ``model{name}_serve_*`` Prometheus families, and
    the routed/deployment_added control-plane timeline."""
    with tempfile.TemporaryDirectory() as tdir:
        onnx_path = os.path.join(tdir, "clf.onnx")
        # author the foreign graph the ingestion path imports — a tiny
        # flax MLP exported to ONNX in its own subprocess (this gate
        # itself must not import jax)
        export = subprocess.run(
            [sys.executable, "-c", (
                "import jax, jax.numpy as jnp\n"
                "from mmlspark_tpu.models import build_model\n"
                "from mmlspark_tpu.models.onnx_export import save_onnx\n"
                "g = build_model('mlp', num_outputs=3, hidden=(16,))\n"
                "v = g.init(jax.random.PRNGKey(0), "
                "jnp.zeros((1, 8), jnp.float32))\n"
                f"save_onnx(g, v, (1, 8), {onnx_path!r})\n"
            )],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=repo,
        )
        if export.returncode != 0:
            fail(f"ONNX export helper exited {export.returncode}:\n"
                 f"{export.stderr}")
        spec = (
            "lm=transformer_lm:slots=2:cache_len=32:vocab_size=16:"
            "d_model=32:heads=2:depth=1:max_len=32;"
            "clf=mlp:max_batch=4:num_outputs=3:hidden=16x16:"
            "input_shape=8;"
            f"ox=onnx:max_batch=4:path={onnx_path}"
        )
        cmd = [
            sys.executable, "-m", "mmlspark_tpu",
            "serve", "--demo", "--models", spec,
            "--device-budget", "2",
            "--requests", str(N_REQUESTS), "--max-new-tokens", "4",
            "--telemetry-dir", tdir,
        ]
        res = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            env=env, cwd=repo,
        )
        if res.returncode != 0:
            fail(f"serve --models exited {res.returncode}:\n"
                 f"{res.stderr}")
        out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
        if len(out_lines) != 1:
            fail(
                f"--models stdout must be exactly ONE JSON line, got "
                f"{len(out_lines)}:\n{res.stdout}"
            )
        try:
            md = json.loads(out_lines[0])
        except json.JSONDecodeError as e:
            fail(f"--models stdout line is not JSON: {e}")
        for key, types in REQUIRED_MULTIMODEL_KEYS.items():
            if key not in md:
                fail(f"--models stdout: missing key {key!r}")
            if not isinstance(md[key], types):
                fail(
                    f"--models stdout: key {key!r} has type "
                    f"{type(md[key]).__name__}, expected one of "
                    f"{[t.__name__ for t in types]} (value: {md[key]!r})"
                )
        if md["multimodel"] is not True:
            fail("--models must report multimodel == true")
        if md["deployments"] != 3:
            fail(f"a 3-entry spec must report deployments == 3, got "
                 f"{md['deployments']}")
        # the demo submits N_REQUESTS per deployment
        want = 3 * N_REQUESTS
        if md["completed"] != want:
            fail(
                f"--models smoke run must complete all {want} requests "
                f"({N_REQUESTS} per deployment), got {md['completed']}"
            )
        if set(md["per_model"]) != {"lm", "clf", "ox"}:
            fail(f"per_model must hold lm/clf/ox, got "
                 f"{sorted(md['per_model'])}")
        for name, sub in md["per_model"].items():
            for key, types in REQUIRED_MULTIMODEL_MODEL_KEYS.items():
                if key not in sub:
                    fail(f"per_model.{name}: missing key {key!r}")
                if not isinstance(sub[key], types):
                    fail(
                        f"per_model.{name}: key {key!r} has type "
                        f"{type(sub[key]).__name__}, expected one of "
                        f"{[t.__name__ for t in types]}"
                    )
        if md["per_model"]["lm"]["kind"] != "lm":
            fail("per_model.lm must be kind 'lm'")
        # the LM deployment keeps its compile pins on the shared line
        if not md["per_model"]["lm"]["decode_compile_count"] >= 1:
            fail("per_model.lm must report decode_compile_count >= 1")
        for name in ("clf", "ox"):
            sub = md["per_model"][name]
            if sub["kind"] != "batch":
                fail(f"per_model.{name} must be kind 'batch'")
            if not (1 <= sub["batch_compile_count"]
                    <= sub["num_batch_buckets"]):
                fail(
                    f"per_model.{name}: batch_compile_count "
                    f"{sub['batch_compile_count']} outside "
                    f"[1, num_batch_buckets="
                    f"{sub['num_batch_buckets']}] — the bucket-ladder "
                    "compile pin broke"
                )
        # the SHARED registry: per-model namespaces, no collisions
        reg = md["registry"]
        for name in ("lm", "clf", "ox"):
            key = f"model{name}.serve.completed"
            if reg.get(key) != N_REQUESTS:
                fail(
                    f"registry key {key!r} must equal {N_REQUESTS}, "
                    f"got {reg.get(key)!r}"
                )
        ppath = os.path.join(tdir, "metrics.prom")
        if not os.path.exists(ppath):
            fail("--models --telemetry-dir did not produce metrics.prom")
        prom = open(ppath, encoding="utf-8").read()
        # the hub translates the shared registry's model{name}. name
        # prefixes into ONE serve_* family per metric with model labels
        for needle in ('serve_ttft_ms_count{model="lm"}',
                       'serve_ttft_ms_count{model="clf"}',
                       'serve_ttft_ms_count{model="ox"}',
                       'serve_completed_total{model="lm"}',
                       'serve_completed_total{model="clf"}',
                       'serve_completed_total{model="ox"}'):
            if needle not in prom:
                fail(f"--models metrics.prom lacks {needle!r}")
        samples = [
            ln.split(" ")[0] for ln in prom.splitlines()
            if ln and not ln.startswith("#")
        ]
        if len(samples) != len(set(samples)):
            dupes = sorted({s for s in samples if samples.count(s) > 1})
            fail(f"--models metrics.prom has duplicate sample lines "
                 f"(label collision): {dupes[:5]}")
        mpath = os.path.join(tdir, "metrics.json")
        if not os.path.exists(mpath):
            fail("--models --telemetry-dir did not produce metrics.json")
        persisted = json.load(open(mpath, encoding="utf-8"))
        missing = set(REQUIRED_MULTIMODEL_KEYS) - set(persisted)
        if missing:
            fail(f"--models metrics.json lacks keys {missing}")
        lines = check_hub_bundle(
            tdir, "--models",
            ("hub", "multimodel", "model:lm", "model:clf", "model:ox"),
        )
        names = set()
        routed_models = set()
        for line in lines[1:]:
            ev = json.loads(line)
            names.add(ev["name"])
            if ev["name"] == "routed":
                routed_models.add(ev.get("attrs", {}).get("model"))
        for needle in ("deployment_added", "routed", "batch_dispatch"):
            if needle not in names:
                fail(
                    f"--models events.jsonl lacks {needle!r} events "
                    f"(names seen: {sorted(names)})"
                )
        if routed_models != {"lm", "clf", "ox"}:
            fail(
                f"routed events must carry every model name, got "
                f"{sorted(routed_models)}"
            )
    print(
        f"check_metrics_schema: OK — --models line carries "
        f"{len(REQUIRED_MULTIMODEL_KEYS)} engine keys and "
        f"{len(REQUIRED_MULTIMODEL_MODEL_KEYS)}+ per-model keys for 3 "
        f"deployments; {md['completed']} requests completed under one "
        f"device budget; model{{name}} namespaces collision-free in "
        f"the exposition"
    )


def check_tracing_mode(env: dict, repo: str) -> None:
    """Distributed-tracing acceptance drill (``--tracing``): a SEEDED
    ``--disagg --faults`` run under replica kills. The merged bundle
    must stitch every request into one causal chain — the hand-off's
    flow arrow crossing the prefill -> decode replica tracks, and a
    killed replica's failover replay joining the ORIGINAL submit's
    trace id on a rebuilt (``#1``-generation) track
    (docs/OBSERVABILITY.md "Distributed tracing")."""
    with tempfile.TemporaryDirectory() as tdir:
        cmd = [
            sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4",
            "serve", "--demo", "--slots", "2",
            "--requests", "6", "--max-new-tokens", "6",
            "--disagg", "--prefill-replicas", "1",
            "--decode-replicas", "2",
            "--faults", "seed=7,serve.health:kill=0.35",
            "--telemetry-dir", tdir,
        ]
        res = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            env=env, cwd=repo,
        )
        if res.returncode != 0:
            fail(f"serve --demo --disagg --faults exited "
                 f"{res.returncode}:\n{res.stderr}")
        out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
        if len(out_lines) != 1:
            fail(f"--tracing stdout must be exactly ONE JSON line, got "
                 f"{len(out_lines)}:\n{res.stdout}")
        md = json.loads(out_lines[0])
        if md["completed"] != 6:
            fail(f"--tracing drill must complete all 6 requests "
                 f"through the kills, got {md['completed']}")
        if md["replica_failovers_total"] < 1:
            fail("--tracing drill's seeded kill spec fired no failover")
        if md["handoffs_total"] < 1:
            fail("--tracing drill routed no hand-off payloads")
        lines = check_hub_bundle(
            tdir, "--tracing", ("hub", "fleet", "prefill0"),
        )
        header = json.loads(lines[0])
        rebuilt = [s for s in header["sources"] if "#" in s]
        if not rebuilt:
            fail(
                "--tracing hub header shows no rebuilt-engine "
                f"generation (a '#1' source): {header['sources']}"
            )
        chains = load_flow_chains(tdir, "--tracing")
        if not chains:
            fail("--tracing trace.json holds no flow arrows at all")
        crossed = [
            t for t, hops in chains.items()
            if any(src == "prefill0" for _, src, _ in hops)
            and any(src.startswith("decode") for _, src, _ in hops)
        ]
        if not crossed:
            fail(
                "--tracing: no flow arrow crosses the prefill0 -> "
                f"decode replica tracks (chains: {chains})"
            )
        replayed = [
            t for t, hops in chains.items()
            if any("#" in src for _, src, _ in hops)
        ]
        if not replayed:
            fail(
                "--tracing: no failover replay joined its original "
                "trace id on a rebuilt-engine track (chains: "
                f"{chains})"
            )
        # the replayed chain's arrow STARTS before the kill — same
        # trace id binds the original submit's fragment to the rebuilt
        # engine's, which is the whole point of propagation
        for t in replayed:
            first_ph, first_src, _ = chains[t][0]
            if first_ph != "s" or "#" in first_src:
                fail(
                    f"--tracing: replayed chain {t} does not start "
                    f"from a pre-kill fragment: {chains[t]}"
                )
    print(
        f"check_metrics_schema: OK — --tracing drill completed 6/6 "
        f"requests through {md['replica_failovers_total']} failover(s); "
        f"{len(chains)} flow chain(s) in the merged trace, "
        f"{len(crossed)} crossing prefill -> decode tracks, "
        f"{len(replayed)} linking a failover replay to its original "
        f"submit via the same trace id (rebuilt sources: {rebuilt})"
    )


def check_int8_mode(env: dict, repo: str) -> None:
    """Third smoke pass: the same demo config at ``--kv-dtype bf16``
    and ``--kv-dtype int8`` (+ ``--quantize-weights``). Pins the
    quantized-decode surface (docs/PERFORMANCE.md "Quantized decode"):
    the JSON line reports the configured kv_dtype, the int8 pool's
    per-device KV bytes land strictly below the bf16 pool's, and the
    run still completes every request."""
    def one(kv_dtype: str) -> dict:
        cmd = [
            sys.executable, "-m", "mmlspark_tpu",
            "serve", "--demo", "--slots", "2",
            "--requests", str(N_REQUESTS), "--max-new-tokens", "4",
            "--kv-dtype", kv_dtype,
        ]
        if kv_dtype == "int8":
            cmd.append("--quantize-weights")
        res = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            env=env, cwd=repo,
        )
        if res.returncode != 0:
            fail(f"serve --demo --kv-dtype {kv_dtype} exited "
                 f"{res.returncode}:\n{res.stderr}")
        out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
        if len(out_lines) != 1:
            fail(
                f"--kv-dtype {kv_dtype} stdout must be exactly ONE "
                f"JSON line, got {len(out_lines)}:\n{res.stdout}"
            )
        try:
            md = json.loads(out_lines[0])
        except json.JSONDecodeError as e:
            fail(f"--kv-dtype {kv_dtype} stdout line is not JSON: {e}")
        check_metrics_dict(md, f"--kv-dtype {kv_dtype} stdout")
        if md.get("kv_dtype") != kv_dtype:
            fail(
                f"a --kv-dtype {kv_dtype} run must report kv_dtype == "
                f"{kv_dtype!r}, got {md.get('kv_dtype')!r}"
            )
        if md.get("completed") != N_REQUESTS:
            fail(
                f"--kv-dtype {kv_dtype} run must complete all "
                f"{N_REQUESTS} requests, got {md.get('completed')}"
            )
        return md
    bf16 = one("bf16")
    int8 = one("int8")
    b_bytes = bf16["cache_pool_bytes_per_device"]
    q_bytes = int8["cache_pool_bytes_per_device"]
    if not q_bytes < b_bytes:
        fail(
            f"the int8 pool must hold fewer per-device KV bytes than "
            f"the bf16 pool at the same geometry, got int8={q_bytes} "
            f"vs bf16={b_bytes}"
        )


def _run_train_demo(env: dict, repo: str, tdir: str, faults: str,
                    label: str, extra: tuple = ()) -> tuple[dict, set]:
    """One ``train`` CLI run at smoke scale with an injected-fault
    spec; returns (metrics dict, event names seen). The injector's
    stream is seeded, so the same spec fires the same faults every
    run — the gate can pin which planes lit up."""
    cmd = [
        sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4",
        "train", "--epochs", "2", "--samples", "96",
        "--batch-size", "32", "--seed", "0", "--checkpoint-every", "1",
        "--anomaly-limit", "8", "--faults", faults,
        "--telemetry-dir", tdir,
        "--checkpoint-dir", os.path.join(tdir, "ck"),
        *extra,
    ]
    res = subprocess.run(
        cmd, capture_output=True, text=True, timeout=300,
        env=env, cwd=repo,
    )
    if res.returncode != 0:
        fail(f"train ({label}) exited {res.returncode}:\n{res.stderr}")
    out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
    if len(out_lines) != 1:
        fail(
            f"train ({label}) stdout must be exactly ONE JSON line, "
            f"got {len(out_lines)}:\n{res.stdout}"
        )
    try:
        md = json.loads(out_lines[0])
    except json.JSONDecodeError as e:
        fail(f"train ({label}) stdout line is not JSON: {e}")
    for key, types in REQUIRED_TRAIN_KEYS.items():
        if key not in md:
            fail(f"train ({label}) stdout: missing key {key!r}")
        if not isinstance(md[key], types):
            fail(
                f"train ({label}) stdout: key {key!r} has type "
                f"{type(md[key]).__name__}, expected one of "
                f"{[t.__name__ for t in types]} (value: {md[key]!r})"
            )
    mpath = os.path.join(tdir, "metrics.json")
    if not os.path.exists(mpath):
        fail(f"train ({label}) --telemetry-dir produced no metrics.json")
    persisted = json.load(open(mpath, encoding="utf-8"))
    missing = set(REQUIRED_TRAIN_KEYS) - set(persisted)
    if missing:
        fail(f"train ({label}) metrics.json lacks keys {missing}")
    epath = os.path.join(tdir, "events.jsonl")
    try:
        lines = open(epath, encoding="utf-8").read().splitlines()
    except OSError as e:
        fail(f"train ({label}) events.jsonl unreadable: {e}")
    if not lines:
        fail(f"train ({label}) events.jsonl is empty")
    try:
        header = json.loads(lines[0])
    except json.JSONDecodeError as e:
        fail(f"train ({label}) events.jsonl header is not JSON: {e}")
    if header.get("header") != "flight_recorder":
        fail(f"train ({label}) events.jsonl must open with the dump "
             f"header, got {header}")
    if not isinstance(header.get("t0_unix"), (int, float)):
        fail(f"train ({label}) dump header lacks numeric t0_unix: "
             f"{header}")
    names: set = set()
    for i, line in enumerate(lines[1:], 2):
        try:
            ev = json.loads(line)
        except json.JSONDecodeError as e:
            fail(f"train ({label}) events.jsonl line {i} is not "
                 f"JSON: {e}")
        if "t" not in ev or "name" not in ev:
            fail(f"train ({label}) events.jsonl line {i} lacks "
                 f"'t'/'name': {ev}")
        names.add(ev["name"])
    # step accounting must hold across faults: 96 samples / 32 batch
    # x 2 epochs = 6 optimizer steps, every one of them exactly once
    if md["steps_total"] != 6:
        fail(
            f"train ({label}): the smoke geometry runs exactly 6 "
            f"steps, got steps_total={md['steps_total']} (a crash or "
            "retry double-advanced or lost a step)"
        )
    ck = md["checkpoint_steps"]
    if not ck or ck != sorted(ck) or not all(
            isinstance(s, int) for s in ck):
        fail(f"train ({label}): checkpoint_steps must be a non-empty "
             f"ascending int list, got {ck!r}")
    if ck[-1] != 5:
        fail(f"train ({label}): the final committed checkpoint must "
             f"be step 5, got {ck[-1]}")
    return md, names


def check_train_mode(env: dict, repo: str) -> None:
    """Training telemetry gate (``--train``): two seeded fault drills
    through the real ``train`` CLI (docs/TRAINING.md). The drill run
    pressures the quarantine/retry plane (``train.data`` poison +
    ``train.step`` transients); the kill run crashes the trainer
    mid-epoch and pins the resume plane (``restore``/``restart``
    events, no lost or double-counted steps). Both pin the full
    ``REQUIRED_TRAIN_KEYS`` stdout/metrics.json schema."""
    with tempfile.TemporaryDirectory() as tdir:
        md, names = _run_train_demo(
            env, repo, tdir,
            "seed=5,train.step:kill=0.12,train.step:transient=0.10,"
            "train.data:poison=0.10",
            "drill",
        )
        missing = REQUIRED_TRAIN_DRILL_EVENTS - names
        if missing:
            fail(f"train (drill) events.jsonl lacks {missing} "
                 f"(names seen: {sorted(names)})")
        if md["train.anomalies_skipped"] < 1:
            fail("train (drill): the poison spec must quarantine at "
                 "least one anomalous step")
        if md["train.retries_total"] < 1:
            fail("train (drill): the transient spec must drive at "
                 "least one retry")
        if md["train.faults_injected_total"] != sum(
                md["faults_injected"].values()):
            fail(
                "train (drill): train.faults_injected_total "
                f"({md['train.faults_injected_total']}) disagrees with "
                f"the injector's counts ({md['faults_injected']})"
            )
        ppath = os.path.join(tdir, "metrics.prom")
        if not os.path.exists(ppath):
            fail("train (drill) --telemetry-dir produced no "
                 "metrics.prom")
        prom = open(ppath, encoding="utf-8").read()
        for needle in ("train_retries_total", "train_anomalies_skipped_total",
                       "train_checkpoints_total",
                       "train_checkpoint_failures_total",
                       "train_faults_injected_total",
                       "# TYPE train_grad_accum gauge",
                       "train_step_ms_bucket{", "train_loss_sum",
                       'le="+Inf"'):
            if needle not in prom:
                fail(f"train (drill) metrics.prom lacks {needle!r}")
        if "_total_total" in prom:
            fail("train (drill) metrics.prom double-suffixed a "
                 "counter name")
    with tempfile.TemporaryDirectory() as tdir:
        md2, names2 = _run_train_demo(
            env, repo, tdir, "seed=5,train.step:kill=0.15", "kill",
        )
        missing = REQUIRED_TRAIN_KILL_EVENTS - names2
        if missing:
            fail(f"train (kill) events.jsonl lacks {missing} "
                 f"(names seen: {sorted(names2)})")
        if md2["restarts"] < 1:
            fail("train (kill): the kill spec must crash the trainer "
                 "at least once")
    with tempfile.TemporaryDirectory() as tdir:
        # integrity drill (docs/TRAINING.md "Integrity audits"): a
        # seeded train.step bit-flip must be caught by the in-graph
        # checksum audit, the divergent replica quarantined, and the
        # deterministic replay adjudicated — with no checkpoint
        # checksum failures on this surface
        md3, names3 = _run_train_demo(
            env, repo, tdir, "seed=3,train.step:corrupt=0.2",
            "corrupt", extra=("--audit-every", "2"),
        )
        missing = REQUIRED_TRAIN_INTEGRITY_EVENTS - names3
        if missing:
            fail(f"train (corrupt) events.jsonl lacks {missing} "
                 f"(names seen: {sorted(names3)})")
        if md3["train.integrity.audits"] < 1:
            fail("train (corrupt): --audit-every 2 must run at least "
                 "one integrity audit")
        if md3["train.integrity.sdc_suspected"] < 1:
            fail("train (corrupt): the seeded bit-flip spec must "
                 "trip at least one cross-replica divergence audit")
        adjudicated = (md3["train.integrity.replay_transient_sdc"]
                       + md3["train.integrity.replay_software_nondeterminism"])
        if adjudicated != md3["train.integrity.sdc_suspected"]:
            fail(
                "train (corrupt): every suspected SDC must get a "
                f"replay verdict — {md3['train.integrity.sdc_suspected']}"
                f" suspected vs {adjudicated} adjudicated"
            )
        if md3["train.integrity.checksum_failures"] != 0:
            fail("train (corrupt): a step-level drill must not report "
                 "checkpoint checksum failures")
    print(
        f"check_metrics_schema: OK — --train line carries "
        f"{len(REQUIRED_TRAIN_KEYS)} keys on both surfaces; drill run "
        f"quarantined {md['train.anomalies_skipped']} step(s) and "
        f"retried {md['train.retries_total']} transient(s); kill run "
        f"survived {md2['restarts']} crash(es) with all 6 steps "
        f"accounted for; corrupt run caught "
        f"{md3['train.integrity.sdc_suspected']} bit-flip(s) across "
        f"{md3['train.integrity.audits']} audit(s); train_* counters "
        f"present in the exposition"
    )


def main() -> None:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if "--disagg" in sys.argv[1:]:
        # the disagg gate in tools/ci.sh runs this surface on its own
        # (the default run keeps the historical three-surface sweep)
        check_disagg_mode(env, repo)
        return
    if "--train" in sys.argv[1:]:
        # the train-resilience gate likewise runs on its own
        check_train_mode(env, repo)
        return
    if "--multi-model" in sys.argv[1:]:
        # the multi-model gate runs the serve --models surface on its own
        check_multimodel_mode(env, repo)
        return
    if "--tracing" in sys.argv[1:]:
        # the distributed-tracing gate: seeded disagg + faults drill
        check_tracing_mode(env, repo)
        return
    with tempfile.TemporaryDirectory() as tdir:
        # --mesh makes the run exercise the SHARDED engine, so the gate
        # also pins the mesh topology keys' populated form
        cmd = [
            sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4",
            "serve", "--demo", "--slots", "2",
            "--requests", str(N_REQUESTS), "--max-new-tokens", "4",
            "--mesh", "data=2,model=2",
            # the PAGED pool (docs/SERVING.md "Paged KV cache"): the
            # same engine contract plus the paging metric keys in
            # populated form — page_utilization must be a number here,
            # not the dense pool's null
            "--paged",
            # chunked prefill + async host loop (docs/PERFORMANCE.md
            # "Chunked prefill & async host loop") stacked on the mesh
            # + paged run: the gate pins the populated form of the new
            # keys AND that the full flag combination keeps serving
            "--prefill-chunk", "8", "--async-host",
            "--telemetry-dir", tdir,
            # generous targets: the SLO plane runs (declared state,
            # window arithmetic, per-tick evaluation) without actually
            # shedding a smoke-scale CPU run
            "--slo", "ttft_p99_ms=60000,per_token_p99_ms=60000,"
            "error_rate=0.99",
            # exercise the explicit flag too; the --telemetry-dir
            # bundle writes its own trace.json alongside
            "--trace-out", os.path.join(tdir, "trace_out.json"),
        ]
        res = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            env=env, cwd=repo,
        )
        if res.returncode != 0:
            fail(f"serve --demo exited {res.returncode}:\n{res.stderr}")
        out_lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
        if len(out_lines) != 1:
            fail(
                f"stdout must be exactly ONE JSON line, got "
                f"{len(out_lines)}:\n{res.stdout}"
            )
        try:
            stdout_metrics = json.loads(out_lines[0])
        except json.JSONDecodeError as e:
            fail(f"stdout line is not JSON: {e}")
        check_metrics_dict(stdout_metrics, "stdout")
        if stdout_metrics.get("mesh_shape") != {"data": 2, "model": 2}:
            fail(
                "stdout: a --mesh data=2,model=2 run must report "
                f"mesh_shape {{'data': 2, 'model': 2}}, got "
                f"{stdout_metrics.get('mesh_shape')!r}"
            )
        if stdout_metrics.get("mesh_devices") != 4:
            fail(
                "stdout: mesh_devices must be 4 on a 2x2 mesh, got "
                f"{stdout_metrics.get('mesh_devices')!r}"
            )
        if not stdout_metrics.get("cache_pool_bytes_per_device", 0) > 0:
            fail("stdout: cache_pool_bytes_per_device must be positive")
        for key in ("page_size", "pages_total"):
            if not stdout_metrics.get(key, 0) > 0:
                fail(f"stdout: a --paged run must report positive {key}")
        if not isinstance(stdout_metrics.get("page_utilization"), NUM):
            fail(
                "stdout: a --paged run must report numeric "
                f"page_utilization, got "
                f"{stdout_metrics.get('page_utilization')!r}"
            )
        # chunked/async populated form: the run passed both flags, so
        # the inert defaults (0 everywhere) would mean the CLI dropped
        # them on the floor
        if stdout_metrics.get("prefill_chunk") != 8:
            fail(
                "stdout: a --prefill-chunk 8 run must report "
                f"prefill_chunk == 8, got "
                f"{stdout_metrics.get('prefill_chunk')!r}"
            )
        if stdout_metrics.get("async_host") != 1:
            fail("stdout: an --async-host run must report async_host == 1")
        if not stdout_metrics.get("chunked_prefills_total", 0) > 0:
            fail(
                "stdout: a chunked run that admitted requests must "
                "report positive chunked_prefills_total, got "
                f"{stdout_metrics.get('chunked_prefills_total')!r}"
            )
        if not isinstance(stdout_metrics.get("host_idle_fraction"), NUM):
            fail(
                "stdout: a run with ticks must report numeric "
                "host_idle_fraction, got "
                f"{stdout_metrics.get('host_idle_fraction')!r}"
            )

        mpath = os.path.join(tdir, "metrics.json")
        if not os.path.exists(mpath):
            fail("--telemetry-dir did not produce metrics.json")
        check_metrics_dict(
            json.load(open(mpath, encoding="utf-8")), "metrics.json"
        )
        if stdout_metrics.get("slo", {}).get("declared") is not True:
            fail("stdout: a --slo run must report slo.declared == true")
        n_events = check_events(
            os.path.join(tdir, "events.jsonl"), N_REQUESTS
        )
        n_trace = check_trace(
            os.path.join(tdir, "trace.json"), N_REQUESTS
        )
        check_trace(os.path.join(tdir, "trace_out.json"), N_REQUESTS)
        ppath = os.path.join(tdir, "metrics.prom")
        if not os.path.exists(ppath):
            fail("--telemetry-dir did not produce metrics.prom")
        prom = open(ppath, encoding="utf-8").read()
        for needle in ("# TYPE perf_mfu gauge", "serve_ttft_ms_bucket{",
                       'le="+Inf"', "serve_submitted_total"):
            if needle not in prom:
                fail(f"metrics.prom lacks {needle!r}")
    check_replica_mode(env, repo)
    check_int8_mode(env, repo)
    print(
        f"check_metrics_schema: OK — {len(REQUIRED_METRIC_KEYS)} metric "
        f"keys on both surfaces, {N_REQUESTS} complete request spans "
        f"across {n_events} events, {n_trace} trace events, prom "
        f"exposition present; --replicas 2 line carries "
        f"{len(REQUIRED_REPLICA_KEYS)} control-plane keys + "
        f"{len(REQUIRED_PER_REPLICA_KEYS)} per-replica keys; int8 pool "
        f"reports fewer per-device KV bytes than bf16"
    )


if __name__ == "__main__":
    main()
