"""Replicated serving control plane (ISSUE 10 tentpole).

The contract under test (docs/SERVING.md "Replicated serving"): a
``ReplicaSet`` of health-checked ``ServeEngine`` replicas behind one
``submit()/run()`` facade survives replica kills, failed health probes,
hedged duplicates, and drains — and every final token stream stays
BIT-IDENTICAL to ``generate()`` (the no-failure oracle), exactly one
result per submitted request. Failover restores the killed replica
from its last PERIODIC snapshot and re-routes in-flight requests
through the emitted-prefix resume path; hedging is
first-committed-wins with wasted-token accounting; drain migrates
pending requests losslessly. Per-replica invariants (compile-count
pins, one host sync per decode block) hold exactly as on an
unsupervised engine — asserted under ``serve_compile_guard`` on
single-device AND 2x2-mesh replicas.

Satellites ride here too: EngineKilled parks device resources
deterministically (pool drained, paged refcounts consistent, step()
refuses, in-process restore works); the ``serve.snapshot`` fault makes
a torn checkpoint non-restorable (the previous one survives); the
paged + prefix-cache engine on a 2x2 mesh round-trips
snapshot/restore under an active fault schedule with refcount totals
equal to mapped references.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import (
    EngineKilled,
    Fault,
    FaultInjector,
    TransientFault,
    parse_fault_spec,
)
from mmlspark_tpu.serve import ReplicaSet, ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


class _FakeClock:
    """Injectable supervisor clock: hedging deadlines and stall probes
    advance only when the test says so."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _assert_parity(m, v, results, gids, prompts, max_new):
    assert len(results) == len(gids)
    for gid, p in zip(gids, prompts):
        res = results[gid]
        assert res.status == "completed", f"gid={gid}: {res.status}"
        np.testing.assert_array_equal(
            np.asarray(res.tokens), ref_tokens(m, v, p, max_new),
            err_msg=f"gid={gid}",
        )


def _assert_engine_pins(engine):
    """Per-replica compile pins: never more programs than the design
    ceilings, whatever the supervisor did around the engine."""
    assert engine.decode_compile_count <= engine.num_decode_blocks
    assert engine.prefill_compile_count <= engine.num_prefill_buckets


# -- routing ---------------------------------------------------------------


def test_routing_parity_and_load_split(lm):
    """Baseline: two replicas behind the facade serve a staggered
    arrival schedule bit-identically to ``generate()``, both replicas
    take work, and each engine's compile pins hold under the guard.

    Six requests for the set's four slots: two wait, and a slot of each
    replica is leased a second time. Three requests never fill a replica,
    so neither the wait behind a full replica nor the second lease would
    be routed; two replicas are the fewest that split a load. Stays."""
    m, v, ids = lm
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=4, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7, 6, 8)]
    gids = []
    with serve_compile_guard(rs.engine(0), min_decode=1, min_prefill=1), \
            serve_compile_guard(rs.engine(1), min_decode=1,
                                min_prefill=1):
        it = iter(prompts)
        pending = True
        while pending or rs.busy:
            for _ in range(2):
                p = next(it, None)
                if p is None:
                    pending = False
                    break
                gids.append(rs.submit(p, 6))
            rs.step()
        results = rs.run()
    _assert_parity(m, v, results, gids, prompts, 6)
    per = rs.metrics_dict()["per_replica"]
    assert per["replica0"]["submitted"] > 0
    assert per["replica1"]["submitted"] > 0
    assert rs.replica_failovers_total == 0


def test_submit_validation_and_global_ids(lm):
    m, v, _ids = lm
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=2, retry_backoff_s=0.0)
    with pytest.raises(FriendlyError, match="non-empty"):
        rs.submit(np.zeros(0, np.int32), 4)
    g0 = rs.submit([1, 2, 3], 4)
    g1 = rs.submit([1, 2, 3], 4)
    assert (g0, g1) == (0, 1)  # global ids, replica-independent
    with pytest.raises(FriendlyError, match="replicas must be"):
        ReplicaSet(m, v, replicas=0)
    with pytest.raises(FriendlyError, match="hedge_ms"):
        ReplicaSet(m, v, replicas=2, hedge_ms=-1.0)
    with pytest.raises(FriendlyError, match="managed by ReplicaSet"):
        ReplicaSet(m, v, replicas=2, replica=0)


# -- failover --------------------------------------------------------------


def _kill_drill(m, v, ids, mesh=None):
    """The acceptance drill: kill replica 0 mid-decode-block; run()
    must still complete EVERY request bit-identically to a no-failure
    run, with per-replica compile pins intact. Mixed budgets make some
    requests complete between the snapshot and the kill, so the
    reconciliation's exactly-once cancel path runs too.

    Six requests, two of them short: with three, which replica takes the
    one short request decides whether any stream ends between replica
    0's snapshot (tick 2) and its kill (tick 3), and the cancel path can
    go unrun; one replica has nowhere to fail over to. So the size stays
    for both the one-device and the 2x2-mesh case."""
    inj = FaultInjector([Fault("serve.decode", "kill", tick=3,
                               replica=0)])
    rs = ReplicaSet(m, v, replicas=2, slots=4, cache_len=32,
                    max_queue=8, decode_block=2, mesh=mesh,
                    snapshot_every_ticks=2, faults=inj,
                    retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7, 6, 8)]
    budgets = [12, 3, 12, 3, 12, 12]
    gids = [rs.submit(p, b) for p, b in zip(prompts, budgets)]
    results = rs.run()
    assert rs.replica_failovers_total == 1
    assert len(results) == len(gids)
    for gid, p, b in zip(gids, prompts, budgets):
        assert results[gid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[gid].tokens), ref_tokens(m, v, p, b),
            err_msg=f"mesh={mesh} gid={gid}",
        )
    for i in range(2):
        _assert_engine_pins(rs.engine(i))
    assert rs.replica_state(0) in ("healthy", "degraded")
    md = rs.metrics_dict()
    assert md["replica_failovers_total"] == 1
    assert md["per_replica"]["replica0"]["failovers"] == 1


def test_kill_failover_bit_identical_single_device(lm):
    m, v, ids = lm
    _kill_drill(m, v, ids, mesh=None)


def test_kill_failover_bit_identical_2x2_mesh(lm):
    m, v, ids = lm
    _kill_drill(m, v, ids, mesh={"data": 2, "model": 2})


def test_health_probe_fault_fails_over(lm):
    """An injected failure at the ``serve.health`` site IS a failed
    probe: the replica quarantines and rebuilds; requests complete
    bit-identically on the survivors + the restored replica."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.health", "transient",
                               replica=0)])
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2,
                    snapshot_every_ticks=1, faults=inj,
                    retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [rs.submit(p, 8) for p in prompts]
    results = rs.run()
    assert rs.replica_failovers_total == 1
    _assert_parity(m, v, results, gids, prompts, 8)


def test_max_failovers_caps_the_rebuild_loop(lm):
    """A deterministic crash that fires on every rebuilt engine must
    not spin forever: past ``max_failovers`` the supervisor raises the
    typed error instead of burning another restore."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.decode", "kill", times=10)])
    rs = ReplicaSet(m, v, replicas=1, slots=2, cache_len=32,
                    max_queue=4, decode_block=2, max_failovers=2,
                    snapshot_every_ticks=1, faults=inj,
                    retry_backoff_s=0.0)
    rs.submit(np.asarray(ids[0, :5]), 8)
    with pytest.raises(FriendlyError, match="max_failovers"):
        rs.run()
    assert rs.replica_failovers_total == 3  # 2 absorbed + the fatal one


# -- hedging ---------------------------------------------------------------


def test_hedging_first_committed_wins_exactly_once(lm):
    """Past the hedge deadline (injected clock) the request duplicates
    onto the second replica; the first copy to commit wins, the loser
    cancels, its emitted tokens count as waste — and the caller sees
    EXACTLY one result, bit-identical to ``generate()``."""
    m, v, ids = lm
    clk = _FakeClock()
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, hedge_ms=50.0,
                    clock=clk, snapshot_every_ticks=None,
                    retry_backoff_s=0.0)
    p = np.asarray(ids[0, :6])
    gid = rs.submit(p, 12)
    rs.step()               # below the deadline: no hedge yet
    assert rs.hedges_total == 0
    clk.t = 0.2             # 200ms queue age > 50ms hedge deadline
    results = rs.run()
    assert rs.hedges_total == 1
    assert rs.hedge_wasted_tokens_total > 0
    assert list(results) == [gid]
    np.testing.assert_array_equal(
        np.asarray(results[gid].tokens), ref_tokens(m, v, p, 12))
    md = rs.metrics_dict()
    assert md["hedges_total"] == 1
    assert md["hedge_wasted_tokens_total"] == rs.hedge_wasted_tokens_total
    # the losing copy was cancelled on exactly one engine
    cancelled = sum(
        md["per_replica"][f"replica{i}"]["cancelled_total"]
        for i in range(2)
    )
    assert cancelled == 1


def test_hedge_needs_a_second_live_replica(lm):
    """With nowhere to duplicate to, the hedge deadline passes without
    effect — no duplicate, no waste, one result."""
    m, v, ids = lm
    clk = _FakeClock()
    rs = ReplicaSet(m, v, replicas=1, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, hedge_ms=1.0,
                    clock=clk, retry_backoff_s=0.0)
    gid = rs.submit(np.asarray(ids[0, :5]), 6)
    clk.t = 10.0
    results = rs.run()
    assert rs.hedges_total == 0
    assert results[gid].status == "completed"


# -- drain -----------------------------------------------------------------


def test_drain_under_load_migrates_bit_identically(lm):
    """Zero-loss drain mid-run: replica 0's pending requests migrate
    to replica 1 with their emitted prefixes, every stream finishes
    bit-identically, and the drained replica takes no new work."""
    m, v, ids = lm
    rs = ReplicaSet(m, v, replicas=2, slots=4, cache_len=32,
                    max_queue=8, decode_block=2,
                    snapshot_every_ticks=2, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7)]
    gids = [rs.submit(p, 12) for p in prompts]
    rs.step()
    rs.step()
    before = rs.engine(1).metrics.submitted
    rs.drain(0)
    assert rs.replica_state(0) in ("draining", "drained")
    assert rs.engine(1).metrics.submitted > before  # migration landed
    g_late = rs.submit(prompts[0], 12)   # routes around the drain
    results = rs.run()
    assert rs.replica_state(0) == "drained"
    assert rs.drains_total == 1
    _assert_parity(m, v, results, gids + [g_late],
                   prompts + [prompts[0]], 12)
    with pytest.raises(FriendlyError, match="already"):
        rs.drain(0)


def test_drain_last_replica_finishes_in_place(lm):
    """With no survivor to migrate to, the draining replica serves its
    own backlog to completion, then retires; further submits reject."""
    m, v, ids = lm
    rs = ReplicaSet(m, v, replicas=1, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, retry_backoff_s=0.0)
    p = np.asarray(ids[0, :5])
    gid = rs.submit(p, 8)
    rs.drain(0)
    results = rs.run()
    np.testing.assert_array_equal(
        np.asarray(results[gid].tokens), ref_tokens(m, v, p, 8))
    rs.step()  # idle draining replica retires on the next tick
    assert rs.replica_state(0) == "drained"
    assert rs.drains_total == 1
    with pytest.raises(FriendlyError, match="no live replica"):
        rs.submit(p, 4)


# -- run() bound -----------------------------------------------------------


def test_run_bound_stalls_open_requests(lm):
    """Hitting max_ticks retires every open request as ``"stalled"``
    with whatever its best copy had emitted, attached to the typed
    error — never a silent drop."""
    m, v, ids = lm
    rs = ReplicaSet(m, v, replicas=1, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, retry_backoff_s=0.0)
    p = np.asarray(ids[0, :5])
    gid = rs.submit(p, 16)
    with pytest.raises(FriendlyError, match="max_ticks") as ei:
        rs.run(max_ticks=1)
    res = ei.value.results[gid]
    assert res.status == "stalled"
    assert res.generated > 0
    np.testing.assert_array_equal(
        np.asarray(res.tokens)[:len(p)], p)
    assert not rs.busy


# -- satellite: EngineKilled parks device resources ------------------------


def test_engine_killed_parks_resources_deterministically(lm):
    """The kill regression (satellite a): an EngineKilled escaping
    run() leaves NO leased slot behind — on a paged pool every slot
    mapping is released (refcount totals drop to the prefix cache's
    own references) — the dead engine refuses further steps, and an
    in-process restore of its last checkpoint completes every stream
    bit-identically."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.decode", "kill", tick=2)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=2, paged=True, prefix_cache=True,
                         snapshot_every_ticks=1, faults=inj,
                         retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (9, 4, 11)]
    rids = [engine.submit(p, 6) for p in prompts]
    with pytest.raises(EngineKilled):
        engine.run()
    assert engine.pool.leased_count == 0
    pg = engine.pool.snapshot()
    refs = sum(pg["npages"]) + sum(
        len(e["pages"]) for e in pg["prefix_entries"])
    assert sum(pg["refcounts"]) == refs
    assert sum(pg["npages"]) == 0  # no slot holds a mapping
    with pytest.raises(FriendlyError, match="killed"):
        engine.step()
    assert engine.cancel(rids[0]) is None
    assert engine.steal_all() == []
    snap = engine.last_snapshot
    assert snap is not None
    rebuilt = ServeEngine.restore(snap, m, v, slots=2, max_queue=8,
                                  decode_block=2, paged=True,
                                  prefix_cache=True)
    results = rebuilt.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 6),
            err_msg=f"request={rid}")


# -- satellite: torn checkpoints are not restorable ------------------------


def test_snapshot_fault_keeps_previous_checkpoint(lm):
    """A fault at the ``serve.snapshot`` site models a checkpoint
    failing MID-WRITE: checkpoint() reports the failure and
    ``last_snapshot`` keeps the previous COMPLETE one — which still
    restores bit-identically."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.snapshot", "transient", tick=3)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=2, faults=inj,
                         retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9)]
    rids = [engine.submit(p, 10) for p in prompts]
    engine.step()
    engine.step()
    good = engine.checkpoint()           # tick 2: clean write
    assert good is not None
    assert engine.metrics.snapshots_total == 1
    engine.step()
    torn = engine.checkpoint()           # tick 3: fault mid-write
    assert torn is None
    assert engine.last_snapshot is good  # previous checkpoint survives
    assert engine.metrics.snapshot_failures_total == 1
    rebuilt = ServeEngine.restore(engine.last_snapshot, m, v, slots=2,
                                  max_queue=8, decode_block=2)
    results = rebuilt.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 10),
            err_msg=f"request={rid}")


def test_parse_fault_spec_site_rates():
    """``site:kind=rate`` keys scope a rate to ONE hook site — the
    snapshot-failure drill's spelling."""
    inj = parse_fault_spec("seed=5,serve.snapshot:transient=1.0")
    inj.fire("serve.decode", tick=0)     # other sites: silent
    inj.fire("serve.health", tick=0)
    with pytest.raises(TransientFault):
        inj.fire("serve.snapshot", tick=0)
    with pytest.raises(FriendlyError, match="site"):
        parse_fault_spec("seed=5,nope.site:transient=0.5")
    with pytest.raises(FriendlyError, match="seed"):
        parse_fault_spec("serve.snapshot:transient=0.5")


# -- satellite: paged + prefix on a 2x2 mesh, faulted round-trip -----------


def test_paged_prefix_mesh_snapshot_roundtrip_under_faults(lm):
    """Snapshot/restore of a paged + prefix-cache engine on a 2x2 mesh
    while a fault schedule is ACTIVE: the mid-run checkpoint is
    auditable (refcount totals == mapped references), the restored
    engine finishes every stream bit-identically, and the audit holds
    again after the restored run."""
    m, v, ids = lm
    inj = FaultInjector([
        Fault("serve.prefill", "transient", times=2),
        Fault("serve.decode", "transient", tick=2),
    ])
    kwargs = dict(slots=2, cache_len=32, max_queue=8, decode_block=2,
                  paged=True, prefix_cache=True,
                  mesh={"data": 2, "model": 2}, retry_backoff_s=0.0)
    engine = ServeEngine(m, v, faults=inj, **kwargs)
    prompts = [np.asarray(ids[0, :n]) for n in (9, 9, 11)]
    rids = [engine.submit(p, 6) for p in prompts]
    engine.step()
    engine.step()
    snap = engine.snapshot()
    pg = snap["paging"]
    refs = sum(pg["npages"]) + sum(
        len(e["pages"]) for e in pg["prefix_entries"])
    assert sum(pg["refcounts"]) == refs
    json.dumps(snap)  # the checkpoint must stay JSON-able
    rebuilt = ServeEngine.restore(snap, m, v, **kwargs)
    results = rebuilt.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 6),
            err_msg=f"request={rid}")
    pg2 = rebuilt.pool.snapshot()
    refs2 = sum(pg2["npages"]) + sum(
        len(e["pages"]) for e in pg2["prefix_entries"])
    assert sum(pg2["refcounts"]) == refs2
    _assert_engine_pins(rebuilt)


# -- metrics surface -------------------------------------------------------


def test_metrics_dict_schema(lm):
    """The keys tools/check_metrics_schema.py gates on the --replicas
    demo line, plus the per-replica nesting."""
    m, v, ids = lm
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, retry_backoff_s=0.0)
    gid = rs.submit(np.asarray(ids[0, :5]), 4)
    rs.run()
    md = rs.metrics_dict()
    for key in ("replicas", "hedge_ms", "supervisor_ticks", "submitted",
                "completed", "failed", "expired", "stalled",
                "tokens_generated", "tokens_per_sec", "wall_s",
                "replica_failovers_total", "hedges_total",
                "hedge_wasted_tokens_total", "drains_total",
                "per_replica"):
        assert key in md, key
    assert md["replicas"] == 2
    assert md["completed"] == 1
    assert set(md["per_replica"]) == {"replica0", "replica1"}
    for sub in md["per_replica"].values():
        for key in ("state", "failovers", "snapshots_total",
                    "cancelled_total", "degraded_mode",
                    "decode_compile_count", "prefill_compile_count"):
            assert key in sub, key
    json.dumps(md, default=str)  # the CLI prints it as one JSON line
    # per-replica registry namespacing: replica0's serve counters carry
    # the prefix, so N expositions concatenate without collisions
    names = rs.engine(0).metrics.registry.names()
    assert any(n.startswith("replica0.serve.") for n in names)
    assert gid == 0
