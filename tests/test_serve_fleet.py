"""Disaggregated prefill/decode fleet (ISSUE 13 tentpole).

The contract under test (docs/SERVING.md "Disaggregated fleet"): a
``DisaggFleet`` of dedicated prefill and decode replicas behind the
``submit()/step()/run()`` facade serves every stream BIT-IDENTICALLY
to a homogeneous ``ReplicaSet`` at equal device count (and to
``generate()``, the shared oracle) — across ragged prompts, mid-run
joins, single device AND a 2x2 mesh, with per-engine compile pins
intact and decode replicas compiling ZERO prefill programs on the
hand-off path. The cross-replica hand-off plane survives injected
``serve.handoff`` faults (retry, then full-prefill fallback), replica
kills, and drains; the fleet-wide prefix index turns a repeat prompt
into a decode-only request on ANY replica with refcount conservation
(``refcount_audit``: refcount total == mapped references on every
pool, fleet index refs == open indexed requests); and the autoscaler
grows a role under bursty load and drains back to baseline with zero
lost or duplicated requests.

Satellites ride here too: ``ServeMetrics`` percentile helpers return
0.0 (never NaN/None) on empty histograms; an unknown fault site names
ALL six hook points; hedged duplicate prefills of the same prompt
never double-insert or refcount-leak the shared prefix entry.
"""

from __future__ import annotations

import numpy as np
import pytest

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import (
    SITES,
    Fault,
    FaultInjector,
    parse_fault_spec,
)
from mmlspark_tpu.serve import (
    AutoscalePolicy,
    DisaggFleet,
    ReplicaSet,
    ServeEngine,
    parse_autoscale_spec,
)
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


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
    assert engine.decode_compile_count <= engine.num_decode_blocks
    assert engine.prefill_compile_count <= engine.num_prefill_buckets


def _assert_pool_audits(fleet):
    """The allocator conservation law on EVERY live paged pool, plus
    the fleet index's own refs == open-indexed audit."""
    for rep in fleet._reps:
        pool = rep.engine.pool
        if hasattr(pool, "refcount_audit"):
            total, mapped = pool.refcount_audit()
            assert total == mapped, (
                f"replica {rep.idx} ({rep.role}): refcount_total="
                f"{total} != mapped_references={mapped}"
            )
    stats = fleet.prefix_index_stats()
    assert stats["refs_total"] == stats["open_indexed"], stats


# -- bit-identity vs the homogeneous ReplicaSet ----------------------------


def _parity_drill(m, v, ids, mesh=None, **extra):
    """The acceptance drill: a 1-prefill + 1-decode fleet vs a
    2-replica homogeneous ReplicaSet at EQUAL device count, ragged
    prompts with mid-run joins, every stream compared token-for-token
    (and against the ``generate()`` oracle). Decode replicas must ride
    the hand-off path — zero prefill compiles."""
    kw = dict(slots=2, cache_len=32, max_queue=8, decode_block=4,
              mesh=mesh, retry_backoff_s=0.0, **extra)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7, 6, 8)]

    rs = ReplicaSet(m, v, replicas=2, **kw)
    rs_gids = [rs.submit(p, 6) for p in prompts[:4]]
    for _ in range(2):
        rs.step()
    rs_gids += [rs.submit(p, 6) for p in prompts[4:]]  # mid-run join
    rs_res = rs.run()

    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        **kw)
    guards = [
        serve_compile_guard(fleet.engine(0), min_prefill=1),
        serve_compile_guard(fleet.engine(1), min_decode=1),
    ]
    with guards[0], guards[1]:
        gids = [fleet.submit(p, 6) for p in prompts[:4]]
        for _ in range(2):
            fleet.step()
        gids += [fleet.submit(p, 6) for p in prompts[4:]]
        results = fleet.run()

    _assert_parity(m, v, results, gids, prompts, 6)
    for rg, fg, p in zip(rs_gids, gids, prompts):
        np.testing.assert_array_equal(
            np.asarray(rs_res[rg].tokens),
            np.asarray(results[fg].tokens),
            err_msg=f"fleet diverged from homogeneous set: {p}",
        )
    # true disaggregation: the decode replica never compiled a prefill
    # program (every request arrived as a KV hand-off) and the prefill
    # replica never compiled a decode block
    assert fleet.engine(1).prefill_compile_count == 0
    assert fleet.engine(0).decode_compile_count == 0
    assert fleet.handoffs_total == len(prompts)
    md = fleet.metrics_dict()
    assert md["per_role"]["prefill"]["handoffs_out_total"] == len(prompts)
    assert md["per_role"]["decode"]["handoffs_adopted_total"] == \
        len(prompts)
    for i in range(2):
        _assert_engine_pins(fleet.engine(i))
    _assert_pool_audits(fleet)


def test_disagg_bit_identical_single_device(lm):
    m, v, ids = lm
    _parity_drill(m, v, ids, mesh=None)


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_disagg_bit_identical_2x2_mesh(lm):
    m, v, ids = lm
    _parity_drill(m, v, ids, mesh={"data": 2, "model": 2})


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_disagg_bit_identical_paged_prefix_mesh(lm):
    """The full stack: paged pools + prefix caches on a 2x2 mesh, the
    hand-off payload landing through ``write_prefill``'s paged path."""
    m, v, ids = lm
    _parity_drill(m, v, ids, mesh={"data": 2, "model": 2},
                  paged=True, prefix_cache=True)


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_disagg_bit_identical_int8_kv(lm):
    """int8 KV pools re-quantize the handed-off bf16 linear cache
    deterministically — same bits as the homogeneous int8 run."""
    m, v, ids = lm
    kw = dict(slots=2, cache_len=32, max_queue=8, decode_block=4,
              kv_dtype="int8", retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    rs = ReplicaSet(m, v, replicas=2, **kw)
    rs_gids = [rs.submit(p, 6) for p in prompts]
    rs_res = rs.run()
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        **kw)
    gids = [fleet.submit(p, 6) for p in prompts]
    results = fleet.run()
    for rg, fg in zip(rs_gids, gids):
        np.testing.assert_array_equal(
            np.asarray(rs_res[rg].tokens),
            np.asarray(results[fg].tokens),
        )


# -- fleet-wide prefix index -----------------------------------------------


def test_fleet_prefix_index_cross_replica_hit(lm):
    """One replica's completed prefill is EVERY replica's cache hit:
    a repeat prompt skips prefill fleet-wide (the prefill replica sees
    no new work), lands decode-only on any decode replica, and every
    pool's refcount audit stays conserved."""
    m, v, ids = lm
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=2,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, paged=True, prefix_cache=True,
                        retry_backoff_s=0.0)
    p = np.asarray(ids[0, :6])
    g0 = fleet.submit(p, 8)
    r0 = fleet.run()
    assert fleet.fleet_prefix_hits_total == 0
    prefills_before = fleet.engine(0).metrics.submitted

    g1 = fleet.submit(p, 8)
    g2 = fleet.submit(p, 8)
    # mid-flight: both hits hold a reference on the index entry
    stats = fleet.prefix_index_stats()
    assert stats["refs_total"] == stats["open_indexed"] == 2
    res = fleet.run()
    assert fleet.fleet_prefix_hits_total == 2
    assert fleet.fleet_prefill_tokens_saved_total == 2 * len(p)
    # the prefill replica never saw the repeats
    assert fleet.engine(0).metrics.submitted == prefills_before
    oracle = ref_tokens(m, v, p, 8)
    for gid, results in ((g0, r0), (g1, res), (g2, res)):
        np.testing.assert_array_equal(
            np.asarray(results[gid].tokens), oracle, err_msg=f"{gid}")
    _assert_pool_audits(fleet)
    md = fleet.metrics_dict()
    assert md["fleet_prefix_hits_total"] == 2
    assert md["fleet_prefix_entries"] >= 1


def test_fleet_index_lru_eviction_pins_referenced_entries(lm):
    m, v, ids = lm
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        prefix_index_capacity=2, slots=2, cache_len=32,
                        max_queue=8, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (4, 5, 6, 7)]
    # wave 1 fills the index to capacity and commits (refs drop to 0)
    for p in prompts[:2]:
        fleet.submit(p, 4)
    fleet.run()
    assert fleet.prefix_index_stats()["entries"] == 2
    # wave 2's inserts evict the now-unreferenced wave-1 entries; a
    # single-burst wave would instead PIN every entry (refs > 0) and
    # the index would deliberately overshoot rather than drop a
    # referenced payload
    for p in prompts[2:]:
        fleet.submit(p, 4)
    fleet.run()
    stats = fleet.prefix_index_stats()
    assert stats["entries"] <= 2
    assert stats["evictions_total"] >= 2
    assert stats["refs_total"] == 0


# -- hand-off fault site ---------------------------------------------------


def test_handoff_transient_fault_retries_bit_identically(lm):
    """A transient ``serve.handoff`` fault is absorbed by the adopt
    retry loop — the payload lands on a later attempt, no fallback."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.handoff", "transient", times=2)])
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, faults=inj,
                        retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [fleet.submit(p, 6) for p in prompts]
    results = fleet.run()
    _assert_parity(m, v, results, gids, prompts, 6)
    md = fleet.metrics_dict()
    assert md["handoff_fallbacks_total"] == 0


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_handoff_lost_payload_falls_back_to_full_prefill(lm):
    """A hand-off that cannot land (persistent fault) falls back to a
    full local prefill on the decode replica — the stream still
    completes bit-identically, and the fallback is counted."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.handoff", "transient",
                               times=1000)])
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, faults=inj,
                        retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9)]
    gids = [fleet.submit(p, 6) for p in prompts]
    results = fleet.run()
    _assert_parity(m, v, results, gids, prompts, 6)
    md = fleet.metrics_dict()
    assert md["handoff_fallbacks_total"] == len(prompts)
    # the fallback ran real prefills on the decode replica
    assert fleet.engine(1).prefill_compile_count > 0


# -- failover / drain ------------------------------------------------------


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_decode_replica_kill_failover_bit_identical(lm):
    """Killing a decode replica mid-decode-block restores it from its
    periodic snapshot; handed-off streams resume through the
    emitted-prefix / local-re-prefill path bit-identically."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.decode", "kill", tick=3,
                               replica=1)])
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=2,
                        slots=4, cache_len=32, max_queue=8,
                        decode_block=2, snapshot_every_ticks=2,
                        faults=inj, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7, 6, 8)]
    budgets = [12, 3, 12, 3, 12, 12]
    gids = [fleet.submit(p, b) for p, b in zip(prompts, budgets)]
    results = fleet.run()
    assert fleet.replica_failovers_total == 1
    assert len(results) == len(gids)
    for gid, p, b in zip(gids, prompts, budgets):
        assert results[gid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[gid].tokens), ref_tokens(m, v, p, b),
            err_msg=f"gid={gid}",
        )
    assert fleet.replica_state(1) in ("healthy", "degraded")
    assert fleet.replica_role(1) == "decode"  # role survives failover
    _assert_pool_audits(fleet)


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_prefill_replica_kill_failover_bit_identical(lm):
    """Killing the PREFILL replica loses its undelivered payloads; the
    fleet re-routes every affected request from its ledger through the
    restored engine and the streams stay bit-identical."""
    m, v, ids = lm
    # tick 0: a prefill-role engine retires each request at admission
    # (the slot frees on hand-off), so its whole backlog prefills in
    # the first tick — later ticks never dispatch a prefill
    inj = FaultInjector([Fault("serve.prefill", "kill", tick=0,
                               replica=0)])
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=2, snapshot_every_ticks=2,
                        faults=inj, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [fleet.submit(p, 8) for p in prompts]
    results = fleet.run()
    assert fleet.replica_failovers_total == 1
    _assert_parity(m, v, results, gids, prompts, 8)
    assert fleet.replica_role(0) == "prefill"


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_drain_decode_replica_migrates_bit_identically(lm):
    """Zero-loss drain of a decode replica mid-run: pending streams
    migrate to the surviving decode replica with their emitted
    prefixes; the drained replica leaves the prefix-index locality
    sets."""
    m, v, ids = lm
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=2,
                        slots=4, cache_len=32, max_queue=8,
                        decode_block=2, snapshot_every_ticks=2,
                        retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7)]
    gids = [fleet.submit(p, 12) for p in prompts]
    for _ in range(3):
        fleet.step()
    fleet.drain(1)
    assert fleet.replica_state(1) in ("draining", "drained")
    g_late = fleet.submit(prompts[0], 12)
    results = fleet.run()
    assert fleet.replica_state(1) == "drained"
    assert fleet.drains_total == 1
    _assert_parity(m, v, results, gids + [g_late],
                   prompts + [prompts[0]], 12)
    for entry in fleet._index.values():
        assert 1 not in entry.home
    with pytest.raises(FriendlyError, match="already"):
        fleet.drain(1)
    _assert_pool_audits(fleet)


# -- autoscaling -----------------------------------------------------------


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_autoscaler_scales_up_under_burst_and_drains_back(lm):
    """Bursty arrivals push per-replica load over ``queue_high``: the
    fleet spawns replicas from the parked budget; once traffic stops,
    idle replicas drain back to baseline. Every request completes
    exactly once — nothing lost, nothing duplicated."""
    m, v, ids = lm
    fleet = DisaggFleet(
        m, v, prefill_replicas=1, decode_replicas=1,
        autoscale=AutoscalePolicy(
            max_prefill=2, max_decode=3, queue_high=1.0,
            slo_burn_ticks=0, idle_ticks=2, cooldown_ticks=0,
        ),
        slots=1, cache_len=32, max_queue=16, decode_block=4,
        retry_backoff_s=0.0,
    )
    assert fleet._parked == {"prefill": 1, "decode": 2}
    prompts = [np.asarray(ids[0, 2:2 + 4 + (i % 3)]) for i in range(8)]
    gids = [fleet.submit(p, 8) for p in prompts]
    results = fleet.run()
    assert fleet.scale_ups_total >= 1
    assert len(results) == len(set(gids)) == len(gids)
    for gid, p in zip(gids, prompts):
        assert results[gid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[gid].tokens), ref_tokens(m, v, p, 8))
    # idle fleet shrinks back to the baseline floor
    for _ in range(12):
        fleet.step()
    assert fleet.scale_downs_total >= 1
    assert fleet.prefill_replicas == 1
    assert fleet.decode_replicas == 1
    md = fleet.metrics_dict()
    assert md["parked_prefill"] == 1
    assert md["parked_decode"] == 2
    _assert_pool_audits(fleet)


def test_autoscale_spec_parsing_and_validation(lm):
    pol = parse_autoscale_spec("max_decode=4,queue_high=1.5,idle_ticks=3")
    assert pol.max_decode == 4
    assert pol.queue_high == 1.5
    assert pol.idle_ticks == 3
    assert pol.min_decode == 1  # defaults survive partial specs
    with pytest.raises(FriendlyError, match="unknown autoscale key"):
        parse_autoscale_spec("bogus=3")
    with pytest.raises(FriendlyError, match="max_decode"):
        AutoscalePolicy(min_decode=3, max_decode=2)
    m, v, _ids = lm
    with pytest.raises(FriendlyError, match="autoscale floor"):
        DisaggFleet(m, v, decode_replicas=1,
                    autoscale=AutoscalePolicy(min_decode=2))


# -- construction / validation ---------------------------------------------


def test_fleet_ctor_validation(lm):
    m, v, _ids = lm
    with pytest.raises(FriendlyError, match="at least one replica"):
        DisaggFleet(m, v, prefill_replicas=0)
    with pytest.raises(FriendlyError, match="managed by DisaggFleet"):
        DisaggFleet(m, v, role="decode")
    with pytest.raises(FriendlyError, match="managed by DisaggFleet"):
        DisaggFleet(m, v, replica=0)
    with pytest.raises(FriendlyError, match="role must be"):
        ServeEngine(m, v, role="hybrid")


# -- fleet snapshot / restore ----------------------------------------------


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_fleet_snapshot_restore_resumes_bit_identically(lm):
    """The fleet checkpoint round-trip: open streams restore onto a
    FRESH fleet with their emitted prefixes and finish bit-identically
    under their original global ids."""
    m, v, ids = lm
    kw = dict(slots=2, cache_len=32, max_queue=8, decode_block=2,
              retry_backoff_s=0.0)
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        **kw)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [fleet.submit(p, 12) for p in prompts]
    for _ in range(4):
        fleet.step()
    snap = fleet.snapshot()
    assert snap["version"] == 1
    restored = DisaggFleet.restore(snap, m, v, **kw)
    results = restored.run()
    _assert_parity(m, v, results, gids, prompts, 12)
    with pytest.raises(FriendlyError, match="snapshot version"):
        DisaggFleet.restore({"version": 99}, m, v, **kw)


# -- satellite: percentile helpers are 0.0 on empty ------------------------


def test_percentile_helpers_zero_on_empty_histograms(lm):
    """Regression: a cold engine (or role with no finished work yet —
    routine in a disagg fleet) reports 0.0 percentiles, never
    NaN/None, so dashboards and route ordering stay arithmetic-safe."""
    m, v, _ids = lm
    eng = ServeEngine(m, v, slots=2, cache_len=32)
    assert eng.metrics.ttft_p99_ms() == 0.0
    assert eng.metrics.per_token_p99_ms() == 0.0
    assert eng.metrics.tick_p99_ms() == 0.0
    fleet = DisaggFleet(m, v)
    assert fleet.ttft_p99_ms() == 0.0
    assert fleet.metrics_dict()["ttft_ms_p99"] == 0.0


# -- satellite: unknown fault site names every hook point ------------------


def test_unknown_fault_site_error_lists_all_sites():
    # seven serve.* sites plus the trainer's four train.* sites
    assert "serve.handoff" in SITES and "train.step" in SITES
    assert "serve.batch" in SITES
    assert len(SITES) == 11
    with pytest.raises(FriendlyError) as ei:
        parse_fault_spec("bogus.site:transient=0.5")
    for site in SITES:
        assert site in str(ei.value)
    with pytest.raises(FriendlyError) as ei:
        Fault("bogus.site", "transient")
    for site in SITES:
        assert site in str(ei.value)


# -- satellite: hedged double-prefill of a shared prefix -------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _hedge_prefix_drill(m, v, ids, mesh=None):
    """Two hedged copies prefill the SAME prompt on different
    replicas; first-committed-wins cancels the loser mid-flight. The
    shared prefix entry must exist at most once per pool and every
    pool's refcounts must stay conserved — a hedge must never
    double-insert or leak."""
    clk = _FakeClock()
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2, hedge_ms=50.0,
                    clock=clk, mesh=mesh, paged=True,
                    prefix_cache=True, snapshot_every_ticks=None,
                    retry_backoff_s=0.0)
    p = np.asarray(ids[0, :6])
    gid = rs.submit(p, 12)
    rs.step()
    clk.t = 0.2  # past the hedge deadline: duplicate onto replica 1
    results = rs.run()
    assert rs.hedges_total == 1
    np.testing.assert_array_equal(
        np.asarray(results[gid].tokens), ref_tokens(m, v, p, 12))
    for i in range(2):
        pool = rs.engine(i).pool
        total, mapped = pool.refcount_audit()
        assert total == mapped, f"replica {i}: {total} != {mapped}"
        # the prompt's prefix entry exists AT MOST once per pool
        assert pool.paging_stats()["prefix_cache_entries"] <= 1
    # resubmitting the same prompt hits a prefix cache, not a re-insert
    g2 = rs.submit(p, 12)
    res2 = rs.run()
    np.testing.assert_array_equal(
        np.asarray(res2[g2].tokens), ref_tokens(m, v, p, 12))
    for i in range(2):
        total, mapped = rs.engine(i).pool.refcount_audit()
        assert total == mapped


def test_hedged_shared_prefix_no_double_insert_single_device(lm):
    m, v, ids = lm
    _hedge_prefix_drill(m, v, ids, mesh=None)


@pytest.mark.slow  # ci.sh's disagg gate runs the full file unfiltered
def test_hedged_shared_prefix_no_double_insert_2x2_mesh(lm):
    m, v, ids = lm
    _hedge_prefix_drill(m, v, ids, mesh={"data": 2, "model": 2})


# -- metrics schema --------------------------------------------------------


def test_fleet_metrics_dict_schema(lm):
    m, v, ids = lm
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        retry_backoff_s=0.0)
    fleet.submit(np.asarray(ids[0, :5]), 4)
    fleet.run()
    md = fleet.metrics_dict()
    for key in ("disagg", "prefill_replicas", "decode_replicas",
                "fleet_ticks", "submitted", "completed", "failed",
                "expired", "stalled", "tokens_generated",
                "tokens_per_sec", "wall_s", "ttft_ms_p99",
                "handoffs_total", "handoff_fallbacks_total",
                "fleet_prefix_hits_total", "fleet_prefix_entries",
                "fleet_prefill_tokens_saved_total",
                "replica_failovers_total", "drains_total",
                "scale_ups_total", "scale_downs_total",
                "parked_prefill", "parked_decode", "per_role",
                "per_replica"):
        assert key in md, key
    for role in ("prefill", "decode"):
        for key in ("replicas", "submitted", "tokens_generated",
                    "queue_depth", "handoffs_out_total",
                    "handoffs_adopted_total",
                    "handoff_fallbacks_total"):
            assert key in md["per_role"][role], (role, key)
    for rep_key, rep in md["per_replica"].items():
        assert rep["role"] in ("prefill", "decode"), rep_key
        for key in ("state", "failovers", "submitted", "completed",
                    "tokens_generated", "handoffs_out_total",
                    "handoffs_adopted_total", "queue_depth",
                    "decode_compile_count", "prefill_compile_count"):
            assert key in rep, (rep_key, key)
    assert md["submitted"] == 1
    assert md["completed"] == 1
