"""Serving resilience (ISSUE 7 tentpole).

The contract under test (docs/SERVING.md "Failure semantics"): the
deterministic fault-injection harness (``core/faults.py``) drives the
engine's hook points, and the engine answers with — capped-backoff
retry that is INVISIBLE to results (transient faults absorbed, token
streams still byte-identical to ``generate()``); per-request QUARANTINE
(a poisoned or undispatachable request retires as ``"failed"``, slot
freed and device live mask dead, everyone else unharmed); graceful
DEGRADATION under RESOURCE_EXHAUSTED (down the existing power-of-two
block ladder + admission caps + preemption-with-resume, recovery probe
re-escalates, compile pins hold because no new program ever compiles);
and ``snapshot()``/``restore()`` crash recovery whose post-restore
tokens are bit-identical (the kill-mid-run crash drill). The seeded
chaos soak closes the loop: random fault schedules through full runs,
single-device and 2x2 mesh, every request reaching a definite terminal
status.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.integrity import SnapshotCorruption
from mmlspark_tpu.core.faults import (
    Fault,
    FaultInjector,
    EngineKilled,
    ResourceExhausted,
    TransientFault,
    is_resource_exhausted,
    is_transient,
    parse_fault_spec,
)
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm

TERMINAL = {"completed", "expired", "failed", "stalled"}


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


# -- injector unit tests (pure host, no engine) ----------------------------


def test_fault_schedule_deterministic():
    inj = FaultInjector([Fault("serve.decode", "transient", times=2)])
    with pytest.raises(TransientFault):
        inj.fire("serve.decode", tick=0)
    with pytest.raises(TransientFault):
        inj.fire("serve.decode", tick=1)
    inj.fire("serve.decode", tick=2)   # entry spent: silent
    inj.fire("serve.prefill", tick=0)  # wrong site: never fires
    assert inj.counts == {"transient": 2}
    assert inj.injected_total == 2


def test_fault_schedule_pinning():
    inj = FaultInjector([Fault("serve.prefill", "oom", tick=3, request=7)])
    inj.fire("serve.prefill", tick=3, request=5)  # wrong request
    inj.fire("serve.prefill", tick=2, request=7)  # wrong tick
    inj.fire("serve.prefill", tick=3)             # no request context
    with pytest.raises(ResourceExhausted, match="RESOURCE_EXHAUSTED"):
        inj.fire("serve.prefill", tick=3, request=7)
    assert inj.injected_total == 1


def test_seeded_rates_replay():
    def run(seed):
        inj = FaultInjector(seed=seed, rates={"transient": 0.3})
        fired = []
        for t in range(60):
            try:
                inj.fire("serve.decode", tick=t)
                fired.append(0)
            except TransientFault:
                fired.append(1)
        return fired

    assert run(7) == run(7)   # same seed, same fault replay
    assert run(7) != run(8)   # different seed, different schedule
    assert 0 < sum(run(7)) < 60


def test_injector_and_fault_validation():
    with pytest.raises(FriendlyError, match="seed"):
        FaultInjector(rates={"transient": 0.5})
    with pytest.raises(FriendlyError, match="rate"):
        FaultInjector(seed=0, rates={"transient": 1.5})
    with pytest.raises(FriendlyError, match="kind"):
        FaultInjector(seed=0, rates={"nope": 0.1})
    with pytest.raises(FriendlyError, match="site"):
        Fault("bad.site", "transient")
    with pytest.raises(FriendlyError, match="kind"):
        Fault("serve.decode", "nope")


def test_parse_fault_spec():
    inj = parse_fault_spec("seed=7, transient=0.05,oom=0.02,stall_s=0.002")
    assert inj.rates == {"transient": 0.05, "oom": 0.02}
    assert inj.stall_s == 0.002
    with pytest.raises(FriendlyError, match="fault spec"):
        parse_fault_spec("transient")
    with pytest.raises(FriendlyError, match="key"):
        parse_fault_spec("bogus=1")
    with pytest.raises(FriendlyError, match="value"):
        parse_fault_spec("transient=lots")


def test_classifiers_cover_injected_and_real_spellings():
    assert is_transient(TransientFault("x"))
    assert not is_transient(ResourceExhausted("x"))
    assert not is_transient(EngineKilled("x"))
    assert is_resource_exhausted(ResourceExhausted("x"))
    # the REAL runtime's status spellings match by class + message
    assert is_resource_exhausted(RuntimeError("RESOURCE_EXHAUSTED: pool"))
    from jax.errors import JaxRuntimeError

    assert is_transient(JaxRuntimeError("UNAVAILABLE: link down"))
    assert is_transient(JaxRuntimeError("DEADLINE_EXCEEDED: slow"))
    assert not is_transient(JaxRuntimeError("INTERNAL: compiler bug"))
    # status text in a non-runtime error type is NOT retryable
    assert not is_transient(RuntimeError("UNAVAILABLE"))


# -- transient retry: invisible to results ---------------------------------


def test_transient_faults_retry_transparently(lm):
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:4], row[:5], row[:6]]
    inj = FaultInjector([
        Fault("serve.decode", "transient", times=2),
        Fault("serve.prefill", "transient", times=1),
        Fault("serve.device_get", "transient", times=1),
    ])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4,
                         faults=inj, retry_backoff_s=0.0)
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
    results = engine.run()
    for rid, p in zip(rids, prompts):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 6)
        )
    assert engine.metrics.retries_total == 4
    assert engine.metrics.faults_injected_total == 4
    assert engine.metrics.failed == 0
    assert engine.metrics.quarantined_total == 0


def test_stall_fault_slows_but_never_fails(lm):
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.decode", "stall", times=2)],
                        stall_s=0.001)
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=2,
                         faults=inj)
    prompt = np.asarray(ids[0, :4])
    rid = engine.submit(prompt, max_new_tokens=6)
    res = engine.run()[rid]
    assert res.status == "completed"
    np.testing.assert_array_equal(
        np.asarray(res.tokens), ref_tokens(m, v, prompt, 6)
    )
    assert inj.counts.get("stall") == 2
    assert engine.metrics.retries_total == 0  # a stall is not an error


# -- quarantine: blast radius is one request -------------------------------


def test_prefill_fault_beyond_retries_quarantines_one_request(lm):
    m, v, ids = lm
    row = np.asarray(ids[0])
    # request id 1's prefill fails EVERY attempt; ids 0/2 are untouched
    inj = FaultInjector([
        Fault("serve.prefill", "transient", request=1, times=10),
    ])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4,
                         faults=inj, retry_limit=2, retry_backoff_s=0.0)
    rids = [engine.submit(row[:n], max_new_tokens=5) for n in (4, 5, 6)]
    results = engine.run()
    assert results[rids[1]].status == "failed"
    assert results[rids[1]].generated == 0
    for rid, n in ((rids[0], 4), (rids[2], 6)):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, row[:n], 5)
        )
    assert engine.metrics.quarantined_total == 1
    assert engine.metrics.failed == 1
    # the quarantined request's slot was freed and re-leased (3 requests
    # flowed through 2 slots); pool accounting is clean afterwards
    assert engine.pool.leased_count == 0 and not engine.busy


def test_prefill_poison_quarantines_before_results(lm):
    m, v, ids = lm
    row = np.asarray(ids[0])
    inj = FaultInjector([Fault("serve.prefill", "poison", request=0)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, faults=inj)
    rid_bad = engine.submit(row[:4], max_new_tokens=5)
    rid_ok = engine.submit(row[:5], max_new_tokens=5)
    results = engine.run()
    assert results[rid_bad].status == "failed"
    assert results[rid_bad].generated == 0  # the poison never landed
    assert results[rid_ok].status == "completed"
    np.testing.assert_array_equal(
        np.asarray(results[rid_ok].tokens), ref_tokens(m, v, row[:5], 5)
    )
    assert engine.metrics.quarantined_total == 1


def test_decode_poison_quarantines_only_that_row(lm):
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:4], row[:5], row[:6]]
    inj = FaultInjector([
        Fault("serve.device_get", "poison", tick=1, times=1),
    ])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=2,
                         faults=inj)
    rids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    results = engine.run()
    statuses = [results[r].status for r in rids]
    assert statuses.count("failed") == 1
    assert engine.metrics.quarantined_total == 1
    for rid, p in zip(rids, prompts):
        res = results[rid]
        if res.status == "failed":
            # the corrupted block never reached the result: every token
            # it DID get is a real pre-fault token
            assert all(0 <= int(t) < 8 for t in res.tokens)
            assert res.generated < 8
        else:
            assert res.status == "completed"
            np.testing.assert_array_equal(
                np.asarray(res.tokens), ref_tokens(m, v, p, 8)
            )
    # the quarantined slot is re-leasable: fresh traffic completes
    rid2 = engine.submit(row[:4], max_new_tokens=4)
    res2 = engine.run()[rid2]
    assert res2.status == "completed"
    np.testing.assert_array_equal(
        np.asarray(res2.tokens), ref_tokens(m, v, row[:4], 4)
    )


# -- graceful degradation under memory pressure ----------------------------


def test_oom_steps_down_ladder_and_recovers(lm):
    m, v, ids = lm
    row = np.asarray(ids[0])
    inj = FaultInjector([Fault("serve.decode", "oom", times=2)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=8,
                         faults=inj, retry_limit=3, retry_backoff_s=0.0,
                         degrade_recover_ticks=2)
    rids = [engine.submit(row[:4], max_new_tokens=20),
            engine.submit(row[:5], max_new_tokens=20)]
    with serve_compile_guard(engine, min_decode=1):
        results = engine.run()
    for rid, n in zip(rids, (4, 5)):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, row[:n], 20)
        )
    # two OOMs walked the cap 8 -> 4 -> 2: the degraded dispatch ran a
    # SMALLER ladder size (already compiled — that is the whole point),
    # and the recovery probe re-escalated to full service by the end
    assert "2" in engine.metrics.decode_blocks
    assert inj.counts.get("oom") == 2
    assert not engine.degraded
    assert engine.metrics.to_dict()["degraded_mode"] == 0
    assert engine.metrics.faults_by_kind.get("oom") == 2


def test_oom_at_ladder_floor_preempts_and_resumes(lm):
    m, v, ids = lm
    row = np.asarray(ids[0])
    inj = FaultInjector([Fault("serve.decode", "oom", times=2)])
    # decode_block=1: the ladder has nowhere to step down, so pressure
    # must preempt the youngest active request instead
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=1,
                         faults=inj, retry_limit=3, retry_backoff_s=0.0,
                         degrade_recover_ticks=2)
    rid_a = engine.submit(row[:4], max_new_tokens=6)
    rid_b = engine.submit(row[:5], max_new_tokens=6)
    results = engine.run()
    assert engine.metrics.preemptions_total >= 1
    # the preempted request RESUMED (prompt + emitted prefix re-prefill)
    # and still matches an uninterrupted generate() byte for byte
    for rid, n in ((rid_a, 4), (rid_b, 5)):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, row[:n], 6)
        )
    assert not engine.degraded  # admission cap re-escalated


# -- crash drill: kill mid-run, restore, bit-identical ---------------------


def test_crash_drill_restore_is_bit_identical(lm):
    """Four requests over two slots, killed at tick 2. With two requests
    nothing waits in the queue when the engine dies, and the snapshot's
    ``queued`` half (re-admission after ``restore``) is never read; with
    half the budget every stream ends before the kill. So the size stays."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:4], row[:5], row[:6], row[:3]]
    inj = FaultInjector([Fault("serve.decode", "kill", tick=2)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=2,
                         faults=inj)
    rids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    results = {}
    snap = engine.snapshot()
    with pytest.raises(EngineKilled):
        while engine.busy:
            snap = engine.snapshot()  # checkpoint BEFORE each tick
            for res in engine.step():
                results[res.id] = res
    json.dumps(snap)  # the checkpoint is a plain JSON-able dict
    assert snap["active"] or snap["queued"]  # it died mid-flight

    rebuilt = ServeEngine.restore(snap, m, v, slots=2, decode_block=2)
    assert rebuilt.tick == snap["tick"]
    results.update(rebuilt.run())
    assert set(results) == set(rids)
    for rid, p in zip(rids, prompts):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 8),
            err_msg=f"request {rid} diverged across the crash",
        )
    # new requests on the restored engine get FRESH ids
    assert rebuilt.submit(row[:4], max_new_tokens=2) == max(rids) + 1


def test_restore_guards(lm):
    m, v, _ = lm
    engine = ServeEngine(m, v, slots=2, cache_len=32)
    snap = engine.snapshot()
    # a tampered-but-stamped snapshot trips the checksum guard before
    # the version/model guards ever run
    with pytest.raises(SnapshotCorruption, match="checksum"):
        ServeEngine.restore({**snap, "version": 99}, m, v)
    unstamped = {k: val for k, val in snap.items() if k != "checksum"}
    with pytest.raises(FriendlyError, match="version"):
        ServeEngine.restore({**unstamped, "version": 99}, m, v)
    with pytest.raises(FriendlyError, match="model"):
        ServeEngine.restore({**unstamped, "model": "other_lm"}, m, v)
    # idle snapshot restores to an idle engine
    rebuilt = ServeEngine.restore(snap, m, v, slots=2)
    assert not rebuilt.busy and rebuilt.tick == engine.tick


# -- seeded chaos soak -----------------------------------------------------


def _chaos_soak(m, v, ids, seed, mesh=None):
    """Eight requests under four kinds of fault drawn at every hook. At
    half the requests a seed fires its hooks half as often, and at these
    rates (0.02 to 0.08 a firing) some seed then draws no ``oom`` or no
    ``poison`` at all: the ladder's step down and the quarantine would go
    unwalked under fire. So the size stays; what it costs is one engine's
    programs and a reference program a prompt length (``ref_tokens``)."""
    row = np.asarray(ids[0])
    rng = np.random.default_rng(seed)
    lengths = rng.integers(2, 9, size=8)
    budgets = rng.integers(3, 11, size=8)
    prompts = [row[:int(n)] for n in lengths]
    inj = FaultInjector(
        seed=seed,
        rates={"transient": 0.08, "oom": 0.04, "stall": 0.02,
               "poison": 0.04},
        stall_s=0.0005,
    )
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=16,
                         decode_block=4, mesh=mesh, faults=inj,
                         retry_limit=2, retry_backoff_s=0.0,
                         degrade_recover_ticks=3)
    results, rids = {}, []
    # request-scoped faults must NEVER escape run(): the whole soak runs
    # under the compile-count pins (degradation only moves DOWN the
    # existing ladder, so no new programs may appear)
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            rids.append(engine.submit(p, max_new_tokens=int(n)))
            if i % 2:
                results.update({r.id: r for r in engine.step()})
        results.update(engine.run())

    assert set(results) == set(rids)
    n_completed = 0
    for rid, p, n in zip(rids, prompts, budgets):
        res = results[rid]
        assert res.status in TERMINAL, (rid, res.status)
        if res.status == "completed":
            n_completed += 1
            # unfaulted (and resumed) requests stay token-identical
            np.testing.assert_array_equal(
                np.asarray(res.tokens), ref_tokens(m, v, p, int(n)),
                err_msg=f"seed={seed} mesh={mesh} request={rid}",
            )
    assert n_completed >= 1  # the engine kept serving under fire
    assert engine.metrics.faults_injected_total == inj.injected_total
    assert engine.pool.leased_count == 0 and not engine.busy
    # consistency of the terminal accounting
    md = engine.metrics.to_dict()
    assert (md["completed"] + md["expired"] + md["failed"]
            + md["stalled"]) == len(rids)
    return engine


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_soak_single_device(lm, seed):
    m, v, ids = lm
    _chaos_soak(m, v, ids, seed)


@pytest.mark.parametrize("seed", [3, pytest.param(4, marks=pytest.mark.slow)])
def test_chaos_soak_sharded(lm, seed):
    m, v, ids = lm
    _chaos_soak(m, v, ids, seed, mesh={"data": 2, "model": 2})


# -- zero-overhead contract -------------------------------------------------


def test_disabled_injection_compiles_same_program_set(lm):
    """With ``faults=None`` the hot path must compile exactly the same
    program set as the pre-resilience engine: one decode program per
    ladder size actually run, one prefill program per bucket hit —
    nothing extra from the hook points."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4)
    assert engine._faults is None  # default: injection disabled
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        rids = [engine.submit(row[:n], max_new_tokens=6)
                for n in (4, 6)]
        results = engine.run()
    for rid, n in zip(rids, (4, 6)):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, row[:n], 6)
        )
    assert engine.metrics.retries_total == 0
    assert engine.metrics.faults_injected_total == 0
    assert engine.metrics.to_dict()["degraded_mode"] == 0
