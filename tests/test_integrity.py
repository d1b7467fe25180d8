"""End-to-end integrity plane (ISSUE 18 tentpole).

The contract under test (docs/OBSERVABILITY.md "Integrity",
docs/TRAINING.md "Integrity audits"): a seeded ``corrupt`` fault —
one deterministic bit-flip — injected at each wired site is DETECTED
within one audit interval, with zero false positives on clean runs:

* ``train.step`` — the in-graph param/opt-state checksum folded into
  the compiled step catches the divergent replica at the next audit
  boundary; the replica is quarantined (re-replicated from a majority
  device) and the deterministic replay adjudicates the verdict.
* ``train.checkpoint`` — the manifest's payload sha256 rejects a
  bit-flipped payload BEFORE orbax reads it (typed error naming both
  hashes); the previous committed checkpoint restores bit-identically
  (drilled in tests/test_train_resilience.py).
* ``serve.handoff`` — checksummed KV hand-off payloads are verified on
  adopt; a mismatch falls back to full local prefill, bit-identically.
* ``serve.snapshot`` — ``ServeEngine.restore()`` rejects a corrupted
  snapshot (typed error); failover falls back to a fresh engine and
  the streams stay bit-identical to ``generate()``.

The checksum primitives themselves are pinned first: the in-graph
device fold equals the host twin, and every single-bit flip changes
it. Serve compile pins and the one-host-sync-per-block contract hold
with integrity enabled.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from mmlspark_tpu.core import integrity
from mmlspark_tpu.core.faults import Fault, FaultInjector, parse_fault_spec
from mmlspark_tpu.core.integrity import (
    CheckpointCorruption,
    IntegrityError,
    SnapshotCorruption,
)
from mmlspark_tpu.serve import DisaggFleet, ReplicaSet, ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from mmlspark_tpu.train.demo import run_train_demo
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


def _demo_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.normal(size=(7, 5)).astype(np.float32),
        "b": rng.normal(size=(5,)).astype(np.float32),
        "emb": {"table": rng.normal(size=(11, 3)).astype(np.float32),
                "ids": np.arange(6, dtype=np.int32)},
    }


# -- checksum primitives ---------------------------------------------------


def test_device_fold_matches_host_twin():
    """The in-graph fold (jitted, uint32 carry) and the host-side
    numpy twin agree on arbitrary pytrees — the audit compares them
    directly, so this equality IS the zero-false-positive property."""
    tree = _demo_tree()
    dev = int(jax.jit(integrity.tree_checksum)(tree))
    assert dev == integrity.tree_checksum_host(tree)
    assert 0 <= dev < 2 ** 32


def test_every_single_bit_flip_changes_the_fold():
    """The fold's per-position multipliers are odd (invertible mod
    2^32): any single-bit flip in any leaf changes the checksum."""
    tree = _demo_tree()
    base = integrity.tree_checksum_host(tree)
    for seed in range(24):
        flipped = dict(tree)
        flipped["w"] = integrity.flip_bit_array(tree["w"], seed)
        assert integrity.tree_checksum_host(flipped) != base, seed
        assert not np.array_equal(flipped["w"], tree["w"])


def test_fold_order_sensitivity():
    """Identical bytes in swapped leaf positions fold differently —
    a transposed restore cannot alias a clean checksum."""
    a = {"x": np.ones((4,), np.float32), "y": np.zeros((4,), np.float32)}
    b = {"x": np.zeros((4,), np.float32), "y": np.ones((4,), np.float32)}
    assert integrity.tree_checksum_host(a) != integrity.tree_checksum_host(b)


def test_payload_checksum_verify_and_corrupt_cycle():
    """Hand-off payloads: stamp -> verify passes; seeded bit-flip ->
    verify names both digests; a stampless (pre-integrity) payload is
    accepted unverified for back-compat."""
    rng = np.random.default_rng(3)
    payload = {
        "prompt": np.arange(5, dtype=np.int32),
        "prefix": np.arange(5, 9, dtype=np.int32),
        "length": 9,
        "first_token": 3,
        "kv": {"k": rng.normal(size=(2, 4, 8)).astype(np.float32)},
    }
    payload["checksum"] = integrity.payload_checksum(payload)
    ok, expected, actual = integrity.verify_payload(payload)
    assert ok and expected == actual

    for seed in (0, 1, 17):
        bad = integrity.corrupt_payload(payload, seed)
        ok, expected, actual = integrity.verify_payload(bad)
        assert not ok
        assert expected == payload["checksum"] and actual != expected

    unstamped = {k: v for k, v in payload.items() if k != "checksum"}
    assert integrity.verify_payload(unstamped)[0]


def test_json_checksum_detects_snapshot_bit_flips():
    snap = {"version": 3, "tick": 41, "slots": [1, 0, 7],
            "nested": {"tokens": [5, 6, 7], "done": False}}
    snap["checksum"] = integrity.json_checksum(snap)
    assert integrity.json_checksum(snap) == snap["checksum"]
    for seed in (0, 5, 23):
        bad = integrity.flip_bit_json(snap, seed)
        assert integrity.json_checksum(bad) != bad["checksum"], seed


def test_typed_errors_name_both_hashes():
    e = CheckpointCorruption(7, expected="aa" * 32, actual="bb" * 32)
    assert isinstance(e, IntegrityError)
    assert e.step == 7
    assert "aa" * 32 in str(e) and "bb" * 32 in str(e)
    s = SnapshotCorruption(expected="cafe", actual="beef")
    assert isinstance(s, IntegrityError)
    assert "cafe" in str(s) and "beef" in str(s)


# -- corrupt fault kind (satellite: faults.py) -----------------------------


def test_corrupt_spec_round_trips_and_is_seeded():
    inj = parse_fault_spec("seed=3,train.step:corrupt=0.2")
    fires = {t: inj.corrupt_spec("train.step", tick=t) for t in range(6)}
    seeds = {t: s for t, s in fires.items() if s is not None}
    assert seeds, "the seeded rate stream must fire within 6 ticks"
    assert all(isinstance(s, int) for s in seeds.values())
    # the stream is deterministic: a fresh injector from the same spec
    # fires at the same ticks with the same seeds
    inj2 = parse_fault_spec("seed=3,train.step:corrupt=0.2")
    assert fires == {t: inj2.corrupt_spec("train.step", tick=t)
                     for t in range(6)}


def test_scheduled_corrupt_carries_its_value_as_seed():
    inj = FaultInjector([Fault("train.step", "corrupt", tick=2,
                               value=99)])
    assert inj.corrupt_spec("train.step", tick=0) is None
    assert inj.corrupt_spec("train.step", tick=2) == 99


# -- train.step: in-graph audit + quarantine + replay ----------------------


def test_train_step_corrupt_detected_within_one_audit_interval():
    """The headline train drill: seeded bit-flips on one replica's
    params are caught at the next audit boundary, the replica is
    quarantined and re-replicated from a majority device, and every
    suspicion gets a replay verdict."""
    out = run_train_demo(epochs=2, n_samples=96, batch_size=32,
                         seed=0, audit_every=2,
                         faults="seed=3,train.step:corrupt=0.2")
    assert out["faults_injected"].get("corrupt", 0) >= 1
    assert out["train.integrity.audits"] == 3  # 6 steps / audit_every=2
    assert out["train.integrity.sdc_suspected"] >= 1
    verdicts = out["replay_verdicts"]
    assert len(verdicts) == out["train.integrity.sdc_suspected"]
    for v in verdicts:
        assert v["verdict"] in ("transient_sdc",
                                "software_nondeterminism")
    adjudicated = (out["train.integrity.replay_transient_sdc"]
                   + out["train.integrity.replay_software_nondeterminism"])
    assert adjudicated == out["train.integrity.sdc_suspected"]
    # a step-level drill must not spill into the checkpoint surface
    assert out["train.integrity.checksum_failures"] == 0


def test_train_clean_soak_zero_false_positives():
    """50 audited steps with NO faults: every audit passes — the
    device fold and the host twin never disagree on a clean run."""
    out = run_train_demo(epochs=5, n_samples=80, batch_size=8,
                         seed=1, audit_every=4, checkpoint_every=0)
    assert out["steps_total"] == 50
    assert out["train.integrity.audits"] == 12  # floor(50 / 4)
    assert out["train.integrity.sdc_suspected"] == 0
    assert out["train.integrity.replay_transient_sdc"] == 0
    assert out["train.integrity.replay_software_nondeterminism"] == 0
    assert out["replay_verdicts"] == []


def test_train_audits_off_by_default():
    out = run_train_demo(epochs=2, n_samples=96, batch_size=32, seed=0)
    assert out["audit_every"] == 0
    assert out["train.integrity.audits"] == 0
    assert out["train.integrity.sdc_suspected"] == 0


# -- serve.handoff: checksummed hand-offs ----------------------------------


@pytest.mark.slow  # ci.sh's integrity gate runs the full file unfiltered
def test_handoff_corrupt_falls_back_bit_identically(lm):
    """A corrupted hand-off payload is rejected on adopt (digest
    mismatch), the decode replica re-prefills locally, and every
    stream stays bit-identical to ``generate()`` — under the compile
    pins."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.handoff", "corrupt", tick=0)])
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, faults=inj,
                        retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    with serve_compile_guard(fleet.engine(0), min_prefill=1), \
            serve_compile_guard(fleet.engine(1), min_decode=1):
        gids = [fleet.submit(p, 6) for p in prompts]
        results = fleet.run()
    _assert_parity(m, v, results, gids, prompts, 6)
    md = fleet.metrics_dict()
    assert md["integrity_handoff_checksum_failures_total"] >= 1
    assert md["handoff_fallbacks_total"] >= 1
    assert md["integrity_snapshot_checksum_failures_total"] == 0


@pytest.mark.slow  # ci.sh's integrity gate runs the full file unfiltered
def test_handoff_clean_run_verifies_without_failures(lm):
    """Every adopted payload is verified; a clean run records zero
    checksum failures and zero fallbacks (no false positives)."""
    m, v, ids = lm
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4, 7)]
    gids = [fleet.submit(p, 6) for p in prompts]
    results = fleet.run()
    _assert_parity(m, v, results, gids, prompts, 6)
    md = fleet.metrics_dict()
    assert md["handoffs_total"] == len(prompts)
    assert md["integrity_handoff_checksum_failures_total"] == 0
    assert md["handoff_fallbacks_total"] == 0


# -- serve.snapshot: verified restore --------------------------------------


def test_engine_restore_rejects_corrupted_snapshot(lm):
    """``ServeEngine.restore()`` verifies the snapshot digest before
    rebuilding anything: a bit-flipped snapshot raises the typed
    error; the clean snapshot round-trips; a stampless legacy
    snapshot is still accepted."""
    m, v, ids = lm
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4)
    engine.submit(np.asarray(ids[0, :5]), max_new_tokens=4)
    engine.run()
    snap = engine.snapshot()
    assert snap["checksum"] == integrity.json_checksum(snap)

    for seed in (0, 1, 2):
        bad = integrity.flip_bit_json(snap, seed)
        with pytest.raises(SnapshotCorruption) as exc:
            ServeEngine.restore(bad, m, v)
        assert bad["checksum"] in str(exc.value)

    ServeEngine.restore(snap, m, v)  # clean round-trip still works
    legacy = {k: s for k, s in snap.items() if k != "checksum"}
    ServeEngine.restore(legacy, m, v)


@pytest.mark.slow  # ci.sh's integrity gate runs the full file unfiltered
def test_snapshot_corrupt_failover_falls_back_to_fresh_engine(lm):
    """A corrupted snapshot + a same-tick kill: the failover path
    rejects the snapshot, rebuilds a FRESH engine, re-admits the
    in-flight prompts, and the streams stay bit-identical."""
    m, v, ids = lm
    inj = FaultInjector([
        Fault("serve.snapshot", "corrupt", tick=1, replica=1),
        Fault("serve.decode", "kill", tick=1, replica=1),
    ])
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2,
                    snapshot_every_ticks=1, faults=inj,
                    retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [rs.submit(p, 10) for p in prompts]
    results = rs.run()
    md = rs.metrics_dict()
    assert md["integrity_snapshot_checksum_failures_total"] == 1
    assert rs.replica_failovers_total >= 1
    _assert_parity(m, v, results, gids, prompts, 10)


@pytest.mark.slow  # ci.sh's integrity gate runs the full file unfiltered
def test_clean_chaos_soak_zero_integrity_false_positives(lm):
    """Seeded NON-corrupt chaos (kills with snapshots on): every
    failover restores from a verified snapshot with ZERO checksum
    failures — the stamps never false-positive on clean payloads."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.decode", "kill", tick=1,
                               replica=0)])
    rs = ReplicaSet(m, v, replicas=2, slots=2, cache_len=32,
                    max_queue=8, decode_block=2,
                    snapshot_every_ticks=1, faults=inj,
                    retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (5, 9, 4)]
    gids = [rs.submit(p, 8) for p in prompts]
    results = rs.run()
    md = rs.metrics_dict()
    assert rs.replica_failovers_total >= 1
    assert md["integrity_snapshot_checksum_failures_total"] == 0
    _assert_parity(m, v, results, gids, prompts, 8)


# -- contracts with integrity enabled --------------------------------------


def test_decode_sync_contract_holds_after_verified_restore(lm, monkeypatch):
    """The one-host-sync-per-block contract survives the integrity
    plane: after a checksum-VERIFIED snapshot restore, a request
    decoding 16 tokens through T=8 blocks still pays at most one
    fetch per block, bit-identical to ``generate()``."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :4])
    src = ServeEngine(m, v, slots=1, cache_len=32, decode_block=8)
    snap = src.snapshot()
    ok = integrity.json_checksum(
        {k: s for k, s in snap.items() if k != "checksum"})
    assert snap["checksum"] == ok
    engine = ServeEngine.restore(snap, m, v, slots=1, cache_len=32, decode_block=8)
    rid = engine.submit(prompt, max_new_tokens=17)

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

    monkeypatch.setattr(jax, "device_get", counting_device_get)
    monkeypatch.setattr(np, "asarray", counting_asarray)
    res = engine.run()[rid]
    monkeypatch.undo()

    np.testing.assert_array_equal(
        np.asarray(res.tokens), ref_tokens(m, v, prompt, 17)
    )
    assert syncs["n"] <= 2, f"host syncs: {syncs['n']} (> 1 per block)"
