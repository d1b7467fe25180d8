"""Continuous-batching serving engine (mmlspark_tpu.serve): scheduling
and parity.

The contract under test (docs/SERVING.md): an engine whose staggered
multi-tenant decode emits BYTE-IDENTICAL tokens to single-request
``generate()``, deterministic tick-based deadlines, and typed
admission-control errors. The pool and its writes: test_serve_pool.py;
the regions and the compile-count soak: test_serve_regions.py.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.metrics_contracts import MetricData
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.testing.compile_guard import compile_guard
from tests.serve_helpers import init_lm, ref_tokens, tiny_lm, trained_lm


# -- token parity (the acceptance test) ------------------------------------

@pytest.mark.parametrize("config", [
    {},                                        # learned positions
    {"pos_embedding": "rope", "kv_heads": 1},  # RoPE + MQA
])
def test_staggered_arrivals_match_generate(config):
    """Three requests with different prompt lengths, submitted on
    different ticks, sharing 2 slots: every request's token stream must
    be byte-identical to a single-request ``generate()`` call, and the
    fused decode step must have compiled exactly once — requests joining
    and leaving mid-flight never retrace it."""
    m, v, ids = trained_lm(**config)
    prompts = [np.asarray(ids[0, :n]) for n in (4, 6, 7)]
    want = {
        i: ref_tokens(m, v, p, 8)
        for i, p in enumerate(prompts)
    }

    engine = ServeEngine(m, v, slots=2, cache_len=32)
    results = {}
    rid_to_idx = {}
    with compile_guard(lambda: engine.decode_compile_count,
                       max_programs=engine.num_decode_blocks,
                       min_programs=1, label="decode"):
        for i, p in enumerate(prompts):  # staggered: one submit per tick
            rid_to_idx[engine.submit(p, max_new_tokens=8)] = i
            for res in engine.step():
                results[res.id] = res
        while engine.busy:
            for res in engine.step():
                results[res.id] = res

    assert len(results) == 3
    for rid, res in results.items():
        assert res.status == "completed"
        np.testing.assert_array_equal(
            np.asarray(res.tokens), want[rid_to_idx[rid]]
        )


def test_more_requests_than_slots_still_match():
    """Queue pressure: 4 requests through 1 slot — pure sequential
    reuse of the same slot buffers (stale K/V from the previous tenant
    must be invisible)."""
    m, v, ids = trained_lm()
    prompts = [np.asarray(ids[0, :n]) for n in (4, 5, 6, 8)]
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=4)
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
    results = engine.run()
    for rid, p in zip(rids, prompts):
        want = ref_tokens(m, v, p, 6)
        np.testing.assert_array_equal(np.asarray(results[rid].tokens), want)
    # distinct XLA programs, one per ladder block size actually run —
    # never one per token or per scan iteration
    assert 1 <= engine.decode_compile_count <= engine.num_decode_blocks


def test_eos_retires_early():
    m, v, ids = trained_lm()
    prompt = np.asarray(ids[0, :4])
    ref = ref_tokens(m, v, prompt, 8)
    eos = int(ref[5])  # the 2nd generated token, by construction
    engine = ServeEngine(m, v, slots=2, cache_len=32)
    rid = engine.submit(prompt, max_new_tokens=8, eos_id=eos)
    res = engine.run()[rid]
    assert res.status == "completed"
    assert res.generated == 2 and int(res.tokens[-1]) == eos


# -- deadlines and admission control ---------------------------------------


def test_deadline_expiry_in_queue():
    """With 1 slot busy on a long request, a queued request whose
    deadline passes expires WITHOUT ever being admitted (no prefill, no
    tokens) — deterministic in ticks."""
    m, v, ids = trained_lm()
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=2)
    rid_a = engine.submit(np.asarray(ids[0, :4]), max_new_tokens=10)
    rid_b = engine.submit(np.asarray(ids[0, :5]), max_new_tokens=4,
                          deadline_ticks=2)
    results = engine.run()
    assert results[rid_a].status == "completed"
    assert results[rid_a].generated == 10
    assert results[rid_b].status == "expired"
    assert results[rid_b].generated == 0
    assert engine.metrics.expired == 1 and engine.metrics.completed == 1


def test_run_max_ticks_attaches_partial_results():
    """``run(max_ticks=N)`` overrunning must not DISCARD the finished
    work: the raised FriendlyError carries ``err.results`` with every
    completed request plus the pending ones retired as ``"stalled"``,
    and the engine is left drained (not busy, pool empty)."""
    m, v, ids = trained_lm()
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=4,
                         decode_block=1)
    rid_short = engine.submit(np.asarray(ids[0, :4]), max_new_tokens=2)
    rid_long = engine.submit(np.asarray(ids[0, :5]), max_new_tokens=20)
    with pytest.raises(FriendlyError, match="stalled") as ei:
        engine.run(max_ticks=4)
    results = ei.value.results
    assert results[rid_short].status == "completed"
    assert results[rid_short].generated == 2
    assert results[rid_long].status == "stalled"
    # partial progress travels with the stalled result
    assert 0 < results[rid_long].generated < 20
    assert engine.metrics.stalled == 1 and engine.metrics.completed == 1
    assert not engine.busy and engine.pool.leased_count == 0
    # the drained engine is still serviceable
    rid2 = engine.submit(np.asarray(ids[0, :4]), max_new_tokens=2)
    assert engine.run()[rid2].status == "completed"


def test_expire_active_slot_forces_device_state_dead():
    """Expiring an ACTIVE request must kill its device-side row — live
    mask False, position 0 — immediately, so the fused decode spends no
    flash-decode KV traffic on a corpse and the slot is re-leasable."""
    m, v, ids = trained_lm()
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=4,
                         decode_block=1)
    prompt_b = np.asarray(ids[0, :5])
    ref_b = ref_tokens(m, v, prompt_b, 10)
    rid_a = engine.submit(np.asarray(ids[0, :4]), max_new_tokens=12,
                          deadline_ticks=2)
    rid_b = engine.submit(prompt_b, max_new_tokens=10)
    results = {r.id: r for r in engine.step()}  # tick 0: both admitted
    slot_a = next(s for s, st in engine._sched.active.items()
                  if st.req.id == rid_a)
    while rid_a not in results:
        results.update({r.id: r for r in engine.step()})
    assert results[rid_a].status == "expired"
    # the expired row is dead ON DEVICE, mid-run, with B still active
    assert not bool(np.asarray(jax.device_get(engine.pool.live))[slot_a])
    assert int(np.asarray(jax.device_get(
        engine.pool.positions))[slot_a]) == 0
    assert any(st.req.id == rid_b
               for st in engine._sched.active.values())
    # the freed slot re-leases cleanly while B keeps decoding
    rid_c = engine.submit(np.asarray(ids[0, :6]), max_new_tokens=4)
    results.update(engine.run())
    assert results[rid_b].status == "completed"
    np.testing.assert_array_equal(np.asarray(results[rid_b].tokens),
                                  np.asarray(ref_b))
    assert results[rid_c].status == "completed"


def test_expired_slot_releases_same_tick():
    """The slot freed by an active-request expiry is safe to re-lease
    in the SAME tick: the replacement prefills into it immediately and
    its stream matches ``generate()`` (no stale KV bleed-through)."""
    m, v, ids = trained_lm()
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=4,
                         decode_block=1)
    prompt_b = np.asarray(ids[0, :5])
    ref_b = ref_tokens(m, v, prompt_b, 6)
    rid_a = engine.submit(np.asarray(ids[0, :4]), max_new_tokens=12,
                          deadline_ticks=2)
    rid_b = engine.submit(prompt_b, max_new_tokens=6)  # waits for the slot
    results = engine.run()
    assert results[rid_a].status == "expired"
    assert results[rid_b].status == "completed"
    # B entered the slot on the very tick A expired out of it
    assert results[rid_b].first_token_tick == results[rid_a].finish_tick
    np.testing.assert_array_equal(np.asarray(results[rid_b].tokens),
                                  np.asarray(ref_b))


def test_queue_full_raises_typed_error():
    m = tiny_lm()
    v = init_lm(m)
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=2)
    engine.submit(np.ones(4, np.int32), max_new_tokens=2)
    engine.submit(np.ones(4, np.int32), max_new_tokens=2)
    with pytest.raises(FriendlyError, match="queue is full"):
        engine.submit(np.ones(4, np.int32), max_new_tokens=2)
    assert engine.metrics.rejected == 1
    assert engine.metrics.submitted == 2


def test_submit_validation():
    m = tiny_lm()
    v = init_lm(m)
    engine = ServeEngine(m, v, slots=1, cache_len=16)
    with pytest.raises(FriendlyError, match="1-D"):
        engine.submit(np.ones((2, 4), np.int32), max_new_tokens=2)
    with pytest.raises(FriendlyError, match="max_new_tokens"):
        engine.submit(np.ones(4, np.int32), max_new_tokens=0)
    with pytest.raises(FriendlyError, match="cache_len"):
        engine.submit(np.ones(10, np.int32), max_new_tokens=10)
    with pytest.raises(FriendlyError, match="deadline_ticks"):
        engine.submit(np.ones(4, np.int32), max_new_tokens=2,
                      deadline_ticks=0)
    with pytest.raises(FriendlyError, match="non-empty"):
        engine.submit(np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(FriendlyError, match="max_new_tokens"):
        engine.submit(np.ones(4, np.int32), max_new_tokens=-3)
    # a prompt >= cache_len gets the POINTED admission error (it could
    # never fit a single generated token, whatever the budget)
    with pytest.raises(FriendlyError, match="truncate the prompt"):
        engine.submit(np.ones(16, np.int32), max_new_tokens=1)
    # out-of-vocab prompt tokens are rejected at submit, not at decode
    with pytest.raises(FriendlyError, match=r"in \[0, 8\)"):
        engine.submit(np.full(4, 99, np.int32), max_new_tokens=2)
    with pytest.raises(FriendlyError, match=r"in \[0, 8\)"):
        engine.submit(np.asarray([1, -2, 3], np.int32), max_new_tokens=2)
    # nothing above leaked into the accounting
    assert engine.metrics.submitted == 0 and not engine.busy


def test_engine_build_guards():
    m = tiny_lm()
    v = init_lm(m)
    # learned position table bounds cache_len
    with pytest.raises(FriendlyError, match="position table"):
        ServeEngine(m, v, cache_len=64)
    # sliding-window models roll their cache; the linear slot pool
    # refuses rather than silently mis-serving long requests
    mw = tiny_lm(window=6)
    vw = init_lm(mw)
    with pytest.raises(FriendlyError, match="window"):
        ServeEngine(mw, vw, cache_len=32)
    ServeEngine(mw, vw, cache_len=6)  # cache_len <= window is fine


# -- metrics ---------------------------------------------------------------


def test_metrics_dict_and_snapshot():
    m, v, ids = trained_lm()
    engine = ServeEngine(m, v, slots=2, cache_len=32)
    engine.submit(np.asarray(ids[0, :4]), max_new_tokens=3)
    engine.submit(np.asarray(ids[0, :6]), max_new_tokens=3)
    engine.run()

    d = engine.metrics.to_dict()
    for key in ("queue_depth_mean", "queue_depth_max", "ttft_ticks_mean",
                "ttft_ms_mean", "per_token_ms", "slot_utilization_mean",
                "slot_utilization_peak", "tokens_per_sec"):
        assert d[key] is not None, key
    assert d["completed"] == 2 and d["tokens_generated"] == 6
    assert 0.0 < d["slot_utilization_peak"] <= 1.0
    json.dumps(d)  # the CLI's one-line contract: JSON-able as-is

    records = engine.metrics.snapshot()
    assert records and all(isinstance(r, MetricData) for r in records)
    assert all(r.group in ("serve", "table") for r in records)
    names = {r.name for r in records}
    assert "serve.completed" in names and "serve.per_token_ms" in names
    # non-scalar metrics must NOT be dropped: prefill_buckets reaches the
    # metrics plane as a create_table record
    tables = [r for r in records if r.group == "table"]
    assert any(r.name == "serve.prefill_buckets" for r in tables)
