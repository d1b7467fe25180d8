"""Chunked prefill + pipelined async host runtime (ISSUE 20 tentpole).

The contract under test (docs/PERFORMANCE.md "Chunked prefill & async
host loop"): with ``prefill_chunk=N`` a long prompt's fill becomes
bounded N-token chunk dispatches interleaved with decode ticks under
ONE program family per chunk bucket (``prefill_compile_count <=
num_chunk_buckets``); with ``async_host=True`` decode block N+1
dispatches behind block N's in-flight execution and N's tokens are
fetched only after N+1 is enqueued — still at most one host sync per
block. In BOTH modes (and combined, and on a 2x2 mesh, and across
paged/int8/prefix-cache pools, and through a kill-mid-chunk crash
drill) token streams stay bit-identical to the synchronous monolithic
engine and to the ``generate()`` oracle.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import EngineKilled, Fault, FaultInjector
from mmlspark_tpu.models import build_model
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.serve.metrics import ServeMetrics
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


# -- config validation -----------------------------------------------------


def test_chunk_validation(lm):
    m, v, _ = lm
    for bad in (12, 6, 3, 9):
        with pytest.raises(FriendlyError, match="power of two"):
            ServeEngine(m, v, slots=1, cache_len=32, prefill_chunk=bad)
    with pytest.raises(FriendlyError, match="exceeds cache_len"):
        ServeEngine(m, v, slots=1, cache_len=32, prefill_chunk=64)
    for kw in ({"max_fills": 1}, {"max_fills": 0, "prefill_chunk": 8}):
        with pytest.raises(FriendlyError, match="max_fills"):
            ServeEngine(m, v, slots=1, cache_len=32, **kw)
    moe = build_model(
        "transformer_lm_moe", vocab_size=8, d_model=16, heads=2,
        depth=1, n_experts=2, max_len=16,
    )
    mv = moe.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(FriendlyError, match="MoE"):
        ServeEngine(moe, mv, slots=1, cache_len=16, prefill_chunk=8)


def test_chunk_bucket_ladder(lm):
    m, v, _ = lm
    e = ServeEngine(m, v, slots=1, cache_len=32, prefill_chunk=16)
    # ladder {8, 16}: two chunk buckets, and the prefill pin redirects
    assert e.num_chunk_buckets == 2
    assert e.num_prefill_buckets == 2
    assert e.chunk_bucket(1) == 8
    assert e.chunk_bucket(8) == 8
    assert e.chunk_bucket(9) == 16
    assert e.chunk_bucket(16) == 16
    e8 = ServeEngine(m, v, slots=1, cache_len=32, prefill_chunk=8)
    assert e8.num_chunk_buckets == 1
    # no chunking: the monolithic bucket count is untouched
    mono = ServeEngine(m, v, slots=1, cache_len=32)
    assert mono.num_prefill_buckets > 0
    assert mono.num_chunk_buckets == 0


# -- parity: chunked fills vs generate() / monolithic ----------------------


@pytest.mark.slow  # ci.sh's chunked gate runs the full file unfiltered
def test_chunked_parity_ragged_prompts_and_mid_fill_joins(lm):
    """Chunk=8 over prompts from 1 to 12 tokens (multi-chunk fills for
    the long ones), heterogeneous budgets, and mid-run joins landing
    while other slots are mid-fill AND mid-decode — every stream equals
    generate()'s, under the compile guard with the TIGHTENED pin."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:12], row[:1], row[:9], row[:4], row[:11], row[:6]]
    budgets = [6, 9, 4, 8, 5, 7]

    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=4, prefill_chunk=8)
    results, rids = {}, []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        for p, n in zip(prompts[:3], budgets[:3]):
            rids.append(engine.submit(p, max_new_tokens=n))
        for _ in range(3):
            results.update({r.id: r for r in engine.step()})
        # joins land while slot 0's 12-token fill may still be open
        for p, n in zip(prompts[3:], budgets[3:]):
            rids.append(engine.submit(p, max_new_tokens=n))
        while engine.busy:
            results.update({r.id: r for r in engine.step()})

    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, n),
            err_msg=f"chunked fill diverged: request={rid}",
        )
    # the tentpole pin: one program per chunk bucket, ceiling included
    assert engine.prefill_compile_count <= engine.num_chunk_buckets == 1
    assert engine.metrics.chunked_prefills_total >= len(prompts) + 1


def test_max_fills_caps_the_fills_in_flight(lm):
    """``max_fills=1``: three long prompts over three free slots start
    one fill at a time, the others wait in the queue, and every stream
    is still generate()'s."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:12], row[1:13], row[2:14]]
    engine = ServeEngine(m, v, slots=3, cache_len=32, max_queue=8,
                         decode_block=4, prefill_chunk=8, max_fills=1)
    rids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    results, most = {}, 0
    while engine.busy:
        results.update({r.id: r for r in engine.step()})
        most = max(most, len(engine._sched.filling))
    assert most == 1
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(np.asarray(results[rid].tokens),
                                      ref_tokens(m, v, p, 5))


def test_chunked_parity_mid_fill_eos_and_tiny_budget(lm):
    """A fill whose FIRST token is the EOS retires at fill completion
    without ever activating; budget=1 retires the same way — both
    match generate()'s trim."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :9])  # 2 chunks at chunk=8
    free = ref_tokens(m, v, prompt, 4)
    eos = int(free[len(prompt)])  # the first generated token

    engine = ServeEngine(m, v, slots=2, cache_len=32, prefill_chunk=8)
    r_eos = engine.submit(prompt, max_new_tokens=4, eos_id=eos)
    r_one = engine.submit(prompt, max_new_tokens=1)
    res = engine.run()
    np.testing.assert_array_equal(
        np.asarray(res[r_eos].tokens), free[:len(prompt) + 1]
    )
    assert res[r_eos].generated == 1
    np.testing.assert_array_equal(
        np.asarray(res[r_one].tokens), free[:len(prompt) + 1]
    )


@pytest.mark.slow  # ci.sh's chunked gate runs the full file unfiltered
def test_chunked_parity_paged_prefix_and_int8(lm):
    """Chunked fills land bit-identically through the paged pool with
    the prefix cache on (a resubmitted prompt seeds its carry from the
    shared prefix) and with int8 KV — the one write_prefill at fill
    completion quantizes ONCE from the bf16 carry, exactly like the
    monolithic path."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:12], row[:12], row[:9], row[:5]]  # [1] re-uses [0]

    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         prefill_chunk=8, paged=True, page_size=8,
                         prefix_cache=True, kv_dtype="int8")
    rids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    res = engine.run()
    oracle = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         paged=True, page_size=8, prefix_cache=True,
                         kv_dtype="int8")
    orids = [oracle.submit(p, max_new_tokens=5) for p in prompts]
    ores = oracle.run()
    for rid, oid, p in zip(rids, orids, prompts):
        np.testing.assert_array_equal(
            np.asarray(res[rid].tokens), np.asarray(ores[oid].tokens),
            err_msg=f"chunked+paged+int8 diverged from monolithic: {p}",
        )
    # dense int8: chunked fills are start=0 whole-range writes (no
    # prefix cache on dense pools), still bit-identical
    dense = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                        prefill_chunk=8, kv_dtype="int8")
    drids = [dense.submit(p, max_new_tokens=5) for p in prompts]
    dres = dense.run()
    for rid, did in zip(rids, drids):
        np.testing.assert_array_equal(
            np.asarray(res[rid].tokens), np.asarray(dres[did].tokens)
        )


# -- parity: async host loop -----------------------------------------------


def test_async_parity_and_at_most_one_sync_per_block(lm, monkeypatch):
    """The async loop's relaxed sync contract: one request decoding 16
    tokens through T=8 blocks pays at most 2 synced fetches (one per
    block — the pipelined fetch lands a tick late but never adds a
    sync), and the stream equals generate()'s."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :4])
    engine = ServeEngine(m, v, slots=1, cache_len=32, decode_block=8,
                         async_host=True)
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
    d = engine.metrics.to_dict()
    assert d["async_host"] == 1
    assert d["host_idle_fraction"] is not None


@pytest.mark.slow  # ci.sh's chunked gate runs the full file unfiltered
def test_async_parity_ragged_with_joins_and_overlap(lm):
    """Multi-slot async run with mid-run joins (new fills start while a
    speculative block is in flight — the identity fence and deferred
    frees keep re-leases safe): streams equal generate()'s and the
    engine really pipelined (overlapped dispatches recorded)."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:4], row[:1], row[:9], row[:6], row[:2]]
    budgets = [10, 7, 3, 12, 5]

    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=4, async_host=True,
                         prefill_chunk=8)
    results, rids = {}, []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        for p, n in zip(prompts[:3], budgets[:3]):
            rids.append(engine.submit(p, max_new_tokens=n))
        for _ in range(2):
            results.update({r.id: r for r in engine.step()})
        for p, n in zip(prompts[3:], budgets[3:]):
            rids.append(engine.submit(p, max_new_tokens=n))
        while engine.busy:
            results.update({r.id: r for r in engine.step()})

    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, n),
            err_msg=f"async stream diverged: request={rid}",
        )
    assert engine.metrics.overlapped_dispatches_total > 0
    assert engine.decode_compile_count <= engine.num_decode_blocks
    assert engine.prefill_compile_count <= engine.num_chunk_buckets


@pytest.mark.slow  # ci.sh's chunked gate runs the full file unfiltered
def test_chunked_async_parity_2x2_mesh(lm):
    """Chunked fills + the pipelined loop on a data=2,model=2 mesh:
    streams stay bit-identical to single-device generate() and both
    compile pins hold (per-tick inputs still commit to the pinned
    NamedShardings)."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:12], row[:3], row[:9], row[:6]]

    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=4, prefill_chunk=8,
                         async_host=True, mesh={"data": 2, "model": 2})
    results, rids = {}, []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
        while engine.busy:
            results.update({r.id: r for r in engine.step()})
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 6),
            err_msg=f"mesh chunked+async diverged: request={rid}",
        )
    assert engine.prefill_compile_count <= engine.num_chunk_buckets
    assert engine.decode_compile_count <= engine.num_decode_blocks


# -- crash drill: kill mid-chunk, restore, bit-identical -------------------


@pytest.mark.slow  # ci.sh's chunked gate runs the full file unfiltered
def test_kill_mid_chunk_restore_is_bit_identical(lm):
    """A kill landing at the prefill site while a multi-chunk fill is
    open (chunked + async engine): the park closes the deferred-free
    window, the snapshot carries the mid-fill request as a queued
    entry, and the restored engine finishes every stream bit-identical
    to the uncrashed oracle."""
    import json

    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:12], row[:9], row[:4], row[:11]]
    # tick 0 dispatches each fill's first chunk (both prompts > chunk);
    # tick 1's first prefill firing is slot 0's FINAL chunk while slot
    # 1's fill is still open — the kill lands mid-multi-chunk-fill
    inj = FaultInjector([Fault("serve.prefill", "kill", tick=1)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=2,
                         prefill_chunk=8, async_host=True, faults=inj)
    rids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    results = {}
    snap = engine.snapshot()
    with pytest.raises(EngineKilled):
        while engine.busy:
            snap = engine.snapshot()
            for res in engine.step():
                results[res.id] = res
    json.dumps(snap)
    assert snap["active"] or snap["queued"]

    rebuilt = ServeEngine.restore(snap, m, v, slots=2, decode_block=2,
                                  prefill_chunk=8, async_host=True)
    results.update(rebuilt.run())
    assert set(results) == set(rids)
    for rid, p in zip(rids, prompts):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 8),
            err_msg=f"request {rid} diverged across the mid-chunk kill",
        )


# -- disaggregated fleet: prefill replicas chunk their backlogs ------------


@pytest.mark.slow  # ci.sh's chunked gate runs the full file unfiltered
def test_disagg_chunked_handoff(lm):
    """A prefill-role replica with chunking on advances its fill
    backlog chunk by chunk and fires the KV hand-off at FILL COMPLETION
    — the decode replica adopts without compiling a prefill program,
    and every stream equals generate()'s."""
    from mmlspark_tpu.serve.fleet import DisaggFleet

    m, v, ids = lm
    prompts = [np.asarray(ids[0, :n]) for n in (12, 4, 9, 6)]
    fleet = DisaggFleet(m, v, prefill_replicas=1, decode_replicas=1,
                        slots=2, cache_len=32, max_queue=8,
                        decode_block=4, prefill_chunk=8,
                        retry_backoff_s=0.0)
    gids = [fleet.submit(p, 6) for p in prompts]
    results = fleet.run()
    for gid, p in zip(gids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[gid].tokens), ref_tokens(m, v, p, 6),
            err_msg=f"disagg chunked hand-off diverged: {p}",
        )
    assert fleet.engine(1).prefill_compile_count == 0
    assert fleet.engine(0).metrics.chunked_prefills_total >= len(prompts)
    assert fleet.engine(0).metrics.handoffs_out_total == len(prompts)


# -- pool plumbing: deferred frees + ranged dense writes -------------------


def test_deferred_free_window_and_dense_start_validation(lm):
    from mmlspark_tpu.serve.cache_pool import SlotCachePool

    m, v, _ = lm
    pool = SlotCachePool(m, v, slots=2, cache_len=32)
    s0 = pool.lease()
    s1 = pool.lease()
    pool.defer_frees(1)
    pool.free(s0)
    # inside the window: the lease is NOT reusable yet...
    with pytest.raises(FriendlyError):
        pool.lease()
    # ...and a second free of the same slot is still a double free
    with pytest.raises(FriendlyError, match="double free"):
        pool.free(s0)
    pool.defer_frees(2)
    pool.free(s1)
    pool.flush_frees(1)  # releases gen<=1 only
    assert pool.lease() == s0
    with pytest.raises(FriendlyError):
        pool.lease()
    pool.flush_frees(None)  # close the window: everything releases
    assert pool.lease() == s1

    # ranged writes: int8 dense pools quantize per-head over the FULL
    # row, so a partial write would re-scale earlier positions
    pool8 = SlotCachePool(m, v, slots=1, cache_len=32, kv_dtype="int8")
    slot = pool8.lease()
    from mmlspark_tpu.models.generate import init_cache

    cache = init_cache(m, v, 1, 32)
    with pytest.raises(FriendlyError, match="start=0"):
        pool8.write_prefill(slot, cache, 8, start=4)


# -- honest attribution + schema under pipelining --------------------------


def test_perf_queued_attribution():
    from mmlspark_tpu.core.perf import PerfAnalytics, ProgramCost

    p = PerfAnalytics(n_devices=1)
    p.register_program(
        "decode[T=4]",
        ProgramCost(flops=1e9, bytes_accessed=1e6, source="test"),
    )
    # 10ms interval, 6ms of it queued behind the previous block
    p.record_dispatch("decode[T=4]", 0.010, tokens=4, queued_s=0.006)
    p.record_dispatch("decode[T=4]", 0.004, tokens=4)
    fam = p.summary()["families"]["decode[T=4]"]
    assert fam["device_s"] == pytest.approx(0.008)
    assert fam["queued_s"] == pytest.approx(0.006)
    # MFU divides by EXECUTING time only — pipelining can't halve it
    assert fam["mfu"] == pytest.approx(2e9 / 0.008 / p.peak.flops_per_s)
    # queued_s clamps into [0, seconds]
    p.record_dispatch("decode[T=4]", 0.002, queued_s=5.0)
    assert p.summary()["families"]["decode[T=4]"]["device_s"] == \
        pytest.approx(0.008)


def test_metrics_new_keys_and_host_idle():
    a = ServeMetrics("m", slots=2)
    d = a.to_dict()
    # inert defaults on a monolithic-synchronous engine
    assert d["prefill_chunk"] == 0
    assert d["chunked_prefills_total"] == 0
    assert d["async_host"] == 0
    assert d["overlapped_dispatches_total"] == 0
    assert d["host_idle_fraction"] is None

    b = ServeMetrics("m", slots=2, prefill_chunk=16, async_host=True)
    b.record_prefill_chunk()
    b.record_prefill_chunk()
    b.record_overlapped_dispatch()
    b.record_host_sync(0.002)
    b.sample_tick(0, 1, 0.010, tokens_emitted=1)
    d = b.to_dict()
    assert d["prefill_chunk"] == 16
    assert d["chunked_prefills_total"] == 2
    assert d["async_host"] == 1
    assert d["overlapped_dispatches_total"] == 1
    assert d["host_idle_fraction"] == pytest.approx(0.2)
    assert d["host_sync_wait_s"] == pytest.approx(0.002)
