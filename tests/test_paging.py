"""Paged KV-cache subsystem (ISSUE 9 tentpole).

The contract under test (docs/SERVING.md "Paged KV cache"):
``PagedCachePool`` virtualizes the dense slot pool's worst-case slabs
behind fixed-shape page stores and per-slot page tables, and NOTHING
the serving engine guarantees moves: greedy token streams stay
bit-identical to the dense pool (which is itself pinned byte-identical
to ``generate()``), the compile-count pins hold, page pressure walks
the PR 7 degradation ladder instead of crashing, and every terminal
status — completed, expired, quarantined — returns its pages. The
prefix cache prefills a shared prompt header ONCE, maps it refcounted
into later slots, and copy-on-extends the moment a write frontier
enters a shared page. Runs on the 8 virtual CPU devices
``tests/conftest.py`` forces.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import Fault, FaultInjector, ResourceExhausted
from mmlspark_tpu.parallel.mesh import make_mesh
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.serve.paging import (
    MIN_PAGE_SIZE,
    PagedCachePool,
    default_page_size,
)
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm

TERMINAL = {"completed", "expired", "failed", "stalled"}


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


def _pool(m, v, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("cache_len", 32)
    return PagedCachePool(m, v, **kw)


def _entry_pages(pool) -> int:
    """Distinct pages the prefix-cache entries keep pinned — what a
    drained pool legitimately holds back from the free list."""
    pages: set[int] = set()
    for e in pool.snapshot()["prefix_entries"]:
        pages.update(e["pages"])
    return len(pages)


def _fake_prefill(pool, length, seed=0):
    """A synthetic batch-1 linear cache — deterministic values so page
    scatter/gather round-trips are content-checkable without a model."""
    rng = np.random.default_rng(seed)
    cache = {}
    for name, (pk, _pv, _pt) in pool.buffers.items():
        hk, d = pk.shape[1], pk.shape[3]
        k = rng.normal(size=(1, length, hk, d)).astype(np.float32)
        v = rng.normal(size=(1, length, hk, d)).astype(np.float32)
        cache[name] = (jnp.asarray(k, jnp.bfloat16),
                       jnp.asarray(v, jnp.bfloat16))
    return cache


# -- page geometry ---------------------------------------------------------


def test_default_page_size():
    assert default_page_size(64) == 8
    assert default_page_size(32) == 8
    assert default_page_size(40) == 8
    assert default_page_size(8) == 8
    assert default_page_size(48) == 8
    for cl in (16, 24, 48, 96, 80):
        ps = default_page_size(cl)
        assert ps >= MIN_PAGE_SIZE and cl % ps == 0
        assert ps % MIN_PAGE_SIZE == 0  # the kernel's sublane contract
    # no multiple of 8 divides these: refuse at BUILD time — the old
    # behavior returned e.g. 10 for 20 and every paged decode dispatch
    # then died on the kernel's sublane check
    for cl in (20, 36, 100):
        with pytest.raises(FriendlyError, match="multiple"):
            default_page_size(cl)


def test_pool_and_engine_flag_validation(lm):
    m, v, _ = lm
    with pytest.raises(FriendlyError, match="page_size"):
        _pool(m, v, page_size=4)
    with pytest.raises(FriendlyError, match="multiple"):
        _pool(m, v, page_size=12)  # not sublane-tileable by the kernel
    with pytest.raises(FriendlyError, match="divide"):
        _pool(m, v, page_size=24)  # 24 does not divide 32
    with pytest.raises(FriendlyError, match="multiple"):
        _pool(m, v, cache_len=20)  # no valid default page size
    with pytest.raises(FriendlyError, match="trash page"):
        _pool(m, v, num_pages=1)
    # paging knobs without paged=True must refuse loudly, not silently
    # serve dense
    with pytest.raises(FriendlyError, match="paged=True"):
        ServeEngine(m, v, slots=2, cache_len=32, page_size=8)
    with pytest.raises(FriendlyError, match="paged=True"):
        ServeEngine(m, v, slots=2, cache_len=32, prefix_cache=True)


# -- host allocator invariants ---------------------------------------------


def test_alloc_refcount_free_and_double_free(lm):
    m, v, _ = lm
    pool = _pool(m, v)  # page_size 8, default worst-case budget
    assert pool.pages_free == pool.pages_allocatable
    slot = pool.lease()
    pool.write_prefill(slot, _fake_prefill(pool, 12), 12)
    snap = pool.snapshot()
    assert snap["npages"][slot] == 2  # ceil(12 / 8)
    mapped = snap["page_table"][slot][:2]
    assert all(snap["refcounts"][p] == 1 for p in mapped)
    assert pool.pages_free == pool.pages_allocatable - 2
    pool.free(slot)
    assert pool.pages_free == pool.pages_allocatable
    assert sum(pool.snapshot()["refcounts"]) == 0
    with pytest.raises(FriendlyError, match="not leased"):
        pool.free(slot)  # double free
    with pytest.raises(FriendlyError, match="underflow"):
        pool._decref(mapped[0])  # page already back on the free list


def test_freed_rows_point_at_the_trash_page(lm):
    m, v, _ = lm
    pool = _pool(m, v)
    slot = pool.lease()
    pool.write_prefill(slot, _fake_prefill(pool, 9), 9)
    assert any(p != 0 for p in pool.snapshot()["page_table"][slot])
    pool.free(slot)
    # every entry of the freed row absorbs dead-row writes harmlessly
    assert all(p == pool._trash_page(0)
               for p in pool.snapshot()["page_table"][slot])


def test_page_scatter_gather_roundtrip(lm):
    """write_prefill's paged scatter and gather_prefix's linearization
    are exact inverses — the resume path feeds the prefill program the
    same bytes the original prefill produced."""
    m, v, _ = lm
    pool = _pool(m, v, prefix_cache=True)
    cache = _fake_prefill(pool, 14, seed=3)
    seq = np.arange(14, dtype=np.int32) % 8
    slot = pool.lease()
    pool.write_prefill(slot, cache, 14)
    pool.prefix_insert(slot, seq)
    hit = pool.prefix_lookup(seq, bucket_fn=lambda n: n)
    assert hit is not None
    entry, keep = hit
    assert keep == 13  # full prefix minus the one remainder token
    lin = pool.gather_prefix(entry, keep)
    for name, (ck, cv) in cache.items():
        gk, gv = lin[name]
        np.testing.assert_array_equal(
            np.asarray(gk[0, :keep], np.float32),
            np.asarray(ck[0, :keep], np.float32), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(gv[0, :keep], np.float32),
            np.asarray(cv[0, :keep], np.float32), err_msg=name)


def test_pool_exhaustion_raises_resource_exhausted(lm):
    m, v, _ = lm
    pool = _pool(m, v, num_pages=4)  # 1 trash + 3 allocatable
    slot = pool.lease()
    with pytest.raises(ResourceExhausted, match="exhausted"):
        pool.write_prefill(slot, _fake_prefill(pool, 32), 32)  # 4 pages
    # pages mapped before the failure stay accounted to the slot, so
    # freeing it leaks nothing
    assert pool.pages_free == 0
    pool.free(slot)
    assert pool.pages_free == pool.pages_allocatable


def test_map_prefix_stale_entry_refuses_resurrection(lm):
    """The resume-retry hazard: attempt 1 maps a prefix entry, the
    remainder write's page pressure evicts that very entry, and the
    retry re-enters map_prefix. The stale re-map must map NOTHING and
    return False (the engine then falls back to a full prefill) — the
    old path released the slot's references, dropped the pages onto the
    free list, and re-mapped them anyway, leaving a page mapped and
    allocatable at once."""
    m, v, _ = lm
    pool = _pool(m, v, prefix_cache=True)
    seq = np.arange(14, dtype=np.int32) % 8
    s0 = pool.lease()
    pool.write_prefill(s0, _fake_prefill(pool, 14), 14)
    pool.prefix_insert(s0, seq)
    pool.free(s0)
    entry, keep = pool.prefix_lookup(seq, bucket_fn=lambda n: n)
    s1 = pool.lease()
    assert pool.map_prefix(s1, entry, keep) is True  # attempt 1
    # mid-attempt eviction, exactly as _evict_prefix_entries does it:
    # the entry leaves the cache and drops its page references (the
    # pages survive on slot 1's references alone)
    assert pool._prefix.pop(seq.tobytes()) is entry
    for page in entry.pages:
        pool._decref(page)
    assert pool.map_prefix(s1, entry, keep) is False  # stale retry
    # invariant: no page is simultaneously mapped and on a free list
    snap = pool.snapshot()
    free = {p for f in pool._free_pages for p in f}
    for s in range(pool.num_slots):
        mapped = set(snap["page_table"][s][:snap["npages"][s]])
        assert not (free & mapped)
    # slot 1 kept its attempt-1 mappings; retirement returns every
    # page without a refcount underflow
    pool.free(s1)
    assert pool.pages_free == pool.pages_allocatable
    assert sum(pool.snapshot()["refcounts"]) == 0


# -- shard locality under a mesh -------------------------------------------


def test_prefix_eviction_is_shard_local(lm):
    """Pressure on one data shard evicts only that shard's prefix
    entries: evicting another shard's entry frees nothing on the
    pressured shard, so the old global-LRU sweep wiped unrelated
    shards' cached prefixes and still exhausted."""
    m, v, _ = lm
    pool = PagedCachePool(m, v, slots=4, cache_len=32,
                          mesh=make_mesh({"data": 2}), num_pages=6,
                          prefix_cache=True)
    # per shard: 1 trash + 2 allocatable pages
    s0, s1, s2 = pool.lease(), pool.lease(), pool.lease()
    a = np.arange(16, dtype=np.int32) % 8
    b = (a + 1) % 8
    pool.write_prefill(s0, _fake_prefill(pool, 16, seed=1), 16)  # shard 0
    pool.prefix_insert(s0, a)
    pool.write_prefill(s2, _fake_prefill(pool, 16, seed=2), 16)  # shard 1
    pool.prefix_insert(s2, b)
    for s in (s0, s1, s2):
        pool.free(s)
    assert pool.pages_free == 0  # both shards fully pinned by entries
    p0 = pool._alloc_page(0)  # pressure on shard 0
    assert pool.prefix_evictions == 1
    assert pool._shard_of_page(p0) == 0
    p1 = pool._alloc_page(0)  # the evicted entry's second page
    # nothing local left to evict: raise rather than wipe shard 1
    with pytest.raises(ResourceExhausted, match="exhausted"):
        pool._alloc_page(0)
    snap = pool.snapshot()
    assert [e["prompt"] for e in snap["prefix_entries"]] == [b.tolist()]
    pool._decref(p0)
    pool._decref(p1)
    assert pool.pages_free == pool.pages_allocatable - _entry_pages(pool)


def test_prefix_cross_shard_hit_copies_pages_local(lm):
    """A hit from a slot on another data shard localizes the entry's
    pages by copy instead of mapping them remotely — the per-page
    placement contract (every page a slot maps lives on the slot's
    shard) holds, the bytes match, and the entry's own pages are
    untouched."""
    m, v, _ = lm
    pool = PagedCachePool(m, v, slots=4, cache_len=32,
                          mesh=make_mesh({"data": 2}),
                          prefix_cache=True)
    seq = np.arange(12, dtype=np.int32) % 8
    s0 = pool.lease()  # slot 0 -> shard 0
    pool.write_prefill(s0, _fake_prefill(pool, 12, seed=5), 12)
    pool.prefix_insert(s0, seq)
    s1, s2 = pool.lease(), pool.lease()  # slot 2 -> shard 1
    hit = pool.prefix_lookup(seq, bucket_fn=lambda n: n, slot=s2)
    assert hit is not None
    entry, keep = hit
    assert pool.map_prefix(s2, entry, keep) is True
    n = -(-keep // pool.page_size)
    snap = pool.snapshot()
    mapped = snap["page_table"][s2][:n]
    lo = pool._pages_per_shard
    assert all(lo <= pg < 2 * lo for pg in mapped), mapped
    assert pool.prefix_shard_copies == n
    for name, (pk, pv, _pt) in pool.buffers.items():
        for i, pg in enumerate(mapped):
            src = entry.pages[i]
            np.testing.assert_array_equal(
                np.asarray(pk[pg], np.float32),
                np.asarray(pk[src], np.float32), err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(pv[pg], np.float32),
                np.asarray(pv[src], np.float32), err_msg=name)
    # localized copies are private (refcount 1), the entry's pages
    # keep only their original references
    assert all(snap["refcounts"][pg] == 1 for pg in mapped)
    for s in (s2, s1, s0):
        pool.free(s)
    assert pool.pages_free == pool.pages_allocatable - _entry_pages(pool)


# -- engine parity: paged == dense == generate() ---------------------------


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_paged_parity_ragged_prompts_and_joins(lm):
    """The dense-pool oracle: the SAME raggedy mid-run-join soak the
    dense engine pins against ``generate()``, through the paged pool —
    token streams byte-identical, compile pins intact, and the drained
    pool page-leak-free."""
    m, v, ids = lm
    lengths = [4, 1, 12, 7, 8, 3, 10, 2, 5, 9]
    prompts = [np.asarray(ids[0, :n]) for n in lengths]
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=16,
                         paged=True)
    assert engine.pool.page_size == 8
    rids, results = [], {}
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        for i, p in enumerate(prompts):
            rids.append(engine.submit(p, max_new_tokens=4))
            if i % 2:
                results.update({r.id: r for r in engine.step()})
        results.update(engine.run())
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 4),
            err_msg=f"request={rid}")
    assert engine.decode_compile_count <= engine.num_decode_blocks
    assert engine.prefill_compile_count <= engine.num_prefill_buckets
    # every retired request returned its pages
    assert engine.pool.pages_free == engine.pool.pages_allocatable
    d = engine.metrics.to_dict()
    assert d["page_size"] == 8 and d["pages_total"] > 0
    assert d["page_utilization"] == 0.0  # drained


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_mid_block_eos_paged(lm):
    """A request dying mid-block releases its pages and matches
    ``generate()`` with the same eos_id byte for byte."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :3])
    free_run = ref_tokens(m, v, prompt, 12)
    eos = int(free_run[len(prompt) + 2])
    want = ref_tokens(m, v, prompt, 12, eos_id=eos)
    stop = len(prompt) + int(np.argmax(want[len(prompt):] == eos))
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=4,
                         decode_block=8, paged=True)
    rid = engine.submit(prompt, max_new_tokens=12, eos_id=eos)
    results = engine.run()
    np.testing.assert_array_equal(
        np.asarray(results[rid].tokens), want[:stop + 1])
    assert engine.pool.pages_free == engine.pool.pages_allocatable


# -- prefix cache + copy-on-extend -----------------------------------------


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_prefix_cache_hit_and_copy_on_extend(lm):
    """Two prompts sharing a 10-token prefix: the second prefills only
    the remainder off the cached pages, copy-on-extends the shared
    partial page when its own writes land, and still matches
    ``generate()`` byte for byte — as does a later exact re-ask of the
    first prompt, proving the cached entry survived the divergence
    untouched."""
    m, v, ids = lm
    a = np.asarray(ids[0, :12])
    b = np.concatenate([a[:10], (a[10:12] + 1) % 8]).astype(np.int32)
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=4,
                         decode_block=4, paged=True, prefix_cache=True)
    ra = engine.submit(a, max_new_tokens=6)
    results = engine.run()
    assert engine.pool.prefix_hits == 0  # first ask is the miss
    rb = engine.submit(b, max_new_tokens=6)
    results.update(engine.run())
    ra2 = engine.submit(a, max_new_tokens=6)
    results.update(engine.run())
    np.testing.assert_array_equal(
        np.asarray(results[ra].tokens), ref_tokens(m, v, a, 6))
    np.testing.assert_array_equal(
        np.asarray(results[rb].tokens), ref_tokens(m, v, b, 6))
    np.testing.assert_array_equal(
        np.asarray(results[ra2].tokens), ref_tokens(m, v, a, 6))
    stats = engine.pool.paging_stats()
    assert stats["prefix_cache_hits_total"] == 2
    assert stats["cow_copies_total"] >= 1  # b's writes entered page 1
    assert stats["prefix_tokens_saved_total"] >= 10
    assert stats["prefix_cache_entries"] >= 1
    # the resume program compiled at most once per remainder bucket
    assert engine.resume_compile_count <= engine.num_prefill_buckets


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_prefix_shared_header_prefills_once(lm):
    """A batch of prompts sharing one header: prefill work lands once
    per UNIQUE prefix — every later admit is a hit (> 0 hit rate) and
    every stream still matches ``generate()``."""
    m, v, ids = lm
    header = np.asarray(ids[0, :9])
    tails = [np.asarray(ids[0, 9:9 + n]) for n in (1, 2, 3, 1)]
    prompts = [np.concatenate([header, (t + i) % 8]).astype(np.int32)
               for i, t in enumerate(tails)]
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=4, paged=True, prefix_cache=True)
    rids = [engine.submit(p, max_new_tokens=4) for p in prompts]
    results = engine.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 4),
            err_msg=f"request={rid}")
    assert engine.pool.prefix_hits >= len(prompts) - 1
    assert engine.metrics.to_dict()["prefix_cache_hits_total"] >= 3


# -- page pressure: the PR 7 degradation ladder ----------------------------


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_page_pressure_degrades_and_still_completes(lm):
    """A page budget too small for the offered concurrency: allocator
    exhaustion surfaces as RESOURCE_EXHAUSTED inside the engine's fault
    envelope and walks the existing ladder (shrink blocks, preempt,
    tighten admission) — every request still completes with
    ``generate()``-exact tokens, and the drained pool leaks nothing."""
    m, v, ids = lm
    prompts = [np.asarray(ids[0, :n]) for n in (8, 7, 6, 5)]
    # each request spans ceil((8 + 8) / 8) = 2 pages; 3 allocatable
    # pages cannot hold two tenants at once
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=4, paged=True, num_pages=4,
                         retry_backoff_s=0.0)
    rids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    results = engine.run()
    for rid, p in zip(rids, prompts):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 8),
            err_msg=f"request={rid}")
    d = engine.metrics.to_dict()
    assert d["preemptions_total"] + d["degraded_mode"] >= 1
    assert engine.pool.pages_free == engine.pool.pages_allocatable


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_quarantine_returns_pages(lm):
    """Leak-on-quarantine guard: a poisoned request retires as 'failed'
    and its pages go back on the free list like any other retirement."""
    m, v, ids = lm
    inj = FaultInjector([Fault("serve.prefill", "poison", request=0)])
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=4,
                         paged=True, faults=inj, retry_backoff_s=0.0)
    prompts = [np.asarray(ids[0, :n]) for n in (6, 4, 9)]
    rids = [engine.submit(p, max_new_tokens=4) for p in prompts]
    results = engine.run()
    assert results[rids[0]].status == "failed"
    assert engine.metrics.quarantined_total == 1
    for rid, p in zip(rids[1:], prompts[1:]):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, 4))
    assert engine.pool.pages_free == engine.pool.pages_allocatable
    assert sum(engine.pool.snapshot()["refcounts"]) == 0


# -- snapshot / restore ----------------------------------------------------


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_snapshot_restore_roundtrip_paged(lm):
    """Mid-run checkpoint of a paged + prefix-cache engine: the paging
    plane rides in the snapshot and is internally consistent (refcount
    totals equal mapped-page references), and a restored engine
    finishes every request bit-identically to ``generate()``."""
    m, v, ids = lm
    prompts = [np.asarray(ids[0, :n]) for n in (9, 4, 11)]
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=2, paged=True, prefix_cache=True)
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(2):
        engine.step()
    snap = engine.snapshot()
    pg = snap["paging"]
    assert pg["page_size"] == 8
    refs = sum(pg["npages"]) + sum(
        len(e["pages"]) for e in pg["prefix_entries"])
    assert sum(pg["refcounts"]) == refs
    import json

    json.dumps(snap)  # the checkpoint must stay JSON-able
    rebuilt = ServeEngine.restore(snap, m, v, slots=2, decode_block=2,
                                  paged=True, prefix_cache=True)
    results = rebuilt.run()
    by_id = {r: res for r, res in results.items()}
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            np.asarray(by_id[rid].tokens), ref_tokens(m, v, p, 6),
            err_msg=f"request={rid}")
    # drained up to the pages the prefix entries deliberately pin
    assert (rebuilt.pool.pages_free
            == rebuilt.pool.pages_allocatable - _entry_pages(rebuilt.pool))


# -- 2x2 mesh soak ---------------------------------------------------------


@pytest.mark.slow  # ci.sh's paged gate runs the full file unfiltered
def test_mesh_soak_paged_matches_dense_2x2(lm):
    """The sharded oracle: dense and paged engines on the SAME 2x2
    (data, model) mesh, same raggedy shared-prefix traffic with mid-run
    joins — token streams identical request for request, compile pins
    intact on the paged engine, prefix hits landing, and the
    workload-sized page budget strictly undercutting the dense pool's
    per-device bytes."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    header = row[:9]
    prompts = [row[:4], np.concatenate([header, row[9:10]]), row[:2],
               np.concatenate([header, (row[9:11] + 1) % 8]), row[:6]]
    prompts = [np.asarray(p, np.int32) for p in prompts]
    budgets = [6, 5, 4, 6, 5]

    def drive(**kw):
        engine = ServeEngine(m, v, slots=4, cache_len=32, max_queue=8,
                             decode_block=4, mesh="data=2,model=2", **kw)
        results, rids = {}, []
        with serve_compile_guard(engine, min_decode=1, min_prefill=1):
            for p, n in zip(prompts[:3], budgets[:3]):
                rids.append(engine.submit(p, max_new_tokens=n))
            for _ in range(2):
                results.update({r.id: r for r in engine.step()})
            for p, n in zip(prompts[3:], budgets[3:]):  # mid-run joins
                rids.append(engine.submit(p, max_new_tokens=n))
            while engine.busy:
                results.update({r.id: r for r in engine.step()})
        return engine, rids, results

    dense_eng, dense_rids, dense_res = drive()
    # budget sized to the workload (each request spans <= 2 pages of 8
    # across prompt+budget <= 16 positions), NOT the dense worst case
    paged_eng, paged_rids, paged_res = drive(
        paged=True, num_pages=14, prefix_cache=True)
    for dr, pr in zip(dense_rids, paged_rids):
        np.testing.assert_array_equal(
            np.asarray(paged_res[pr].tokens),
            np.asarray(dense_res[dr].tokens),
            err_msg=f"request={pr}")
    assert paged_eng.decode_compile_count <= paged_eng.num_decode_blocks
    assert paged_eng.prefill_compile_count <= paged_eng.num_prefill_buckets
    assert paged_eng.pool.prefix_hits >= 1
    assert (paged_eng.pool.device_bytes_per_device()
            < dense_eng.pool.device_bytes_per_device())
    # drained up to the pages the prefix entries deliberately pin
    assert (paged_eng.pool.pages_free
            == paged_eng.pool.pages_allocatable
            - _entry_pages(paged_eng.pool))
