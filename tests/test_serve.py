"""Continuous-batching serving engine (mmlspark_tpu.serve).

The contract under test (docs/SERVING.md): a slot-based KV-cache pool
with exact lease/free accounting, an engine whose staggered multi-tenant
decode emits BYTE-IDENTICAL tokens to single-request ``generate()``
while compiling the fused decode step exactly once, deterministic
tick-based deadlines, and typed admission-control errors.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.metrics_contracts import MetricData
from mmlspark_tpu.models import build_model, generate
from mmlspark_tpu.models.generate import HeadMajorKV
from mmlspark_tpu.serve import ServeEngine, SlotCachePool
from mmlspark_tpu.serve.cache_pool import KV_SCALE_MARGIN, kv_head_scales
from mmlspark_tpu.testing.compile_guard import (
    compile_guard,
    jit_cache_size,
    serve_compile_guard,
)

PERIOD = 4


def _train_lm(m, steps=30, seq=16):
    from mmlspark_tpu.testing.datagen import overfit_periodic_lm

    return overfit_periodic_lm(m, steps=steps, seq=seq, period=PERIOD)


def _tiny(**kw):
    cfg = dict(vocab_size=8, d_model=32, heads=2, depth=2, max_len=32)
    cfg.update(kw)
    return build_model("transformer_lm", **cfg)


# -- slot pool -------------------------------------------------------------


def test_slot_pool_lease_free_accounting():
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pool = SlotCachePool(m, v, slots=3, cache_len=16)
    assert pool.free_count == 3 and pool.leased_count == 0
    assert pool.utilization == 0.0

    a, b, c = pool.lease(), pool.lease(), pool.lease()
    assert sorted((a, b, c)) == [0, 1, 2]
    assert pool.free_count == 0 and pool.utilization == 1.0
    with pytest.raises(FriendlyError, match="no free KV-cache slots"):
        pool.lease()

    pool.free(b)
    assert pool.free_count == 1 and pool.leased_count == 2
    with pytest.raises(FriendlyError, match="not leased"):
        pool.free(b)  # double free
    assert pool.lease() == b  # the freed slot is reusable

    # buffer geometry: one (K, V) pair per cache-accepting block,
    # slot-major and, in bf16 on one device, head-major within a slot
    for entry in pool.buffers.values():
        ck, cv = entry
        assert isinstance(entry, HeadMajorKV)
        assert ck.shape == (3, 2, 16, 16) and ck.dtype == jnp.bfloat16
        assert cv.shape == ck.shape


def test_slot_pool_guards():
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(FriendlyError, match="slots"):
        SlotCachePool(m, v, slots=0, cache_len=16)
    with pytest.raises(FriendlyError, match="cache_len"):
        SlotCachePool(m, v, slots=2, cache_len=1)


# -- the pool's one jitted write -------------------------------------------


def _random_pool(kv_dtype, slots, cache_len, seed=0, **model):
    """A pool whose every array holds seeded noise, so a row the write
    must leave alone is told from one it never touched, with all but
    one slot leased."""
    m = _tiny(max_len=64, **model)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pool = SlotCachePool(m, v, slots=slots, cache_len=cache_len,
                         kv_dtype=kv_dtype)
    rng = np.random.default_rng(seed)
    noisy = {}
    for name, entry in pool.buffers.items():
        kv = [rng.integers(-127, 128, size=a.shape) for a in entry[:2]]
        scales = [rng.uniform(0.5, 2.0, size=a.shape) for a in entry[2:]]
        noisy[name] = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(entry),
            [jnp.asarray(x, a.dtype) for x, a in zip(kv + scales, entry)],
        )
    pool.buffers = noisy
    for _ in range(slots - 1):
        pool.lease()
    return pool


def _source_cache(pool, rows, length, dtype, seed=1, heads=None):
    """A batch-1 LINEAR prefill cache of ``rows`` rows whose rows from
    ``length`` on hold a sentinel no prompt row comes near. ``heads``
    is the model's ``(hk, d)`` where the pool's entry does not show it
    (a packed one)."""
    rng = np.random.default_rng(seed)
    cache = {}
    for name, entry in pool.buffers.items():
        k = entry[0]
        hk_d = heads or ((k.shape[1], k.shape[3])
                         if isinstance(entry, HeadMajorKV) else k.shape[2:])
        pair = []
        for _ in range(2):
            x = rng.normal(size=(1, rows) + tuple(hk_d)) * 3.0
            x[0, length:] = 1e4
            pair.append(jnp.asarray(x, dtype))
        cache[name] = tuple(pair)
    return cache


def _host(tree):
    return jax.tree_util.tree_map(np.array, tree)


@pytest.mark.parametrize(
    "kv_dtype,src_dtype,rows,slot,start,length,d_model",
    [
        ("bf16", jnp.bfloat16, 16, 1, 0, 11, 32),
        ("bf16", jnp.bfloat16, 16, 2, 4, 13, 32),
        ("bf16", jnp.float32, 8, 2, 0, 5, 32),
        # the chunked fill's carry: as many rows as the pool
        ("bf16", jnp.bfloat16, 24, 1, 7, 19, 32),
        ("bf16", jnp.bfloat16, 16, 0, 0, 16, 32),
        # heads of 64: two side by side in a row of 128 lanes
        ("bf16", jnp.bfloat16, 16, 2, 4, 13, 256),
        ("int8", jnp.bfloat16, 16, 1, 0, 11, 32),
        ("int8", jnp.float32, 24, 2, 0, 24, 32),
    ],
    ids=["bf16", "bf16-resume", "bf16-cast", "bf16-carry", "bf16-full",
         "bf16-packed", "int8", "int8-carry"],
)
def test_write_prefill_matches_the_eager_write_bit_for_bit(
        kv_dtype, src_dtype, rows, slot, start, length, d_model):
    """The jitted, donated write against a NumPy oracle of the eager
    one it replaced: rows ``[start, length)`` of one slot change and
    nothing else does. The bf16 pool's rows lie head-major, so the
    oracle writes them transposed, adjacent heads side by side where
    the pool packs them."""
    heads = 4 if d_model == 256 else 2
    pool = _random_pool(kv_dtype, slots=4, cache_len=24, d_model=d_model,
                        heads=heads)
    cache = _source_cache(pool, rows, length, src_dtype,
                          heads=(heads, d_model // heads))
    want = _host(pool.buffers)
    want_pos, want_live = _host((pool.positions, pool.live))
    for name, entry in want.items():
        for i, c in enumerate(cache[name]):
            values = np.asarray(c)[0, start:length]
            if kv_dtype == "int8":
                scale = np.asarray(kv_head_scales(c[0, :length],
                                                  axes=(0, 2)))
                f32 = values.astype(np.float32)
                amax = np.abs(f32).max(axis=(0, 2))
                np.testing.assert_array_equal(
                    scale, amax * np.float32(KV_SCALE_MARGIN / 127.0))
                entry[2 + i][slot] = scale
                values = np.clip(np.round(f32 / scale[:, None]),
                                 -127, 127)
            values = values.astype(entry[i].dtype)
            if kv_dtype == "bf16":
                assert isinstance(pool.buffers[name], HeadMajorKV)
                packed = entry[i].shape[1], entry[i].shape[3]
                assert packed == ((2, 128) if d_model == 256
                                  else (heads, d_model // heads))
                entry[i][slot, :, start:length] = np.moveaxis(
                    values.reshape(len(values), *packed), 0, 1)
            else:
                entry[i][slot, start:length] = values
    want_pos[slot], want_live[slot] = length, True

    dispatches, nbytes = pool.write_prefill(slot, cache, length,
                                            start=start)

    assert dispatches == 1
    width = 1 if kv_dtype == "int8" else 2
    assert nbytes == len(want) * 2 * (length - start) * d_model * width
    got = _host(pool.buffers)
    for name, entry in want.items():
        assert len(got[name]) == len(entry)
        for g, w in zip(got[name], entry):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(np.asarray(pool.positions), want_pos)
    np.testing.assert_array_equal(np.asarray(pool.live), want_live)
    # the source was not donated: a chunked fill keeps it as its carry
    for pair in cache.values():
        for c in pair:
            assert not c.is_deleted()
            assert float(np.asarray(c, np.float32)[0, -1, 0, 0]) != 0.0


def test_write_prefill_refusals_leave_the_pool_untouched():
    """What the write refuses it refuses before the donation."""
    pool = _random_pool("int8", slots=2, cache_len=24)
    before = _host(pool.buffers)
    cache = _source_cache(pool, 8, 8, jnp.bfloat16)
    for args, match in (((1, cache, 6), "not leased"),
                        ((0, cache, 25), "exceeds"),
                        ((0, cache, 6, 6), "must lie in"),
                        ((0, cache, 6, 2), "start=0"),
                        ((0, cache, 12), "fewer than")):
        with pytest.raises(FriendlyError, match=match):
            pool.write_prefill(*args)
    for name, entry in _host(pool.buffers).items():
        for g, w in zip(entry, before[name]):
            np.testing.assert_array_equal(g, w)


def test_write_prefill_compiles_one_program_a_source_shape():
    """``slot``, ``start`` and ``length`` are data: five lengths into
    four slots from one source shape are ONE program, a second source
    shape one more. The geometry is this test's own, so nothing an
    earlier test compiled can stand in for either."""
    m = _tiny(d_model=48, heads=3, max_len=64)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pool = SlotCachePool(m, v, slots=4, cache_len=40)
    for _ in range(4):
        pool.lease()
    seen = jit_cache_size(pool._write)
    writes = ((0, 3, 0), (1, 16, 0), (2, 9, 2), (3, 12, 0), (1, 5, 4))
    for slot, length, start in writes:
        pool.write_prefill(slot, _source_cache(pool, 16, length,
                                               jnp.bfloat16),
                           length, start=start)
    assert jit_cache_size(pool._write) - seen == 1
    for slot, length, start in writes:
        pool.write_prefill(slot, _source_cache(pool, 32, length,
                                               jnp.bfloat16),
                           length, start=start)
    assert jit_cache_size(pool._write) - seen == 2
    assert np.asarray(pool.positions).tolist() == [3, 5, 9, 12]


def test_pool_write_compiles_once_a_prefill_bucket():
    """On a request's own timeline: of the ``serve.pool_write`` regions
    of one prefill bucket only the first may report a compile, whatever
    the prompts' lengths."""
    m = _tiny(d_model=48, heads=3, max_len=64)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = ServeEngine(m, v, slots=3, cache_len=48)
    rng = np.random.default_rng(0)
    lengths = (9, 13, 16, 11, 20, 31, 10, 27, 17)
    for n in lengths:
        engine.submit(rng.integers(0, 8, size=n).astype(np.int32),
                      max_new_tokens=3)
    results = engine.run()
    assert all(r.status == "completed" for r in results.values())
    events = engine.recorder.events()
    bucket_of = {e["attrs"]["request"]: e["attrs"]["bucket"]
                 for e in events if e["name"] == "serve.prefill"}
    writes: dict = {}
    for e in events:
        if e["name"] == "serve.pool_write":
            writes.setdefault(bucket_of[e["attrs"]["request"]], []).append(
                e["attrs"].get("compiles", 0))
    assert {b: len(c) for b, c in writes.items()} == {16: 5, 32: 4}
    for compiles in writes.values():
        assert compiles[0] == 1 and not any(compiles[1:]), writes


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
    m = _tiny(**config)
    v, ids = _train_lm(m)
    prompts = [np.asarray(ids[0, :n]) for n in (4, 6, 7)]
    want = {
        i: np.asarray(generate(m, v, p[None], max_new_tokens=8))[0]
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
    m = _tiny()
    v, ids = _train_lm(m)
    prompts = [np.asarray(ids[0, :n]) for n in (4, 5, 6, 8)]
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=4)
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
    results = engine.run()
    for rid, p in zip(rids, prompts):
        want = np.asarray(generate(m, v, p[None], max_new_tokens=6))[0]
        np.testing.assert_array_equal(np.asarray(results[rid].tokens), want)
    # distinct XLA programs, one per ladder block size actually run —
    # never one per token or per scan iteration
    assert 1 <= engine.decode_compile_count <= engine.num_decode_blocks


def test_eos_retires_early():
    m = _tiny()
    v, ids = _train_lm(m)
    prompt = np.asarray(ids[0, :4])
    ref = np.asarray(generate(m, v, prompt[None], max_new_tokens=8))[0]
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
    m = _tiny()
    v, ids = _train_lm(m, steps=5)
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
    m = _tiny()
    v, ids = _train_lm(m, steps=5)
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
    m = _tiny()
    v, ids = _train_lm(m, steps=5)
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=4,
                         decode_block=1)
    prompt_b = np.asarray(ids[0, :5])
    ref_b = generate(m, v, prompt_b[None], 10)[0]
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
    m = _tiny()
    v, ids = _train_lm(m, steps=5)
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=4,
                         decode_block=1)
    prompt_b = np.asarray(ids[0, :5])
    ref_b = generate(m, v, prompt_b[None], 6)[0]
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
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = ServeEngine(m, v, slots=1, cache_len=32, max_queue=2)
    engine.submit(np.ones(4, np.int32), max_new_tokens=2)
    engine.submit(np.ones(4, np.int32), max_new_tokens=2)
    with pytest.raises(FriendlyError, match="queue is full"):
        engine.submit(np.ones(4, np.int32), max_new_tokens=2)
    assert engine.metrics.rejected == 1
    assert engine.metrics.submitted == 2


def test_submit_validation():
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
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


@pytest.mark.parametrize("config,packed", [
    ({}, 1),                                            # MHA: a group of 1
    ({"heads": 4, "kv_heads": 2}, 1),                   # GQA, a group of 2
    ({"d_model": 64, "heads": 8, "kv_heads": 1}, 1),    # a group of 8
    ({"d_model": 128, "heads": 2}, 2),                  # MHA, heads of 64
    ({"d_model": 256, "heads": 4, "kv_heads": 2}, 2),   # GQA, heads of 64
    ({"model": "transformer_lm_moe", "n_experts": 2}, 1),
], ids=["mha", "gqa2", "group8", "mha-packed", "gqa2-packed", "moe"])
def test_head_major_pool_serves_generates_tokens(config, packed):
    """The one-device bf16 pool keeps its rows head-major (heads of 64
    two to a row of 128 lanes) and the decode step writes and reads them
    where they lie: five requests over two slots, so slots retire and
    are leased again mid-run, give ``generate()``'s tokens one for one.
    Groups under 8 take several KV heads a grid step, a group of 8 one."""
    config = dict(config)
    name = config.pop("model", "transformer_lm")
    cfg = dict(vocab_size=8, d_model=32, heads=2, depth=2, max_len=32)
    cfg.update(config)
    m = build_model(name, **cfg)
    v, ids = _train_lm(m)
    prompts = [np.asarray(ids[0, :n]) for n in (4, 9, 6, 3, 7)]
    budgets = (8, 5, 9, 6, 8)
    want = [np.asarray(generate(m, v, p[None], max_new_tokens=n))[0]
            for p, n in zip(prompts, budgets)]
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4)
    hk = cfg.get("kv_heads") or cfg["heads"]
    d = cfg["d_model"] // cfg["heads"]
    for entry in engine.pool.buffers.values():
        assert isinstance(entry, HeadMajorKV)
        assert entry.k.shape == entry.v.shape == (2, hk // packed, 32,
                                                  packed * d)
    rids = [engine.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    results = engine.run()
    for rid, w in zip(rids, want):
        assert results[rid].status == "completed"
        np.testing.assert_array_equal(np.asarray(results[rid].tokens), w)
    leases = [e for e in engine.recorder.events()
              if e["name"] == "serve.pool_write"]
    assert len(leases) == 5 > engine.pool.num_slots
    for e in leases:
        assert e["attrs"]["bytes_full"] == e["attrs"]["bytes"] > 0


@pytest.mark.parametrize("holder", ["bf16", "bf16-heads-of-64", "int8",
                                    "paged", "mesh"])
def test_the_pools_layout_is_its_holders(holder):
    """Which layout a block's rows have is the pool's to decide, by what
    holds them: bf16 on one device lies head-major (heads of 64 two to
    a row), and every byte a prefill writes is counted as ``bytes_full``;
    int8 rows, pages and a pool under a mesh keep the layouts they had
    and count none."""
    options = {
        "int8": {"kv_dtype": "int8"},
        "paged": {"paged": True, "page_size": 8},
        "mesh": {"mesh": {"data": 2, "model": 2}},
    }.get(holder, {})
    if holder == "mesh" and jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    hk, d = (2, 64) if holder == "bf16-heads-of-64" else (2, 16)
    m = _tiny(d_model=hk * d, heads=hk)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = ServeEngine(m, v, slots=2, cache_len=32, **options)
    rng = np.random.default_rng(0)
    for n in (5, 9, 3):
        engine.submit(rng.integers(0, 8, size=n).astype(np.int32),
                      max_new_tokens=4)
    assert all(r.status == "completed" for r in engine.run().values())
    writes = [e["attrs"] for e in engine.recorder.events()
              if e["name"] == "serve.pool_write"]
    assert len(writes) == 3
    shapes = {
        "bf16": (2, 2, 32, 16), "bf16-heads-of-64": (2, 1, 32, 128),
        "int8": (2, 32, 2, 16), "mesh": (2, 32, 2, 16),
        "paged": (engine.pool.buffers["block0"][0].shape[0], 2, 8, 16),
    }
    for entry in engine.pool.buffers.values():
        assert entry[0].shape == entry[1].shape == shapes[holder]
        assert isinstance(entry, HeadMajorKV) == holder.startswith("bf16")
        assert len(entry) == {"int8": 4, "paged": 3}.get(holder, 2)
    if holder.startswith("bf16"):
        assert engine.pool.kinds == {"block0": "full", "block1": "full"}
        assert all(w["bytes_full"] == w["bytes"] > 0 for w in writes)
        assert all(w["bytes_ring"] == 0 for w in writes)
    else:
        assert not getattr(engine.pool, "kinds", None)
        assert not any("bytes_full" in w for w in writes)
    if holder == "mesh":
        from jax.sharding import PartitionSpec as P

        for entry in engine.pool.buffers.values():
            assert entry[0].sharding.spec == P("data", None, "model", None)


def test_engine_build_guards():
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # learned position table bounds cache_len
    with pytest.raises(FriendlyError, match="position table"):
        ServeEngine(m, v, cache_len=64)
    # sliding-window models roll their cache; the linear slot pool
    # refuses rather than silently mis-serving long requests
    mw = _tiny(window=6)
    vw = mw.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(FriendlyError, match="window"):
        ServeEngine(mw, vw, cache_len=32)
    ServeEngine(mw, vw, cache_len=6)  # cache_len <= window is fine


# -- metrics ---------------------------------------------------------------


def test_metrics_dict_and_snapshot():
    m = _tiny()
    v, ids = _train_lm(m, steps=5)
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


# -- compile-count invariants (bucketed prefill + fused decode) -------------


def test_mixed_length_soak_pins_compile_counts():
    """Soak with mixed-length joiners: every distinct prompt length in
    [1, 12] flows through 2 slots. The fused decode step must compile
    exactly once and bucketed prefill at most once per power-of-two
    bucket — NOT once per distinct length — while every request still
    matches single-request ``generate()`` byte for byte."""
    m = _tiny()
    v, ids = _train_lm(m)
    lengths = [4, 1, 12, 7, 8, 3, 10, 2, 5, 9]  # raggedy on purpose
    prompts = [np.asarray(ids[0, :n]) for n in lengths]
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=16)
    assert engine.num_prefill_buckets == 3  # 8, 16, 32
    rids = []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        results = {}
        for i, p in enumerate(prompts):  # two joiners per tick
            rids.append(engine.submit(p, max_new_tokens=4))
            if i % 2:
                results.update({r.id: r for r in engine.step()})
        results.update(engine.run())
    for rid, p in zip(rids, prompts):
        want = np.asarray(generate(m, v, p[None], max_new_tokens=4))[0]
        np.testing.assert_array_equal(np.asarray(results[rid].tokens), want)
    # the 10 distinct lengths landed in at most 2 buckets (8 and 16):
    # far fewer programs than the per-length prefill would have traced
    assert engine.prefill_compile_count <= 2
    buckets = engine.metrics.prefill_buckets
    assert set(buckets) <= {"8", "16"}
    assert sum(buckets.values()) == len(prompts)
    # length-aware decode touched strictly less KV than a dense read
    d = engine.metrics.to_dict()
    assert 0.0 < d["decode_flop_utilization"] < 1.0
    assert d["decode_live_kv_tokens"] < d["decode_dense_kv_tokens"]


# -- regions of the admit path and the tick ----------------------------------

ADMISSION = ("serve.admit_one", "serve.prefill", "serve.prefill_dispatch",
             "serve.pool_write", "serve.first_token", "serve.handoff")
TICK = ("serve.tick", "serve.admit", "serve.decode", "serve.fetch",
        "serve.retire")


@pytest.mark.parametrize("options", [
    {},
    {"async_host": True},
    {"prefill_chunk": 8},
    {"paged": True, "page_size": 8},
], ids=["sync", "async_host", "prefill_chunk", "paged"])
def test_every_admission_and_every_tick_leave_their_regions(options):
    """Whichever option is on, an admitted request leaves exactly one
    ``serve.admit_one`` with one ``serve.pool_write`` and one
    ``serve.first_token`` inside it, at most 8 region events an admission
    and 6 a tick: counts, so nothing here can flake on a timing."""
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = ServeEngine(m, v, slots=2, cache_len=32, **options)
    rng = np.random.default_rng(0)
    rids = [engine.submit(rng.integers(0, 8, size=n).astype(np.int32),
                          max_new_tokens=6) for n in (4, 6, 7, 12, 5)]
    results = engine.run()
    assert all(results[r].status == "completed" for r in rids)

    events = engine.recorder.events()
    regions = [e for e in events if e["name"].startswith("serve.")]
    assert {e["name"] for e in regions} <= set(ADMISSION + TICK)
    assert all(e["attrs"]["ms"] >= 0 and e["attrs"]["t0"] > 0
               for e in regions)
    by_request: dict = {}
    for e in regions:
        if e["name"] in ADMISSION:
            by_request.setdefault(e["attrs"]["request"], []).append(e)
    assert sorted(by_request) == sorted(rids)
    chunks = 0
    for rid, evs in by_request.items():
        names = [e["name"] for e in evs]
        assert names.count("serve.admit_one") == 1, names
        assert names.count("serve.pool_write") == 1
        assert names.count("serve.first_token") == 1
        assert len(evs) <= 8, names
        parent = {e["name"]: e["attrs"]["parent"] for e in evs}
        # the write and the wait lie in the prefill that made them, and
        # that in the request's one admission
        assert parent["serve.pool_write"] == "serve.prefill"
        assert parent["serve.first_token"] == "serve.prefill"
        assert parent["serve.admit_one"] == "serve.admit"
        one = next(e for e in evs if e["name"] == "serve.admit_one")
        inside = [e for e in evs if e is not one
                  and one["attrs"]["t0"] <= e["attrs"]["t0"]
                  and e["t"] <= one["t"]]
        assert {"serve.pool_write", "serve.first_token"} <= {
            e["name"] for e in inside}
        assert one["attrs"]["prompt_len"] in (4, 5, 6, 7, 12)
        assert one["attrs"]["slot"] in (0, 1)
        chunks += names.count("serve.prefill") - 1
    # a chunked fill adds a prefill and its dispatch for every chunk
    # before the last: only the 12-token prompt has one
    assert chunks == (1 if "prefill_chunk" in options else 0)
    per_tick: dict = {}
    for e in regions:
        if e["name"] in TICK:
            per_tick[e["tick"]] = per_tick.get(e["tick"], 0) + 1
    assert len(per_tick) == engine.tick and max(per_tick.values()) <= 6
    # one fetch and one consume for every dispatched block
    count = {n: sum(e["name"] == n for e in regions) for n in TICK}
    assert count["serve.decode"] == count["serve.fetch"] > 0
    assert count["serve.tick"] == count["serve.admit"] == engine.tick
    finished = sum(e["attrs"]["finished"] for e in regions
                   if e["name"] == "serve.retire")
    assert finished == len(rids)
    # what the pool counts: the dense pool's one jitted write; in the
    # paged pool a slice and a scatter for each K and each V array (the
    # prefill cache has the pool's dtype), positions and live, the page
    # and offset vectors, a head index a block and, when the tables
    # changed, one table a block
    writes = [e["attrs"] for e in regions if e["name"] == "serve.pool_write"]
    blocks = len(engine.pool.buffers)
    if "paged" in options:
        assert {w["dispatches"] for w in writes} <= {
            2 + 5 * blocks + 2, 2 + 5 * blocks + blocks + 2}
    else:
        assert {w["dispatches"] for w in writes} == {1}
    row = 2 * 32 * 2          # K and V, d_model 32, bfloat16
    assert sorted(w["bytes"] for w in writes) == sorted(
        blocks * row * n for n in (4, 6, 7, 12, 5))
    # the lifecycle events that readers filter on keep their form
    assert sum(e["name"] == "tick" for e in events) == engine.tick
    assert sum(e["name"] == "prefill" and e.get("span_name") == "request"
               for e in events) == len(rids)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_an_admission_behind_a_block_in_flight_keeps_its_fetch_target(
        kv_dtype):
    """The async host loop admits while its last block is still to be
    fetched, and that block's ``live`` output IS ``pool.live``: the
    pool's write must leave it readable (it donates the K/V buffers
    alone). Arrivals into an engine that is not full are what reaches
    that state: no retirement has rebound ``pool.live`` in between."""
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 8, size=n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    streams = {}
    for async_host in (False, True):
        engine = ServeEngine(m, v, slots=4, cache_len=32, decode_block=2,
                             kv_dtype=kv_dtype, async_host=async_host)
        write, behind = engine.pool._write, []

        def watched(buffers, positions, live, *rest):
            block = engine._inflight
            behind.append(block is not None and block["live"] is live)
            out = write(buffers, positions, live, *rest)
            assert not live.is_deleted() and not positions.is_deleted()
            return out

        engine.pool._write = watched
        rids, results = [], {}
        for prompt in prompts:
            # one arrival a tick, each behind the last one's first block
            rids.append(engine.submit(prompt, max_new_tokens=8))
            results.update((r.id, r) for r in engine.step())
        results.update(engine.run())
        assert all(results[r].status == "completed" for r in rids)
        assert any(behind) == async_host, behind
        streams[async_host] = [results[r].tokens.tolist() for r in rids]
    assert streams[True] == streams[False]


def test_fetch_region_feeds_the_host_sync_account():
    """``serve.fetch``'s own interval is what ``record_host_sync`` gets,
    in both loops: the fetch is timed once."""
    m = _tiny()
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    for options in ({}, {"async_host": True}):
        engine = ServeEngine(m, v, slots=2, cache_len=32, **options)
        engine.submit(np.arange(5, dtype=np.int32) % 8, max_new_tokens=6)
        engine.run()
        fetches = [e["attrs"]["ms"] for e in engine.recorder.events()
                   if e["name"] == "serve.fetch"]
        assert fetches
        assert engine.metrics.host_sync_wait_s == pytest.approx(
            sum(fetches) / 1e3, abs=1e-5 * len(fetches))


def test_compile_guard_raises_on_violation():
    calls = {"n": 0}

    def count():
        return calls["n"]

    with pytest.raises(AssertionError, match="at most"):
        with compile_guard(count, max_programs=0, label="demo"):
            calls["n"] += 1
    with pytest.raises(AssertionError, match="at least"):
        with compile_guard(count, max_programs=3, min_programs=1,
                           label="demo"):
            pass
    with pytest.raises(ValueError, match="max_programs"):
        with compile_guard(count, max_programs=0, min_programs=1):
            pass


# -- soak / CLI (slow tier) ------------------------------------------------


@pytest.mark.slow
def test_demo_soak():
    from mmlspark_tpu.serve.demo import run_demo

    out = run_demo(slots=3, n_requests=10, max_new_tokens=6,
                   arrivals_per_tick=2, cache_len=48, seed=1)
    assert out["completed"] == 10 and out["expired"] == 0
    assert 1 <= out["decode_compiles"] <= out["decode_block"].bit_length()
    assert out["tokens_generated"] == 60


@pytest.mark.slow
def test_cli_serve_demo_emits_one_json_line():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4", "serve",
         "--demo", "--slots", "2", "--requests", "4",
         "--max-new-tokens", "4"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd="/root/repo",
    )
    assert res.returncode == 0, res.stderr
    lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1  # ONE parseable JSON line, mirroring bench
    metrics = json.loads(lines[0])
    for key in ("queue_depth_mean", "ttft_ms_mean", "per_token_ms",
                "slot_utilization_mean", "tokens_per_sec"):
        assert key in metrics, key
    assert metrics["completed"] == 4
    assert 1 <= metrics["decode_compiles"] <= (
        metrics["decode_block"].bit_length()
    )
