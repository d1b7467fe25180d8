"""The serving engine's KV pool and its writes (mmlspark_tpu.serve.cache_pool).

The contract under test (docs/SERVING.md): a slot-based KV-cache pool
with exact lease/free accounting, ONE jitted, donated write an
admission, bit for bit what the eager write left, and head-major
entries (ops/kv_cache.py) that serve ``generate()``'s tokens.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.ops.kv_cache import (
    KV_SCALE_MARGIN,
    HeadMajorKV,
    kv_head_scales,
)
from mmlspark_tpu.serve import ServeEngine, SlotCachePool
from mmlspark_tpu.testing.compile_guard import jit_cache_size
from tests.serve_helpers import (
    TINY,
    init_lm,
    ref_tokens,
    tiny_lm,
    trained_lm,
)


# -- slot pool -------------------------------------------------------------


def test_slot_pool_lease_free_accounting():
    m = tiny_lm()
    v = init_lm(m)
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
    m = tiny_lm()
    v = init_lm(m)
    with pytest.raises(FriendlyError, match="slots"):
        SlotCachePool(m, v, slots=0, cache_len=16)
    with pytest.raises(FriendlyError, match="cache_len"):
        SlotCachePool(m, v, slots=2, cache_len=1)


# -- the pool's one jitted write -------------------------------------------


def _random_pool(kv_dtype, slots, cache_len, seed=0, **model):
    """A pool whose every array holds seeded noise, so a row the write
    must leave alone is told from one it never touched, with all but
    one slot leased."""
    m = tiny_lm(max_len=64, **model)
    v = init_lm(m)
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
    m = tiny_lm(d_model=48, heads=3, max_len=64)
    v = init_lm(m)
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
    m = tiny_lm(d_model=48, heads=3, max_len=64)
    v = init_lm(m)
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


# -- head-major entries, end to end ----------------------------------------

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
    Groups under 8 take several KV heads a grid step, a group of 8 one.

    Prompts of two lengths, one a prefill bucket (five lengths before,
    over the same two buckets), retire and re-lease the same, and the
    reference compiles a program a length; the engine still sees five
    different (prompt, budget) pairs."""
    cfg = {**TINY, **config}
    m, v, ids = trained_lm(**config)
    prompts = [np.asarray(ids[0, o:o + n])
               for o, n in ((0, 4), (1, 9), (2, 4), (3, 9), (1, 4))]
    budgets = (8, 5, 9, 6, 8)
    want = [ref_tokens(m, v, p, n) for p, n in zip(prompts, budgets)]
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
    m = tiny_lm(d_model=hk * d, heads=hk)
    v = init_lm(m)
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
