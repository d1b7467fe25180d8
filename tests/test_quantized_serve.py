"""Quantized decode hot path (ISSUE 11 tentpole).

The contract under test (docs/PERFORMANCE.md "Quantized decode"):
``kv_dtype="int8"`` swaps the pools' bf16 K/V slabs for int8 stores
plus f32 quantization scales — per-(slot, kv-head) in the dense pool,
per-(page, kv-head) in the paged pool — and the flash-decode kernels
dequantize in-VMEM off the scalar-prefetch channel, so HBM streams
half the bytes while the online-softmax math stays f32. NOTHING the
serving engine guarantees moves: compile-count pins, one host sync per
block, page accounting, prefix-cache copy-on-extend (which must copy
scales WITH pages), and freed leases reset their scale state. The bf16
dense pool stays the accuracy oracle: parity is a token-flip budget,
not bit-identity. Runs on the 8 virtual CPU devices
``tests/conftest.py`` forces.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.ops.flash_attention import flash_decode, paged_flash_decode
from mmlspark_tpu.ops.kv_cache import (
    kv_head_scales,
    quantize_kv,
    validate_kv_dtype,
)
from mmlspark_tpu.ops.quantize import kv_cache_bytes
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.serve.cache_pool import SlotCachePool
from mmlspark_tpu.serve.paging import PagedCachePool
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import init_lm, tiny_lm, trained_lm

#: accepted greedy-stream divergence vs the bf16 oracle at smoke scale:
#: one int8 rounding flip near an argmax tie cascades for the rest of
#: the stream (greedy decode re-feeds its own tokens), so the budget
#: prices the cascade, not per-token error
FLIP_BUDGET = 0.25


@pytest.fixture(scope="module")
def raw_lm():
    """Random-init model — enough for pool/accounting/validation
    tests, which never compare token streams."""
    m = tiny_lm()
    v = init_lm(m)
    return m, v


@pytest.fixture(scope="module")
def lm():
    """Trained model for the parity soaks: confident logits make the
    flip budget meaningful instead of measuring argmax ties."""
    return trained_lm()


def _flip_rate(streams_a: dict, streams_b: dict) -> float:
    flips = total = 0
    for key in streams_a:
        a, b = list(streams_a[key]), list(streams_b[key])
        n = min(len(a), len(b))
        flips += sum(x != y for x, y in zip(a[:n], b[:n]))
        flips += abs(len(a) - len(b))  # early-EOS divergence counts
        total += max(len(a), len(b))
    return flips / max(total, 1)


def _fake_linear_cache(pool, length, seed=0):
    """A synthetic batch-1 linear cache matching ``write_prefill``'s
    input — deterministic values so quantize/dequantize round-trips
    are content-checkable without a model."""
    rng = np.random.default_rng(seed)
    cache = {}
    paged = isinstance(pool, PagedCachePool)
    for name, entry in pool.buffers.items():
        pk = entry[0]
        # paged stores are (num_pages, hk, page_size, d); dense slabs
        # are (slots, cache_len, hk, d)
        hk = pk.shape[1] if paged else pk.shape[2]
        d = pk.shape[3]
        k = rng.normal(size=(1, length, hk, d)).astype(np.float32)
        v = rng.normal(size=(1, length, hk, d)).astype(np.float32)
        cache[name] = (jnp.asarray(k, jnp.bfloat16),
                       jnp.asarray(v, jnp.bfloat16))
    return cache


# -- validation ------------------------------------------------------------


def test_kv_dtype_validation():
    with pytest.raises(FriendlyError, match="kv_dtype"):
        validate_kv_dtype("fp8", {"b0": (2, 16)})
    # int8 packs VREG lanes pairwise: head_dim must be even
    with pytest.raises(FriendlyError, match="even"):
        validate_kv_dtype("int8", {"b0": (2, 15)})
    validate_kv_dtype("int8", {"b0": (2, 16)})  # fine
    validate_kv_dtype("bf16", {"b0": (2, 15)})  # bf16 never restricted


def test_engine_rejects_bad_kv_dtype(raw_lm):
    m, v = raw_lm
    with pytest.raises(FriendlyError, match="kv_dtype"):
        ServeEngine(m, v, slots=2, cache_len=32, kv_dtype="int4")


def test_run_demo_rejects_odd_head_dim():
    """The CLI surface: ``serve --kv-dtype int8`` on a model whose
    head_dim is odd must die with a FriendlyError at build time, not a
    kernel shape error mid-decode."""
    from mmlspark_tpu.serve.demo import run_demo

    with pytest.raises(FriendlyError, match="even"):
        run_demo(slots=2, n_requests=1, max_new_tokens=2, d_model=30,
                 heads=2, cache_len=32, kv_dtype="int8")


# -- kernel parity ---------------------------------------------------------


def test_flash_decode_int8_parity():
    """The dense int8 kernel against the bf16 kernel on identical
    tensors: dequantizing through per-(row, kv-head) scales in-VMEM
    must land within the quantization error budget."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    b, L, h, hk, d = 4, 32, 2, 2, 16
    q = jax.random.normal(keys[0], (b, 1, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, L, hk, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, L, hk, d), jnp.bfloat16)
    lengths = jnp.asarray([32, 17, 8, 1], jnp.int32)
    ks = kv_head_scales(k, axes=(1, 3))  # (b, hk)
    vs = kv_head_scales(v, axes=(1, 3))
    qk = quantize_kv(k, ks[:, None, :])
    qv = quantize_kv(v, vs[:, None, :])
    ref = flash_decode(q, k, v, lengths)
    got = flash_decode(q, qk, qv, lengths, k_scale=ks, v_scale=vs)
    assert got.dtype == ref.dtype
    err = float(jnp.max(jnp.abs(
        ref.astype(jnp.float32) - got.astype(jnp.float32))))
    assert err <= 0.0625, f"int8 dense decode error {err}"


def test_flash_decode_int8_requires_scales():
    b, L, h, d = 2, 16, 2, 16
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    k = jnp.zeros((b, L, h, d), jnp.int8)
    lengths = jnp.full((b,), L, jnp.int32)
    with pytest.raises(ValueError, match="scale"):
        flash_decode(q, k, k, lengths)


def test_paged_flash_decode_int8_parity():
    """The paged int8 kernel against the paged bf16 kernel: page faces
    dequantize through their PER-PAGE scales, scatter layout and page
    indirection identical on both sides."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    b, h, hk, d, ps, max_pages = 3, 2, 2, 16, 8, 4
    L = ps * max_pages
    num_pages = b * max_pages
    q = jax.random.normal(keys[0], (b, 1, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, L, hk, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, L, hk, d), jnp.bfloat16)
    lengths = jnp.asarray([32, 19, 6], jnp.int32)
    # unique physical page per (row, logical page); stores hold the
    # linear cache re-laid-out as (num_pages, hk, page_size, d)
    pt = jnp.arange(num_pages, dtype=jnp.int32).reshape(b, max_pages)
    kp = k.reshape(b, max_pages, ps, hk, d).transpose(0, 1, 3, 2, 4)
    vp = v.reshape(b, max_pages, ps, hk, d).transpose(0, 1, 3, 2, 4)
    kp = kp.reshape(num_pages, hk, ps, d)
    vp = vp.reshape(num_pages, hk, ps, d)
    ks = kv_head_scales(kp, axes=(2, 3))  # (num_pages, hk)
    vs = kv_head_scales(vp, axes=(2, 3))
    qkp = jnp.clip(jnp.round(
        kp.astype(jnp.float32) / ks[:, :, None, None]
    ), -127, 127).astype(jnp.int8)
    qvp = jnp.clip(jnp.round(
        vp.astype(jnp.float32) / vs[:, :, None, None]
    ), -127, 127).astype(jnp.int8)
    ref = paged_flash_decode(q, kp, vp, lengths, pt)
    got = paged_flash_decode(q, qkp, qvp, lengths, pt,
                             k_scale=ks, v_scale=vs)
    assert got.dtype == ref.dtype
    err = float(jnp.max(jnp.abs(
        ref.astype(jnp.float32) - got.astype(jnp.float32))))
    assert err <= 0.0625, f"int8 paged decode error {err}"


# -- pool scale-state lifecycle --------------------------------------------


def test_dense_free_resets_scales(raw_lm):
    """A freed dense lease returns its quantization scales to the 1.0
    init — quarantine/preemption must not leak one tenant's
    calibration into the next."""
    m, v = raw_lm
    pool = SlotCachePool(m, v, slots=2, cache_len=32, kv_dtype="int8")
    cache = _fake_linear_cache(pool, 8)
    slot = pool.lease()
    pool.write_prefill(slot, cache, 8)
    for _k, _v, ks, vs in pool.buffers.values():
        assert not np.allclose(np.asarray(ks[slot]), 1.0)
        assert not np.allclose(np.asarray(vs[slot]), 1.0)
    pool.free(slot)
    for _k, _v, ks, vs in pool.buffers.values():
        np.testing.assert_allclose(np.asarray(ks[slot]), 1.0)
        np.testing.assert_allclose(np.asarray(vs[slot]), 1.0)


def test_paged_free_returns_pages_int8(raw_lm):
    m, v = raw_lm
    pool = PagedCachePool(m, v, slots=2, cache_len=32, kv_dtype="int8")
    assert pool.snapshot()["kv_dtype"] == "int8"
    slot = pool.lease()
    pool.write_prefill(slot, _fake_linear_cache(pool, 12), 12)
    assert pool.pages_free < pool.pages_allocatable
    pool.free(slot)
    assert pool.pages_free == pool.pages_allocatable


def test_gather_prefix_int8_roundtrip(raw_lm):
    """write_prefill quantizes into pages; gather_prefix dequantizes
    back to a linear bf16 cache — the round trip must reproduce the
    source within the per-page quantization budget."""
    m, v = raw_lm
    pool = PagedCachePool(m, v, slots=2, cache_len=32, kv_dtype="int8",
                          prefix_cache=True)
    length = 12  # page 0 full, page 1 partial
    cache = _fake_linear_cache(pool, length, seed=3)
    slot = pool.lease()
    seq = np.arange(length, dtype=np.int32) % 8
    pool.write_prefill(slot, cache, length)
    pool.prefix_insert(slot, seq)
    entry = pool._prefix[seq.tobytes()]
    out = pool.gather_prefix(entry, length)
    for name, (gk, gv) in out.items():
        assert gk.dtype == jnp.bfloat16
        for got, src in ((gk, cache[name][0]), (gv, cache[name][1])):
            np.testing.assert_allclose(
                np.asarray(got[0, :length], np.float32),
                np.asarray(src[0, :length], np.float32),
                atol=0.06, err_msg=f"block={name}",
            )
    pool.free(slot)


def test_copy_on_extend_copies_scales(raw_lm):
    """A CoW-privatized page is only faithful WITH its quantization
    scales: the copy must land the source page's scale rows on the new
    physical page, and a mid-page resume keeps the registered scale
    (the already-written half decodes through it)."""
    m, v = raw_lm
    pool = PagedCachePool(m, v, slots=2, cache_len=32, kv_dtype="int8",
                          prefix_cache=True)
    ps = pool.page_size
    length = ps + 4  # page 1 shared AND partial
    seq = np.arange(length, dtype=np.int32) % 8
    s0 = pool.lease()
    pool.write_prefill(s0, _fake_linear_cache(pool, length, seed=5), length)
    pool.prefix_insert(s0, seq)
    pool.free(s0)
    entry = pool._prefix[seq.tobytes()]
    s1 = pool.lease()
    assert pool.map_prefix(s1, entry, length)
    shared_phys = int(pool._pt_host[s1, 1])
    name0 = next(iter(pool.buffers))
    want_ks = np.asarray(pool.buffers[name0][3][shared_phys])
    # the resume's write frontier enters the shared partial page
    pool.write_prefill(
        s1, _fake_linear_cache(pool, 2 * ps, seed=6), 2 * ps, start=length
    )
    assert pool.cow_copies == 1
    new_phys = int(pool._pt_host[s1, 1])
    assert new_phys != shared_phys
    np.testing.assert_allclose(
        np.asarray(pool.buffers[name0][3][new_phys]), want_ks,
        err_msg="CoW must carry the source page's k-scales",
    )
    # the entry's original page kept ITS scales too
    np.testing.assert_allclose(
        np.asarray(pool.buffers[name0][3][shared_phys]), want_ks)
    pool.free(s1)


# -- accounting ------------------------------------------------------------


def test_kv_cache_bytes_and_metrics(raw_lm):
    """int8 pools report ~half the bf16 baseline (scale leaves cost a
    few percent back) and the engine's metrics carry kv_dtype + the
    smaller per-device figure."""
    m, v = raw_lm
    bf16 = ServeEngine(m, v, slots=2, cache_len=32)
    int8 = ServeEngine(m, v, slots=2, cache_len=32, kv_dtype="int8")
    stored, baseline = kv_cache_bytes(int8.pool.buffers)
    assert stored < baseline
    assert baseline > 1.6 * stored  # ~2x minus the scale-leaf overhead
    d8, d16 = int8.metrics.to_dict(), bf16.metrics.to_dict()
    assert d8["kv_dtype"] == "int8" and d16["kv_dtype"] == "bf16"
    assert (d8["cache_pool_bytes_per_device"]
            < d16["cache_pool_bytes_per_device"])


# -- engine parity vs the bf16 oracle --------------------------------------


def _drive(m, v, prompts, budgets, **kw):
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=16, **kw)
    streams, rids, results = {}, [], {}
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            rids.append(engine.submit(p, max_new_tokens=n))
            if i % 2:
                results.update({r.id: r for r in engine.step()})
        results.update(engine.run())
    for i, rid in enumerate(rids):
        streams[i] = list(np.asarray(results[rid].tokens)[len(prompts[i]):])
    assert engine.decode_compile_count <= engine.num_decode_blocks
    assert engine.prefill_compile_count <= engine.num_prefill_buckets
    return engine, streams


@pytest.mark.slow  # ci.sh's int8 gate runs the full file unfiltered
def test_dense_engine_int8_within_flip_budget(lm):
    m, v, ids = lm
    lengths = [4, 1, 12, 7, 8, 3]
    prompts = [np.asarray(ids[0, :n]) for n in lengths]
    budgets = [6] * len(prompts)
    _, oracle = _drive(m, v, prompts, budgets)
    eng, got = _drive(m, v, prompts, budgets, kv_dtype="int8")
    rate = _flip_rate(oracle, got)
    assert rate <= FLIP_BUDGET, f"dense int8 flip rate {rate}"
    # drained engine returned every slot, scales reset with them
    for _k, _v, ks, vs in eng.pool.buffers.values():
        np.testing.assert_allclose(np.asarray(ks), 1.0)


@pytest.mark.slow  # ci.sh's int8 gate runs the full file unfiltered
def test_paged_engine_int8_within_flip_budget(lm):
    m, v, ids = lm
    lengths = [4, 9, 2, 12, 6, 3]
    prompts = [np.asarray(ids[0, :n]) for n in lengths]
    budgets = [5] * len(prompts)
    _, oracle = _drive(m, v, prompts, budgets)
    eng, got = _drive(m, v, prompts, budgets, kv_dtype="int8",
                      paged=True)
    rate = _flip_rate(oracle, got)
    assert rate <= FLIP_BUDGET, f"paged int8 flip rate {rate}"
    assert eng.pool.pages_free == eng.pool.pages_allocatable


@pytest.mark.slow  # ci.sh's int8 gate runs the full file unfiltered
def test_quantized_weights_engine_parity(lm):
    """Weight-only int8 on top of int8 KV — the full quantized hot
    path — still lands inside the flip budget and keeps the pins."""
    m, v, ids = lm
    prompts = [np.asarray(ids[0, :n]) for n in (4, 8, 3, 11)]
    budgets = [6] * len(prompts)
    _, oracle = _drive(m, v, prompts, budgets)
    _, got = _drive(m, v, prompts, budgets, kv_dtype="int8",
                    quantize_weights=True)
    rate = _flip_rate(oracle, got)
    assert rate <= FLIP_BUDGET, f"quantized-weights flip rate {rate}"


@pytest.mark.slow  # ci.sh's int8 gate runs the full file unfiltered
def test_mesh_soak_int8_2x2(lm):
    """The sharded soak: bf16 and int8 paged engines on the SAME 2x2
    (data, model) mesh, same raggedy traffic with mid-run joins —
    stream divergence inside the flip budget, compile pins intact,
    pages drained, and the int8 pool's per-device bytes strictly under
    the bf16 pool's."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [np.asarray(p, np.int32)
               for p in (row[:4], row[:9], row[:2], row[:11], row[:6])]
    budgets = [6, 5, 4, 6, 5]

    def drive(**kw):
        engine = ServeEngine(m, v, slots=4, cache_len=32, max_queue=8,
                             decode_block=4, mesh="data=2,model=2",
                             paged=True, num_pages=24, **kw)
        streams, rids = {}, []
        with serve_compile_guard(engine, min_decode=1, min_prefill=1):
            for p, n in zip(prompts[:3], budgets[:3]):
                rids.append(engine.submit(p, max_new_tokens=n))
            results = {}
            for _ in range(2):
                results.update({r.id: r for r in engine.step()})
            for p, n in zip(prompts[3:], budgets[3:]):  # mid-run joins
                rids.append(engine.submit(p, max_new_tokens=n))
            while engine.busy:
                results.update({r.id: r for r in engine.step()})
        for i, rid in enumerate(rids):
            streams[i] = list(
                np.asarray(results[rid].tokens)[len(prompts[i]):])
        return engine, streams

    bf16_eng, oracle = drive()
    int8_eng, got = drive(kv_dtype="int8")
    rate = _flip_rate(oracle, got)
    assert rate <= FLIP_BUDGET, f"2x2 mesh int8 flip rate {rate}"
    assert int8_eng.decode_compile_count <= int8_eng.num_decode_blocks
    assert (int8_eng.pool.device_bytes_per_device()
            < bf16_eng.pool.device_bytes_per_device())
    assert int8_eng.pool.pages_free == int8_eng.pool.pages_allocatable
    assert int8_eng.metrics.to_dict()["kv_dtype"] == "int8"
