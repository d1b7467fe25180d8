"""Length-aware flash-decode kernel (ops/flash_attention.flash_decode).

The contract under test: single-token split-KV attention over slot
caches matches the dense reference over RAGGED per-row live lengths —
including the degenerate rows (length 0 -> zeros, length == cache_len
-> full read) — across dtypes, GQA groupings, and block counts, with the
per-row masking geometry shared with ``ops/attention.py``
(``decode_live_lengths``) and ONE home for both NEG_INF conventions.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.attention import (
    KERNEL_NEG_INF,
    NEG_INF,
    causal_block_mask,
    decode_live_lengths,
    dense_attention,
    mask_value,
)
from mmlspark_tpu.ops.flash_attention import (
    _decode_block,
    _heads_and_rows,
    flash_decode,
    flash_decode_grouped,
)


def _qkv(b, L, h, hk, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, L, hk, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, L, hk, d)), dtype)
    return q, k, v


def _dense_ref(q, k, v, lengths):
    # live length L means positions [0, L): a query "at" position L-1
    # under the causal mask (length 0 -> q_offset -1 masks everything,
    # the fully-masked row dense_attention answers with zeros)
    return dense_attention(
        q, k, v, causal=True, q_offset=jnp.asarray(lengths) - 1
    )


# -- parity over ragged live lengths ----------------------------------------


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-5),
    (jnp.bfloat16, 1e-2),  # the acceptance bound: bf16 in, f32 softmax
])
def test_parity_ragged_lengths(dtype, tol):
    L = 32
    q, k, v = _qkv(6, L, 4, 4, 16, dtype)
    lengths = jnp.asarray([0, 1, 5, 17, L - 1, L], jnp.int32)
    out = flash_decode(q, k, v, lengths)
    ref = _dense_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )
    # length 0: no live positions at all -> exact zeros, same as the
    # dense fully-masked convention
    assert not np.asarray(out[0]).any()


def test_parity_multi_block_and_ragged_tail():
    # block=8 over L=30 streams multiple KV blocks, and 30 has no
    # power-of-two tiling — the divisor/padded-tail path
    L = 30
    q, k, v = _qkv(5, L, 2, 2, 8, jnp.float32, seed=1)
    lengths = jnp.asarray([0, 3, 11, 29, L], jnp.int32)
    out = flash_decode(q, k, v, lengths, block=8)
    ref = _dense_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("hk", [1, 2])  # MQA and grouped
def test_gqa_parity(hk):
    L = 16
    q, k, v = _qkv(4, L, 4, hk, 8, jnp.bfloat16, seed=2)
    lengths = jnp.asarray([1, 7, 12, L], jnp.int32)
    out = flash_decode(q, k, v, lengths, block=8)
    ref = _dense_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=1e-2, rtol=1e-2,
    )


def test_one_program_serves_every_length():
    """The serving invariant at kernel scope: lengths are TRACED (the
    scalar-prefetch channel), so one jitted program serves every ragged
    pattern — recompiles per length vector would defeat the engine's
    compile-once decode tick."""
    from mmlspark_tpu.testing.compile_guard import compile_guard

    L = 16
    q, k, v = _qkv(3, L, 2, 2, 8, jnp.bfloat16, seed=3)
    f = jax.jit(lambda q, k, v, n: flash_decode(q, k, v, n, block=8))
    with compile_guard(f._cache_size, max_programs=1, min_programs=1,
                       label="flash_decode"):
        for lens in ([1, 2, 3], [L, 0, 5], [7, 7, 7]):
            lengths = jnp.asarray(lens, jnp.int32)
            out = f(q, k, v, lengths)
            ref = _dense_ref(q, k, v, lengths)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                atol=1e-2, rtol=1e-2,
            )


def test_lengths_clip_to_cache_len():
    # defensive contract: lengths beyond the buffer read the whole
    # buffer, never out of bounds
    L = 8
    q, k, v = _qkv(2, L, 2, 2, 8, jnp.float32, seed=4)
    out = flash_decode(q, k, v, jnp.asarray([L + 50, 2], jnp.int32))
    ref = _dense_ref(q, k, v, jnp.asarray([L, 2]))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


# -- the grouped kernel, a group smaller than a sublane tile ----------------


@pytest.mark.parametrize("hk,g,pack,budget", [
    (20, 1, 1, None),       # gpt2-large's heads: all 20 a grid step
    (20, 1, 2, None),       # the same, two heads to a row of 128 lanes
    (20, 1, 1, 4 * 128 * 512),   # room for 2 head-blocks of 128 rows
    (3, 1, 1, 4 * 128 * 512),    # a prime: no divisor but 1 fits
    (3, 2, 1, None),        # a group of 2, every head a step
    (4, 2, 2, None),        # GQA, packed: a row's two heads, two groups
    (2, 8, 2, None),        # a packed group of 8: one row-head a step
], ids=["mha20", "mha20-packed", "mha20-2heads", "prime3-1head", "gqa3",
        "gqa4-packed", "group8-packed"])
def test_grouped_kernel_small_groups_match_dense(hk, g, pack, budget,
                                                 monkeypatch):
    """``flash_decode_grouped`` over head-major rows, lengths 0, 1, ``L``
    and ragged, against ``dense_attention`` over the same rows laid
    linear: several KV heads of a slot a grid step when the group is
    under 8, as many as the budget holds and ``hk`` divides by."""
    from mmlspark_tpu.ops import flash_attention as fa

    L, d = 384, 64
    if budget is not None:
        monkeypatch.setattr(fa, "_DECODE_KV_VMEM", budget)
        want_heads = {20: 2, 3: 1}[hk]
        assert _heads_and_rows(hk, L, 512, d, d, 2) == (want_heads, 128)
    elif g * pack < 8:
        assert _heads_and_rows(hk // pack, L, 512, pack * d, pack * d,
                               2)[0] == hk // pack
    q, k, v = _qkv(6, L, hk * g, hk, d, jnp.bfloat16)
    lengths = jnp.asarray([0, 1, L, 129, 37, 256], jnp.int32)

    def head_major(x):      # (B, L, hk, d) -> (B, hk / f, L, f * d)
        return jnp.moveaxis(x.reshape(6, L, hk // pack, pack * d), 1, 2)

    out = flash_decode_grouped(q, head_major(k), head_major(v), lengths)
    ref = _dense_ref(q, k, v, lengths)
    assert out.shape == ref.shape and out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=1e-2, rtol=1e-2)
    assert not np.asarray(out[0], np.float32).any()


def test_decode_block_prefers_exact_divisors():
    # an exact divisor means the cache streams with NO pad copy — the
    # serving hot path never duplicates its K/V buffers
    assert _decode_block(32, 128) == 32    # whole cache in one block
    assert _decode_block(256, 128) == 128
    assert _decode_block(48, 32) == 24     # largest divisor <= block
    assert _decode_block(30, 8) == 8       # no divisor in [8, 8]: padded


def test_validation_errors():
    q, k, v = _qkv(2, 8, 4, 2, 8, jnp.bfloat16)
    with pytest.raises(ValueError, match="one dtype"):
        flash_decode(q.astype(jnp.float32), k, v, jnp.ones(2, jnp.int32))
    with pytest.raises(ValueError, match="SINGLE query"):
        flash_decode(jnp.concatenate([q, q], 1), k, v,
                     jnp.ones(2, jnp.int32))
    with pytest.raises(ValueError, match="heads"):
        flash_decode(q, k[:, :, :1].repeat(3, 2), v[:, :, :1].repeat(3, 2),
                     jnp.ones(2, jnp.int32))
    with pytest.raises(ValueError, match="lengths"):
        flash_decode(q, k, v, jnp.ones((3,), jnp.int32))


# -- shared masking geometry ------------------------------------------------


def test_decode_live_lengths_contract():
    # scalar pos broadcasts; per-row passes through; both are pos + 1
    np.testing.assert_array_equal(
        np.asarray(decode_live_lengths(4, 3)), [5, 5, 5]
    )
    np.testing.assert_array_equal(
        np.asarray(decode_live_lengths(jnp.asarray([0, 2, 9]), 3)),
        [1, 3, 10],
    )


def test_mask_value_single_home():
    import mmlspark_tpu.ops.flash_attention as fa

    assert mask_value(kernel=False) == NEG_INF == float("-inf")
    assert mask_value(kernel=True) == KERNEL_NEG_INF == -1e30
    # flash kernels use the one kernel-side constant, not a third copy
    assert fa.NEG_INF == KERNEL_NEG_INF


def test_causal_block_mask_per_row_with_window():
    """Per-row q_offset combined with window=W (the previously untested
    corner): each row of the (B, 1, Q, K) mask must equal the scalar
    mask built at that row's offset."""
    B, Q, K, W = 4, 2, 12, 5
    offsets = jnp.asarray([0, 3, 7, 10])
    got = causal_block_mask(Q, K, offsets, 0, window=W)
    assert got.shape == (B, 1, Q, K)
    for b in range(B):
        want = causal_block_mask(Q, K, int(offsets[b]), 0, window=W)
        np.testing.assert_array_equal(
            np.asarray(got[b, 0]), np.asarray(want)
        )


def test_per_row_mask_requires_scalar_kv_offset():
    with pytest.raises(ValueError, match="scalar kv_offset"):
        causal_block_mask(1, 4, jnp.asarray([0, 1]), jnp.asarray([0, 1]))
