"""The KV cache's entry formats behind their one call (ops/kv_cache.py).

The contract under test: whatever an entry's layout, a few decode steps
through :func:`decode_step` give what plain attention gives over the
same rows laid out linearly, and hand back an entry of the same type,
leaves, shapes and dtypes. Tiny shapes, the kernels in interpret mode.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import ParamError
from mmlspark_tpu.ops import kv_cache
from mmlspark_tpu.ops.attention import dense_attention
from mmlspark_tpu.ops.kv_cache import (
    HeadMajorKV,
    Int8Rows,
    LatentRows,
    PagedInt8KV,
    PagedKV,
    kv_head_scales,
    quantize_kv,
)

SLOTS, ROWS, HEADS, KV_HEADS, DK = 4, 32, 4, 2, 64
WINDOW, PAGE = 8, 8
#: where each slot's next row goes; with PAGE 8 the first and the third
#: open a fresh page at their last step, which fixes that page's scale
START = np.asarray([6, 11, 22, 3])
LIVE = np.asarray([True, True, True, False])
STEPS = 3
#: the int8 budget tests/test_quantized_serve.py holds the kernels to
INT8_BUDGET, BF16_BUDGET = 0.0625, 0.02


def _head_major(lin, f=1):
    s, rows, hk, d = lin.shape
    return jnp.moveaxis(lin.reshape(s, rows, hk // f, f * d), 1, 2)


def _ring(lin):
    """Row ``j`` of the ring holds the latest position below the slot's
    start that is congruent to ``j``."""
    lin = np.asarray(lin.astype(jnp.float32))
    ring = np.zeros((SLOTS, lin.shape[2], WINDOW, lin.shape[3]), np.float32)
    for slot, start in enumerate(START):
        for p in range(max(0, start - WINDOW), start):
            ring[slot, :, p % WINDOW] = lin[slot, p]
    return jnp.asarray(ring, jnp.bfloat16)


def _pages(lin):
    """Slot ``s``'s page ``i`` is physical page ``1 + s * n + i``; page 0
    is nobody's."""
    n = ROWS // PAGE
    faces = jnp.swapaxes(lin.reshape(SLOTS * n, PAGE, *lin.shape[2:]), 1, 2)
    store = jnp.concatenate([jnp.zeros_like(faces[:1]), faces])
    table = 1 + jnp.arange(SLOTS * n, dtype=jnp.int32).reshape(SLOTS, n)
    return store, table


def _linear(k, v):
    return (k, v)


def _linear_int8(k, v):
    ks, vs = kv_head_scales(k, axes=(1, 3)), kv_head_scales(v, axes=(1, 3))
    return Int8Rows(quantize_kv(k, ks[:, None]), quantize_kv(v, vs[:, None]),
                    ks, vs)


def _paged(k, v):
    (pk, table), (pv, _) = _pages(k), _pages(v)
    # donation forbids shared leaves, and so does nothing here: one table
    return PagedKV(pk, pv, table)


def _paged_int8(k, v):
    pk, pv, table = _paged(k, v)
    ks, vs = kv_head_scales(pk, axes=(2, 3)), kv_head_scales(pv, axes=(2, 3))

    def quantized(pages, scales):  # quantize_kv wants (..., hk, d)
        rows = quantize_kv(jnp.swapaxes(pages, 1, 2), scales[:, None])
        return jnp.swapaxes(rows, 1, 2)

    return PagedInt8KV(quantized(pk, ks), quantized(pv, vs), table, ks, vs)


#: name -> (entry from the linear rows, value width, window, sink, budget)
CASES = {
    "linear": (_linear, DK, None, False, BF16_BUDGET),
    "linear-int8": (_linear_int8, DK, None, False, INT8_BUDGET),
    "head-major": (
        lambda k, v: HeadMajorKV(_head_major(k), _head_major(v)),
        DK, None, False, BF16_BUDGET),
    "head-major-packed": (
        lambda k, v: HeadMajorKV(_head_major(k, 2), _head_major(v, 2)),
        DK, None, False, BF16_BUDGET),
    # as hybrid_lm's window layers have it: narrower values, a sink
    "ring": (lambda k, v: HeadMajorKV(_ring(k), _ring(v)),
             DK // 2, WINDOW, True, BF16_BUDGET),
    "paged": (_paged, DK, None, False, BF16_BUDGET),
    "paged-int8": (_paged_int8, DK, None, False, INT8_BUDGET),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_steps_match_plain_attention_over_linear_rows(case):
    build, dv, window, with_sink, budget = CASES[case]
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 3 + 3 * STEPS))

    def draw(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    written = jnp.arange(ROWS)[None, :, None, None] < START[:, None, None,
                                                           None]
    lin_k = jnp.where(written, draw(SLOTS, ROWS, KV_HEADS, DK), 0)
    lin_v = jnp.where(written, draw(SLOTS, ROWS, KV_HEADS, dv), 0)
    sink = draw(HEADS).astype(jnp.float32) if with_sink else None
    entry = build(lin_k, lin_v)
    step = jax.jit(partial(kv_cache.decode_step, window=window,
                           name="attn", sink=sink))
    pos, live = jnp.asarray(START, jnp.int32), jnp.asarray(LIVE)
    slots = jnp.arange(SLOTS)
    for _ in range(STEPS):
        q = draw(SLOTS, 1, HEADS, DK)
        k, v = draw(SLOTS, 1, KV_HEADS, DK), draw(SLOTS, 1, KV_HEADS, dv)
        got, new = step(entry, q, k, v, pos, live)
        assert type(new) is type(entry)
        assert [(a.shape, a.dtype) for a in new] == [
            (a.shape, a.dtype) for a in entry]
        entry = new
        lin_k = lin_k.at[slots, pos].set(k[:, 0])
        lin_v = lin_v.at[slots, pos].set(v[:, 0])
        want = dense_attention(q, lin_k, lin_v, causal=True, window=window,
                               q_offset=pos, sink=sink)
        gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        assert float(gap[LIVE].max()) <= budget, (case, float(gap.max()))
        # a dead row reads nothing
        assert not np.asarray(got[~LIVE], np.float32).any()
        pos = jnp.where(live, pos + 1, pos)


@pytest.mark.parametrize("per_row", [True, False],
                         ids=["fused-step", "scalar-position"])
def test_latent_steps_match_plain_attention_over_linear_rows(per_row):
    """A latent entry has ONE array and one KV head: a step's row is its
    key, the row's first 128 columns its value. Rows of 136 numbers lie
    in 256 lanes; 8 query heads share them, at the block's own scale."""
    dk, dv, wide, heads, scale = 136, 128, 256, 8, 24 ** -0.5
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 1 + 2 * STEPS))

    def draw(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    start = START if per_row else np.full_like(START, 6)
    live = jnp.asarray(LIVE) if per_row else None
    written = jnp.arange(ROWS)[None, :, None, None] < start[:, None, None,
                                                           None]
    lin = jnp.where(written, draw(SLOTS, ROWS, 1, dk), 0)
    entry = LatentRows(jnp.pad(lin[:, :, 0], ((0, 0), (0, 0),
                                              (0, wide - dk))))
    step = jax.jit(partial(kv_cache.decode_step, name="attn_mla_decode",
                           scale=scale))
    pos = jnp.asarray(start, jnp.int32) if per_row else 6
    slots = jnp.arange(SLOTS)
    for _ in range(STEPS):
        q, k = draw(SLOTS, 1, heads, dk), draw(SLOTS, 1, 1, dk)
        got, new = step(entry, q, k, k[..., :dv], pos, live)
        assert type(new) is LatentRows
        assert (new.rows.shape, new.rows.dtype) == (entry.rows.shape,
                                                    entry.rows.dtype)
        assert not np.asarray(new.rows[..., dk:], np.float32).any()
        entry = new
        lin = lin.at[slots, pos].set(k[:, 0])
        want = dense_attention(q, lin, lin[..., :dv], causal=True,
                               q_offset=pos, scale=scale)
        gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        kept = LIVE if per_row else slice(None)
        assert got.shape == (SLOTS, 1, heads, dv)
        assert float(gap[kept].max()) <= BF16_BUDGET, float(gap.max())
        if per_row:
            assert not np.asarray(got[~LIVE], np.float32).any()
            pos = jnp.where(live, pos + 1, pos)
        else:
            pos += 1


@pytest.mark.parametrize("case", [c for c in CASES if c != "linear"])
def test_a_pool_only_layout_refuses_all_but_the_fused_step(case):
    build, dv, window, _sink, _budget = CASES[case]
    k = jnp.zeros((SLOTS, ROWS, KV_HEADS, DK), jnp.bfloat16)
    v = jnp.zeros((SLOTS, ROWS, KV_HEADS, dv), jnp.bfloat16)
    entry = build(k, v)
    assert not kv_cache.is_linear(entry)
    q = jnp.zeros((SLOTS, 1, HEADS, DK), jnp.bfloat16)
    with pytest.raises(ParamError, match="prefill"):  # a scalar position
        kv_cache.decode_step(entry, q, k[:, :1], v[:, :1], 3, window=window)
    with pytest.raises(ParamError, match="prefill"):
        kv_cache.write_rows(entry, k[:, :4], v[:, :4], 0)


def test_a_sink_is_refused_where_no_kernel_reads_it():
    k = jnp.zeros((SLOTS, ROWS, KV_HEADS, DK), jnp.bfloat16)
    q = jnp.zeros((SLOTS, 1, HEADS, DK), jnp.bfloat16)
    with pytest.raises(ParamError, match="sink"):
        kv_cache.decode_step((k, k), q, k[:, :1], k[:, :1],
                             jnp.zeros((SLOTS,), jnp.int32),
                             sink=jnp.zeros((HEADS,), jnp.float32))
