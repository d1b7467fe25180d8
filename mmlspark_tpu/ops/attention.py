"""Attention math: dense reference implementation + the online-softmax
block update shared by the ring (context-parallel) and flash paths.

The reference has NO attention code at all (SURVEY.md §5 "long-context:
absent" — its only sequence model is an opaque downloaded BiLSTM graph,
notebook 304). Long-context support is a required capability *upgrade* for
the TPU build, so this module is designed hardware-first rather than ported:
scores accumulate in float32, the streaming-softmax update lets K/V arrive
in blocks (from a ring neighbor or a VMEM tile) without materializing the
full (S, S) score matrix, and every shape is static for XLA.

Layout convention: ``(batch, seq, heads, head_dim)`` for q/k/v, running
stats ``(batch, heads, q_len)``, accumulator ``(batch, q_len, heads, dim)``.
"""

from __future__ import annotations

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# masking values — ONE home for both conventions, so masks composed across
# the dense (XLA) and Pallas paths can never mix semantics:
#
# - ``NEG_INF`` (true -inf) is the DENSE/XLA additive-mask value. The dense
#   paths detect fully-masked rows exactly (``isneginf`` on the running max,
#   ``denom == 0``) and emit zeros for them; exp(-inf - finite) is exactly 0.
# - ``KERNEL_NEG_INF`` (finite -1e30) is the Pallas in-kernel stand-in. The
#   blockwise kernels carry a running max initialized to it across grid
#   iterations, and true -inf would poison that algebra the first time the
#   update computes ``exp(m_prev - m_new)`` with both at -inf (inf - inf ->
#   nan). -1e30 is far below any finite f32 score, so ``exp(s - m)``
#   underflows to exactly 0.0 for masked entries; kernels detect
#   fully-masked rows via ``l == 0`` (dead blocks are skipped, never
#   accumulated), not via isneginf.
#
# Pick with :func:`mask_value`; never hard-code a third convention.

NEG_INF = float("-inf")
KERNEL_NEG_INF = -1e30


def mask_value(*, kernel: bool) -> float:
    """The additive value for dead attention scores: the finite Pallas
    in-kernel stand-in when ``kernel=True`` (running-max algebra cannot
    survive -inf minus -inf), true ``-inf`` for the dense/XLA paths
    (which detect fully-masked rows exactly). See the module-level note
    above for why the two must not mix."""
    return KERNEL_NEG_INF if kernel else NEG_INF


def decode_live_lengths(pos, batch: int, live=None, rows: int = 1):
    """Per-row LIVE KV lengths for a single-token decode step writing at
    absolute position ``pos``: the step's own K/V lands at ``pos``, so
    positions ``[0, pos]`` are live — length ``pos + 1``. A step that
    writes ``rows`` rows from ``pos`` on (a denoising step's block) makes
    ``pos + rows`` live.

    This is the one definition of the decode off-by-one shared by the
    dense cache read (``dense_attention(..., q_offset=pos)`` masks
    ``kpos > pos``, i.e. keeps exactly ``pos + 1`` keys) and the
    split-KV kernel (``flash_decode`` masks ``kpos >= length``), so the
    two paths agree on which cache rows a step may see. ``pos`` is a
    traced scalar or a per-row ``(B,)`` vector (the serving engine's
    multi-tenant step); returns ``(batch,)`` int32.

    ``live`` ((B,) bool, optional — the fused decode BLOCK's carry)
    zeroes dead rows' lengths: ``flash_decode``'s index-map clamp
    early-outs at length 0, so a row that finished mid-block stops
    paying for cache reads entirely (its masked output is a pad either
    way).
    """
    pos = jnp.asarray(pos, jnp.int32)
    if not pos.ndim:
        pos = jnp.broadcast_to(pos, (batch,))
    lengths = pos + rows
    if live is not None:
        lengths = jnp.where(live, lengths, 0)
    return lengths


def causal_block_mask(q_len: int, kv_len: int, q_offset, kv_offset,
                      window: int | None = None, causal_block: int = 1):
    """Additive mask (q_len, kv_len) for a block of a causal attention
    matrix whose global coordinates start at (q_offset, kv_offset);
    ``window=W`` additionally masks keys older than ``qpos - W + 1``
    (the causal sliding window). ``causal_block=L`` > 1 makes the mask
    BLOCK-causal, as a model that generates by diffusion over blocks of
    ``L`` positions reads its clean blocks: query ``i`` sees key ``j`` iff
    ``j // L <= i // L``, so the positions of one block see each other
    both ways.

    Offsets may be traced scalars (ring steps compute the kv offset from
    the rotating source index) — only the lengths must be static.
    ``q_offset`` may also be a PER-ROW vector (B,) — the serving engine's
    fused decode step, where every batch row is a different request at
    its own absolute position — producing a (B, 1, q_len, kv_len) mask
    that broadcasts over heads; ``kv_offset`` must be scalar then (slot
    caches all start at position 0).
    """
    q_offset = jnp.asarray(q_offset)
    if q_offset.ndim:
        if jnp.ndim(kv_offset):
            raise ValueError(
                "per-row q_offset requires a scalar kv_offset"
            )
        qi = (
            q_offset[:, None, None, None]
            + jnp.arange(q_len)[None, None, :, None]
        )  # (B, 1, Q, 1)
        kj = kv_offset + jnp.arange(kv_len)[None, None, None, :]
    else:
        qi = q_offset + jnp.arange(q_len)[:, None]
        kj = kv_offset + jnp.arange(kv_len)[None, :]
    dead = (kj > qi if causal_block == 1
            else kj // causal_block > qi // causal_block)
    if window is not None:
        dead = dead | (kj <= qi - window)
    return jnp.where(dead, NEG_INF, 0.0).astype(jnp.float32)


def softmax_block_update(carry, q, k, v, scale, mask=None):
    """One streaming-softmax step: fold the (k, v) block into the running
    (max, normalizer, accumulator) for queries ``q``.

    ``carry = (m, l, acc)`` with m, l: (B, H, Q) float32 and
    acc: (B, Q, H, D) float32. Blocks where every entry is masked
    contribute exactly zero (the -inf running max is substituted before
    exponentiation, never subtracted from itself).
    """
    m, l, acc = carry
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale
    if mask is not None:
        s = s + mask  # broadcast (Q, K) or (B, H, Q, K)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows still at -inf (nothing unmasked yet): exponentiate against 0
    # so exp(-inf - 0) == 0 instead of exp(-inf + inf) == nan
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    corr = jnp.exp(m - m_safe)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * jnp.moveaxis(corr, 1, 2)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def finalize_softmax(l, acc, dtype):
    """Normalize the accumulator; fully-masked rows come out as zeros."""
    denom = jnp.moveaxis(jnp.where(l == 0.0, 1.0, l), 1, 2)[..., None]
    return (acc / denom).astype(dtype)


def _validate_and_expand_gqa(q, k, v):
    """Shared grouped-query contract: k/v heads equal and dividing q
    heads, expanded to q heads by repeat (query head i reads kv head
    i // group). ONE definition so the dense reference and the rolled
    decode path can never drift apart."""
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise ValueError(
            "k/v heads must be equal and divide q heads, got "
            f"q={q.shape[2]} k={k.shape[2]} v={v.shape[2]}"
        )
    rep = q.shape[2] // k.shape[2]
    if rep != 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def sink_denominator(m, denom, sink):
    """A learned per-head SINK joined to a softmax's denominator only:
    ``m`` (B, H, Q, 1) is the running maximum of the scores, ``denom``
    (B, H, Q) the sum of ``exp(s - m)``, ``sink`` (H,) one logit a query
    head. Returns the factor that rescales the weights to the maximum
    taken with the sink, and the denominator with the sink's share:
    ``p_ij = exp(s_ij - m') / (sum_j exp(s_ij - m') + exp(b_h - m'))``,
    ``m' = max(m, b_h)``. A row with nothing to see keeps weights of
    nought over a denominator of one."""
    b = sink.astype(jnp.float32)[None, :, None, None]
    m_new = jnp.maximum(m, b)
    corr = jnp.exp(m - m_new)
    return corr, denom * corr[..., 0] + jnp.exp(b - m_new)[..., 0]


def rolled_window_attention(q, k, v, pos, *, scale=None, sink=None):
    """One decode step against a ROLLED sliding-window cache.

    ``k``/``v`` are (B, W, Hkv, D) circular buffers where slot ``j``
    holds the key/value at the latest absolute position congruent to
    ``j`` mod W that is <= ``pos`` — by construction every written slot
    is inside the causal window of the query at ``pos``, so no window
    mask is needed; the only masking is validity for slots not yet
    written while ``pos < W``. ``q`` is (B, 1, H, D) (single decode
    step); ``pos`` may be traced. GQA follows the dense convention
    (fewer K/V heads, repeated to query heads).

    This is what keeps long generations O(window) in memory: the
    framework's sliding-window models never need a (B, P+N, ...) cache
    (models/generate.py picks this path automatically).

    ``pos`` may also be a (B,) vector of per-row positions, the values
    may be narrower or wider than the keys, and ``sink`` (H,) joins the
    denominator (:func:`sink_denominator`).
    """
    k, v = _validate_and_expand_gqa(q, k, v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    w = k.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim:
        pos = pos[:, None, None, None]
    valid = jnp.arange(w)[None, None, None, :] <= pos  # pos >= W: all on
    s = jnp.where(valid, s, NEG_INF)
    # the slot at pos % W is always valid, so no fully-masked rows exist
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    denom = p.sum(axis=-1)
    if sink is not None:
        corr, denom = sink_denominator(m, denom, sink)
        p = p * corr
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return (out / jnp.moveaxis(denom, 1, 2)[..., None]).astype(q.dtype)


def dense_attention(q, k, v, *, causal: bool = False,
                    window: int | None = None, scale=None,
                    q_offset: int = 0, kv_offset: int = 0, sink=None,
                    causal_block: int = 1):
    """Reference multi-head attention, (B, S, H, D) layout.

    Single fused einsum-softmax-einsum — exactly what XLA fuses well on one
    chip; the parallel layer (:mod:`mmlspark_tpu.parallel.context_parallel`)
    decomposes the same math across devices and must match this output.
    ``window`` is the causal sliding window (same semantics as the flash
    kernel: each query sees its W most recent keys; requires causal).
    ``q_offset`` may be a (B,) vector of per-row positions (the serving
    engine's multi-tenant decode step — see ``causal_block_mask``).
    The values may be narrower or wider than the keys (the output takes
    the values' width), and ``sink`` (H,), one learned logit a query
    head, joins the softmax's denominator only
    (:func:`sink_denominator`). ``causal_block`` > 1 (with ``causal``)
    makes the mask block-causal (:func:`causal_block_mask`).
    """
    if causal_block != 1 and (not causal or window is not None):
        raise ValueError("causal_block requires causal=True and no window")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    # grouped-query attention, same convention as the flash kernel
    # (query head i -> kv head i // group); the dense REFERENCE just
    # repeats — the kernel is where the no-copy expansion lives
    k, v = _validate_and_expand_gqa(q, k, v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        s = s + causal_block_mask(q.shape[1], k.shape[1], q_offset,
                                  kv_offset, window=window,
                                  causal_block=causal_block)
    m = s.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m)
    denom = p.sum(axis=-1)
    if sink is not None:
        corr, denom = sink_denominator(m, denom, sink)
        p = p * corr
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    denom = jnp.moveaxis(jnp.where(denom == 0.0, 1.0, denom), 1, 2)[..., None]
    return (out / denom).astype(q.dtype)
