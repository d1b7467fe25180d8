"""The KV cache's entry formats, and the two things a program does with
an entry: a prefill writes rows into it, a decode step appends one row a
slot and attends over the live rows.

One home for what the models (``models/``) and the pools (``serve/``)
both have to know, below both. An ENTRY is what one block's cache is, in
``generate()``'s cache dict and in a pool's ``buffers`` alike, a pytree
of arrays whose layout is said by its TYPE: :class:`Int8Rows`,
:class:`HeadMajorKV`, :class:`LatentRows`, :class:`PagedKV`,
:class:`PagedInt8KV`, and the two that are no attention's:
:class:`StateRows` and :class:`SlotState`, a short convolution's inputs
(:func:`state_step`). Linear
bfloat16 rows ``(B, rows, hk, d)`` are the ONE untyped default, a plain
``(k, v)`` pair: it is what :func:`mmlspark_tpu.models.generate.
init_cache`, ``generate()``, every prefill program, the snapshots and
the fleet's hand-off exchange, so it keeps their format (a pool under a
mesh serves from it too), and every other layout is told from it by
type. :func:`decode_step` is the one call of an ATTENTION's decode step,
whatever the entry; :func:`write_rows` the linear entry's write;
:func:`state_step` the one call of a short convolution over its state,
which takes no query and reads no position. A new cache kind is a type
and a step here, plus what a pool allocates.
"""

from __future__ import annotations

import contextlib
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError, ParamError
from mmlspark_tpu.ops.attention import (
    decode_live_lengths,
    dense_attention,
    rolled_window_attention,
)

#: the cache kinds a block can have: ``linear`` (what a block that declares
#: nothing gets: a row for every position, laid out ``(B, rows, hk, d)``),
#: and the two a block DECLARES through ``cache_spec()``: ``full`` (a row
#: for every position) and ``ring`` (the last ``rows`` positions, position
#: ``p`` in row ``p % rows``), which the serving pool lays out head-major
#: (models/hybrid.py, serve/cache_pool.py), and ``latent`` (a row for every
#: position that is no K/V pair: :class:`LatentRows`) and ``state`` (a
#: CONSTANT number of rows a slot, whatever its length: the inputs a causal
#: short convolution still needs, :class:`SlotState`)
LINEAR, FULL_ROWS, RING_ROWS = "linear", "full", "ring"
LATENT_ROWS = "latent"
STATE_ROWS = "state"

#: headroom multiplied onto the prefill amax when fixing a slot's int8
#: quantization scale: decode steps quantize with the SAME scale
#: in-graph (a per-step rescale would invalidate already-written int8
#: rows), so the margin absorbs decode K/V drifting above the prompt's
#: range; values beyond it saturate at ±127 — graceful, and part of the
#: declared error budget (docs/PERFORMANCE.md "Quantized decode")
KV_SCALE_MARGIN = 1.5

VALID_KV_DTYPES = ("bf16", "int8")


class Int8Rows(NamedTuple):
    """The dense pool's int8 mode: linear ``(S, rows, hk, d)`` int8 rows
    and ``(S, hk)`` float32 scales, fixed for a lease by its prefill."""

    k: Any
    v: Any
    k_scale: Any
    v_scale: Any


class HeadMajorKV(NamedTuple):
    """A serving pool entry whose rows lie HEAD-MAJOR: ``k`` is ``(S, hk,
    rows, dk)`` and ``v`` ``(S, hk, rows, dv)``, the layout that
    ``cache_row_write`` updates in place and ``flash_decode_grouped``
    streams without a copy. Shapes cannot tell ``(S, hk, L, d)`` from a
    linear ``(S, L, hk, d)``, so the pool says it with the entry's TYPE.
    ``rows`` is every position, or a window block's ring (position ``p``
    in row ``p % rows``).

    Rows narrower than the TPU's 128 lanes are PACKED where the heads
    allow it (:func:`lane_pack`): ``f`` adjacent KV heads lie side by
    side in one row, ``(S, hk / f, rows, f * d)``, which in memory is
    the prefill cache's own ``(rows, hk, d)`` order with the head axis
    split. The row write and the pool's prefill write see ``hk / f``
    heads of width ``f * d``; ``flash_decode_grouped`` reads ``f`` off
    the widths."""

    k: Any
    v: Any


class LatentRows(NamedTuple):
    """A LATENT entry (multi-head latent attention): ONE array ``rows``
    ``(B, rows, W)``, a position's row ``[c ; k_rope]`` the compressed
    KV and the one rotary key that ALL query heads share, so there is no
    head axis and no value array: the values are the rows' first columns
    (``c``), read in the same fetch as the keys. ``generate()``, a
    prefill's cache and the serving pool hold it alike (with one KV head
    head-major and linear are the same bytes); what differs is the step:
    per-row positions take the engine's fused step (one row written in
    place, :func:`~mmlspark_tpu.ops.flash_attention.flash_decode_grouped`
    with ``values_in_keys``), anything else a plain write and a dense
    read.

    ``W`` is the rows' width in whole lanes (:func:`latent_width`), the
    pad columns nought: a v5e holds ``bf16[S, L, 576]`` with the ROWS in
    its lanes (``{1,2,0}``: sandbox compile, PR 33), so a kernel that
    streams rows would have the pool copied into its layout around every
    decode block; ``bf16[S, L, 640]`` is held as the kernel reads it."""

    rows: Any


class StateRows(NamedTuple):
    """The LINEAR form of a gated short convolution's state: ``rows`` (B,
    total, W), the convolution's input ``g_t = B_t * u_t`` at EVERY
    position. What a prefill fills, a chunked fill carries and
    ``generate()`` decodes on: any later call finds the ``K - 1`` inputs
    before its first position by a slice, and the pool takes the last
    ``K - 1`` below a prompt's TRUE length, wherever its bucket ends."""

    rows: Any


class SlotState(NamedTuple):
    """The serving pool's form of the same state: ``rows`` (S, (K - 1) *
    W), a slot's last ``K - 1`` inputs side by side in ONE row, oldest
    first (``slots x (K - 1) x W`` in memory; two rows of 2,048 make one of
    4,096 lanes, which a v5e holds as the kernel reads it). Constant in
    size whatever the slot's length: no head axis, no position, nothing an
    attention could read. :func:`state_step` shifts it in place."""

    rows: Any


class PagedKV(NamedTuple):
    """The paged pool's entry (serve/paging.py): the page stores and the
    block's own copy of the page table (donation forbids shared leaves)."""

    k: Any
    v: Any
    page_table: Any


class PagedInt8KV(NamedTuple):
    """The paged pool's int8 mode: ``(num_pages, hk)`` float32 scales, a
    page's fixed at its first write."""

    k: Any
    v: Any
    page_table: Any
    k_scale: Any
    v_scale: Any


def lane_pack(hk: int, dk: int, dv: int) -> int:
    """How many adjacent KV heads one row of a head-major pool entry
    holds side by side: as many as fill 128 lanes, where the widths and
    the head count divide, else 1. An array whose minor dimension is
    under 128 lives on the TPU with a LARGER dimension in its lanes
    (bf16[16, 20, 1024, 64] is held ``{2,3,1,0}``, rows in the lanes:
    sandbox compile, PR 30), so a kernel that wants rows of 64 would
    have the whole pool copied into its layout and back around every
    decode block; rows of 128 are held as the kernel reads them."""
    lanes = 128
    if dk != dv or not 0 < dk < lanes or lanes % dk:
        return 1
    f = lanes // dk
    return f if hk % f == 0 else 1


def latent_width(dk: int) -> int:
    """The width a :class:`LatentRows` entry stores rows of ``dk``
    numbers at: whole lanes of 128."""
    return -(-int(dk) // 128) * 128


def validate_kv_dtype(kv_dtype: str, geometry: dict) -> None:
    """Shared pool-level contract for ``kv_dtype`` (dense and paged
    pools): the flag must name a supported dtype, and int8 requires an
    even head_dim — the decode kernels' int8 VREG tile packs lanes
    pairwise and rejects odd D (the CLI surfaces this as the
    FriendlyError, not a kernel shape crash mid-serve)."""
    if kv_dtype not in VALID_KV_DTYPES:
        raise FriendlyError(
            f"kv_dtype must be one of {VALID_KV_DTYPES}, got "
            f"{kv_dtype!r}"
        )
    if kv_dtype == "int8":
        for name, (hk, d) in geometry.items():
            if d % 2:
                raise FriendlyError(
                    f"kv_dtype='int8' requires an even head_dim (the "
                    f"int8 decode-kernel tile packs lanes pairwise), "
                    f"but block '{name}' has head_dim {d}. Use "
                    f"kv_dtype='bf16' or an even d_model/heads split"
                )


def quantize_kv(values, scales):
    """Symmetric int8 quantization of K/V ``values`` (..., hk, d) with
    per-kv-head ``scales`` broadcastable over (..., hk); out-of-range
    values saturate at ±127. ONE definition shared by the pools'
    prefill writes and the in-graph decode-step writes, so both paths
    land bit-identical int8 for identical inputs."""
    q = jnp.round(values.astype(jnp.float32) / scales[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def kv_head_scales(values, axes) -> jnp.ndarray:
    """Per-kv-head f32 quantization scales from the amax of ``values``
    over ``axes`` (every dim but the kv-head dim), with the
    ``KV_SCALE_MARGIN`` headroom and a 1.0 floor substituted for
    all-zero heads (a zero scale would divide by zero; scale 1.0 maps
    zeros to zeros exactly)."""
    amax = jnp.abs(values.astype(jnp.float32)).max(axis=axes)
    scale = amax * (KV_SCALE_MARGIN / 127.0)
    return jnp.where(scale == 0.0, 1.0, scale)


#: what each pool-only layout says when it is handed anything but the
#: engine's fused decode step
_REFUSALS = {
    PagedKV: (
        "paged caches serve per-row single-token decode only (the serve "
        "engine's fused decode step); prefill uses the linear cache path"
    ),
    HeadMajorKV: (
        "head-major caches serve per-row single-token full-window decode "
        "only (the serve engine's fused decode step); prefill uses the "
        "linear cache path"
    ),
    Int8Rows: (
        "int8 dense caches serve the engine's per-row single-token "
        "full-window decode only; prefill and single-request generate "
        "use bf16 linear caches"
    ),
}
_REFUSALS[PagedInt8KV] = _REFUSALS[PagedKV]
_REFUSALS[SlotState] = (
    "a slot's convolution state serves per-row single-token steps only "
    "(the serve engine's fused decode step); prefill uses the linear "
    "state rows"
)


def is_linear(entry) -> bool:
    """Whether ``entry`` is the untyped default, linear ``(k, v)`` rows."""
    return type(entry) not in _REFUSALS


def write_rows(entry, k, v, pos, *, rolled: bool = False):
    """The LINEAR entry with this call's ``k``/``v`` (B, T, hk, d)
    written from ``pos`` on: a prefill, a chunk or a resume against a
    live prefix, ``generate()``'s decode steps. ``pos`` (B,) is the
    engine's multi-tenant step: every batch row is a different request
    writing its own absolute position in its own slot buffer.
    ``rolled`` (O(window) circular, sliding-window models on long
    generations): the step's K/V land at slot ``pos % W`` — every
    written slot is inside the window by construction
    (ops/attention.py rolled_window_attention)."""
    if not is_linear(entry):
        raise ParamError(_REFUSALS[type(entry)])
    ck, cv = entry
    if jnp.ndim(pos):
        if k.shape[1] != 1:
            raise ParamError("per-row cache positions (the serve engine's "
                             "fused decode step) are single-token")
        rows = jnp.arange(ck.shape[0])
        return (ck.at[rows, pos].set(k[:, 0].astype(ck.dtype)),
                cv.at[rows, pos].set(v[:, 0].astype(cv.dtype)))
    at = (0, pos % ck.shape[1] if rolled else pos, 0, 0)
    return (jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), at),
            jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), at))


def _fused_step_only(entry, q, pos, window, rows: int | None = None):
    """Refuse a pool-only layout anything but the engine's fused decode
    step: one token a row at per-row positions, and where ``rows`` is
    given a window that covers them."""
    if q.shape[1] != 1 or not jnp.ndim(pos) or (
            rows is not None and window is not None and window < rows):
        raise ParamError(_REFUSALS[type(entry)])


def _linear_step(entry, q, k, v, pos, live, *, window, sink, name, mesh,
                 rolled):
    from mmlspark_tpu.ops import flash_attention as kernels  # lazy: Pallas

    ck, cv = new = write_rows(entry, k, v, pos, rolled=rolled)
    if rolled:
        return rolled_window_attention(q, ck, cv, pos), new
    if window is not None and window < ck.shape[1]:
        # a window tighter than the buffer: dense read with the mask
        return dense_attention(q, ck, cv, causal=True, window=window,
                               q_offset=pos), new
    # the length-aware split-KV kernel reads only each row's LIVE
    # positions [0, pos+1) — per-row work O(pos), not O(cache_len) — and
    # ``live`` zeroes dead rows' lengths, so it skips their cache traffic
    lengths = decode_live_lengths(pos, q.shape[0], live=live)
    return kernels.flash_decode(q, ck, cv, lengths, mesh=mesh), new


def _int8_rows_step(entry, q, k, v, pos, live, *, window, sink, name, mesh,
                    rolled):
    from mmlspark_tpu.ops import flash_attention as kernels

    # only the flash-decode read below can dequantize the rows
    _fused_step_only(entry, q, pos, window, rows=entry.k.shape[1])
    rows = jnp.arange(q.shape[0])
    # quantize the step's K/V against the slots' prefill-fixed scales
    # (out-of-range values saturate — priced into the parity budget)
    wk = quantize_kv(k[:, 0], entry.k_scale)
    wv = quantize_kv(v[:, 0], entry.v_scale)
    new = entry._replace(k=entry.k.at[rows, pos].set(wk),
                         v=entry.v.at[rows, pos].set(wv))
    o = kernels.flash_decode(
        q, new.k, new.v, decode_live_lengths(pos, q.shape[0], live=live),
        k_scale=new.k_scale, v_scale=new.v_scale, mesh=mesh)
    return o, new


def _head_major_step(entry, q, k, v, pos, live, *, window, sink, name, mesh,
                     rolled):
    from mmlspark_tpu.ops import flash_attention as kernels

    b, rows = q.shape[0], entry.k.shape[2]
    _fused_step_only(entry, q, pos, window, rows=rows)
    # a ring: position p lies in row p % rows
    at = pos if window is None else pos % rows
    # (b, hk, d) -> the entry's own heads and width (lane_pack). The row
    # is written in place and the kernel streams (rows, d) tiles of each
    # KV head: no relayout of the pool on either side
    packed = (b, entry.k.shape[1], -1)
    new = HeadMajorKV(*kernels.cache_row_write(
        *entry, k[:, 0].reshape(packed), v[:, 0].reshape(packed), at))
    lengths = decode_live_lengths(pos, b, live=live)
    if window is not None:
        # every written row of a ring lies inside the window
        lengths = jnp.minimum(lengths, rows)
    # the kernel is jitted where it stands, so no module's scope names
    # it: ``name`` is what the trace shows, where the decode metrics look
    return kernels.flash_decode_grouped(q, *new, lengths, sink=sink,
                                        name=name), new


def _paged_step(entry, q, k, v, pos, live, *, window, sink, name, mesh,
                rolled):
    from mmlspark_tpu.ops import flash_attention as kernels

    # strictly the serve engine's fused decode-block format — prefill
    # runs on a linear batch-1 cache and the pool scatters it into pages
    _fused_step_only(entry, q, pos, window)
    ck, cv, ptab = entry.k, entry.v, entry.page_table
    b, ps, virt = q.shape[0], ck.shape[2], ptab.shape[1] * ck.shape[2]
    if window is not None and window < virt:
        raise ParamError(
            f"paged decode has no windowed read: window ({window}) must "
            f"cover the virtual cache ({virt})"
        )
    # scatter this step's K/V through the table: row b's position pos[b]
    # lands in physical page ptab[b, pos // ps] at offset pos % ps. Dead
    # rows hold a frozen pos whose page the pool keeps pointed at a
    # trash page, so their writes never touch live data.
    pages = ptab[jnp.arange(b), pos // ps]
    offs = pos % ps
    at = (pages[:, None], jnp.arange(ck.shape[1])[None, :], offs[:, None])
    scales = {}
    if isinstance(entry, PagedInt8KV):
        # int8 page store: a page's scale is FIXED at its first write —
        # offs == 0 means this token opens a fresh page
        # (ensure_decode_pages pre-mapped it), so its amax (+ headroom)
        # becomes the page's scale; later tokens into the page quantize
        # against it and saturate into the error budget. Dead rows
        # re-stamp their trash page's scale, which nothing ever reads
        # (live length 0).
        tk = k[:, 0].astype(jnp.float32)
        tv = v[:, 0].astype(jnp.float32)
        first = (offs == 0)[:, None]
        row_ks = jnp.where(first, kv_head_scales(tk, axes=(2,)),
                           entry.k_scale[pages])
        row_vs = jnp.where(first, kv_head_scales(tv, axes=(2,)),
                           entry.v_scale[pages])
        scales = {"k_scale": entry.k_scale.at[pages].set(row_ks),
                  "v_scale": entry.v_scale.at[pages].set(row_vs)}
        wk, wv = quantize_kv(tk, row_ks), quantize_kv(tv, row_vs)
    else:
        wk, wv = k[:, 0].astype(ck.dtype), v[:, 0].astype(cv.dtype)
    new = entry._replace(k=ck.at[at].set(wk), v=cv.at[at].set(wv), **scales)
    o = kernels.paged_flash_decode(
        q, new.k, new.v, decode_live_lengths(pos, b, live=live), ptab,
        mesh=mesh, **scales)
    return o, new


def _lanes(x, entry):
    """``x`` widened with noughts to a latent entry's whole lanes, in its
    dtype."""
    pad = entry.rows.shape[-1] - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]).astype(
        entry.rows.dtype)


def write_latent_rows(entry, rows, pos):
    """The :class:`LatentRows` entry with this call's ``rows`` (B, T, dk)
    written from the scalar ``pos`` on: a prefill, a chunk or a resume,
    ``generate()``'s steps."""
    return LatentRows(jax.lax.dynamic_update_slice(
        entry.rows, _lanes(rows, entry), (0, pos, 0)))


def _latent_step(entry, q, k, v, pos, live, *, window, sink, name, mesh,
                 rolled, scale=None):
    from mmlspark_tpu.ops import flash_attention as kernels

    if window is not None or rolled or scale is None:
        raise ParamError(
            "latent rows are read over every position, at the scale the "
            "block gives (their width is not the scores'); got window "
            f"{window}, rolled {rolled}, scale {scale}")
    b, t = q.shape[:2]
    dv = v.shape[-1]
    q = _lanes(q, entry)
    if jnp.ndim(pos) and t == 1:
        # the engine's fused step: the row written in place, each live
        # row fetched once for its keys and its values
        rows = kernels.latent_row_write(
            entry.rows, _lanes(k[:, 0, 0], entry), pos)
        o = kernels.flash_decode_grouped(
            q, rows[:, None], None, decode_live_lengths(pos, b, live=live),
            scale=scale, name=name, values_in_keys=dv)
        return o, LatentRows(rows)
    if jnp.ndim(pos):
        raise ParamError("per-row cache positions (the serve engine's "
                         "fused decode step) are single-token")
    # generate()'s steps, a chunk or a resume against a live prefix: a
    # plain write and a dense read, the values a slice of the keys
    new = write_latent_rows(entry, k[:, :, 0], pos)
    keys = new.rows[:, :, None]
    return dense_attention(q, keys, keys[..., :dv], causal=True,
                           q_offset=pos, scale=scale), new


def _causal_taps(g, before, taps):
    """``c_t = sum_j taps[j] * g_{t - (K - 1) + j}`` over ``g`` (B, T, W)
    with ``before`` (B, K - 1, W) the inputs ahead of its first position:
    an explicit sum over shifted copies, in float32."""
    k, t = taps.shape[0], g.shape[1]
    padded = jnp.concatenate((before, g), axis=1).astype(jnp.float32)
    taps = taps.astype(jnp.float32)
    return sum(taps[j] * padded[:, j:j + t] for j in range(k))


def state_step(entry, proj, taps, pos, live=None, *,
               name: str | None = None):
    """A gated short convolution over this call's positions and the state
    ``entry`` holds. ``proj`` (B, T, 3 * W) is the layer's input
    projection ``[b ; c ; u]``, ``taps`` (K, W) with ``taps[K - 1]`` on
    the current position: ``g = b * u``, ``conv_t = sum_j taps[j] * g_{t -
    (K - 1) + j}``, ``y = c * conv``. Returns ``(y (B, T, W) in proj's
    dtype, new entry)``. ``g`` is rounded to the entry's dtype BEFORE the
    sum, so a position's output is the same whether its predecessors come
    from this call or from the state.

    A :class:`StateRows` entry (linear, ``g`` at every position) takes a
    scalar ``pos``: a prefill, a chunk or a resume against a live prefix,
    ``generate()``'s steps; the ``K - 1`` rows before ``pos`` are read
    (nought before position 0) and this call's written from ``pos`` on,
    under ``jax.named_scope("conv_prefill")`` where ``T`` > 1. A
    :class:`SlotState` entry takes the engine's fused step: one token a
    slot, the slot's rows shifted in place by the ``conv_decode`` kernel
    (``name`` is what a trace shows), dead slots (``live`` False)
    untouched."""
    k, w = taps.shape
    if isinstance(entry, SlotState):
        from mmlspark_tpu.ops.conv_decode import conv_decode

        if proj.shape[1] != 1 or not jnp.ndim(pos):
            raise ParamError(_REFUSALS[SlotState])
        if live is None:
            live = jnp.ones((proj.shape[0],), bool)
        y, rows = conv_decode(proj[:, 0], entry.rows, taps, live, name=name)
        return y[:, None], SlotState(rows)
    if not isinstance(entry, StateRows) or jnp.ndim(pos):
        raise ParamError(
            "a short convolution steps over StateRows at a scalar position "
            "or over a pool's SlotState at per-row positions; got a "
            f"{type(entry).__name__} entry, pos of rank {jnp.ndim(pos)}")
    f32 = jnp.float32
    b_gate, c_gate, u = (proj[..., j * w:(j + 1) * w].astype(f32)
                         for j in range(3))
    g = (b_gate * u).astype(entry.rows.dtype)
    # the K - 1 inputs before ``pos``, row by row: nought before position 0
    before = jnp.concatenate([
        jnp.where(at >= 0, jax.lax.dynamic_slice_in_dim(
            entry.rows, jnp.maximum(at, 0), 1, axis=1), 0)
        for at in (pos - (k - 1) + j for j in range(k - 1))], axis=1)
    new = StateRows(jax.lax.dynamic_update_slice(entry.rows, g, (0, pos, 0)))
    with (jax.named_scope("conv_prefill") if proj.shape[1] > 1
          else contextlib.nullcontext()):
        conv = _causal_taps(g, before, taps)
    return (c_gate * conv).astype(proj.dtype), new


_STEPS = {Int8Rows: _int8_rows_step, HeadMajorKV: _head_major_step,
          LatentRows: _latent_step, PagedKV: _paged_step,
          PagedInt8KV: _paged_step}


def decode_step(entry, q, k, v, pos, live=None, *, window=None, sink=None,
                name=None, mesh=None, rolled: bool = False, scale=None):
    """One decode step over ``entry``, whatever its layout: append this
    step's K/V row for every slot at ``pos`` and attend ``q`` over the
    live rows. ``q`` is (B, 1, H, dk), ``k``/``v`` (B, 1, hk, d); ``pos``
    is (B,) per-row positions (the serve engine's fused decode step,
    which every pool-only layout requires) or, for linear rows, a scalar
    (``generate()``, ``rolled`` where its buffers are circular). ``live``
    ((B,) bool) zeroes dead rows' lengths, so the length-aware kernels
    skip their cache traffic. The block's static facts: its ``window``
    (None: full attention), its learned ``sink`` (head-major entries
    only), the ``name`` its decode kernel has in a trace, its ``mesh``.
    Returns ``(o, new entry)``: ``o`` (B, 1, H, dv), the entry of the
    same type and leaves.

    A :class:`LatentRows` entry takes ``k`` (B, T, 1, dk), this call's
    rows ``[c ; k_rope]``, and ``v`` their first ``dv`` columns (only its
    width is read: nothing is stored twice); ``q`` (B, T, H, dk) is the
    ABSORBED query and ``scale`` the scores' own, which the rows' width
    does not give. ``T`` > 1 at a scalar ``pos`` is a chunk or a resume;
    ``o`` is (B, T, H, dv) in the latent space."""
    if sink is not None and not isinstance(entry, HeadMajorKV):
        raise ParamError(
            "only head-major entries are read with a learned sink; got a "
            f"{type(entry).__name__} entry")
    if scale is not None and not isinstance(entry, LatentRows):
        raise ParamError(
            "only latent entries are read at a given scale; got a "
            f"{type(entry).__name__} entry")
    step = _STEPS.get(type(entry), _linear_step)
    return step(entry, q, k, v, pos, live, window=window, sink=sink,
                name=name, mesh=mesh, rolled=rolled,
                **({} if scale is None else {"scale": scale}))
