"""DeepSeek sparse attention's device parts: the lightning indexer's scores
and their top-k, and the latent read over the rows a query chose.

The indexer (DeepSeek-V3.2's) scores every earlier position ``s`` for a
query at ``t`` as ``I_{t,s} = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)``: a
few heads ``j`` of small queries, ONE key a position (the index-key cache,
:class:`mmlspark_tpu.ops.kv_cache.IndexedRows`), per-head weights ``w``.
The query then attends over the ``k`` positions of highest score alone,
``min(k, t + 1)`` of them.

:func:`index_scores` is the scores' Pallas kernel: a grid step takes a
block of query rows and a block of keys, multiplies every head's queries
against the keys at once, and sums the heads' ReLUs weighted; key blocks
past a query block's last position are neither fetched nor multiplied.
:func:`index_select` adds the top-k, in query blocks so that a prefill's
scores never lie whole in memory; a prefill block's top-k runs over the
key blocks it can see alone (:func:`running_top_k`).
:func:`sparse_latent_read` gathers each query's chosen latent rows and
reads them with the grouped decode kernel
(:func:`mmlspark_tpu.ops.flash_attention.flash_decode_grouped`,
``values_in_keys``): a decode step's 16 slots each read rows of their own.
:func:`staged_latent_read` is a prefill's: all its queries read ONE
request's rows, so a call of 16 queries copies the request's live prefix
into VMEM once, in whole blocks, and gathers each query's rows from there
inside the kernel, where XLA's gather fetched each chosen row from HBM
(16 x 2,048 rows a call, as many as the whole prefix holds at 32k).
Under ``jax.named_scope`` and by the kernels' names a trace shows
``index_decode`` / ``index_prefill`` and ``attn_dsa_decode`` /
``attn_dsa_prefill``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops.attention import KERNEL_NEG_INF
from mmlspark_tpu.ops.flash_attention import (
    _DECODE_GROUP_BLOCK,
    LANES,
    _decode_block,
    _interpret,
    _with_sink,
    flash_decode_grouped,
)

#: keys a grid step of the score kernel: whole lanes of the scores' block
INDEX_KEY_BLOCK = 512
#: query rows a grid step of the score kernel in a prefill (a decode step
#: has one a slot): 64 rows x 32 heads x 512 keys of float32 is 4 MiB
INDEX_ROW_BLOCK = 64
#: a prefill's queries whose scores lie in memory at once, (rows, cache)
INDEX_QUERY_CHUNK = 512
#: keys a step of a prefill's running top-k merges into the choice so far:
#: a sort of 2,048 + 4,096 a step, over the live blocks alone
INDEX_MERGE_BLOCK = 4096
#: queries a call of the read kernel: a decode step's 16 slots, or 16 of a
#: prefill's queries, which share the live prefix the call stages
READ_QUERY_CHUNK = 16
#: rows of the live prefix one staging copy moves from HBM into VMEM: the
#: prefix is staged in whole blocks up to the call's last query, so at most
#: a block less one of dead rows is fetched (1.3 MB at 640 lanes)
STAGE_BLOCK = 1024
#: the most VMEM a call's staged rows may take (a v5e core has 128 MiB):
#: a cache of 32,768 rows of 640 lanes in bfloat16 takes 42 MB; a longer
#: one is read through XLA's gather (:func:`sparse_latent_read`)
STAGE_VMEM = 80 << 20
#: rows of a query's choice a step of the kernel's gather copies: whole
#: tiles of the chosen rows' buffer (8 words of two packed bfloat16 rows)
GATHER_ROWS = 32

NEG_INF = float("-inf")


def index_scores_reference(q, w, keys, first):
    """What :func:`index_scores` computes, in plain ``jax.numpy``."""
    t = q.shape[1]
    s = jnp.einsum("bthd,bld->bthl", q.astype(jnp.float32),
                   keys.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    s = (jnp.maximum(s, 0.0) * w[..., None].astype(jnp.float32)).sum(2)
    qpos = jnp.asarray(first)[:, None] + jnp.arange(t)[None, :]
    kpos = jnp.arange(keys.shape[1])
    return jnp.where(kpos[None, None, :] <= qpos[..., None], s, NEG_INF)


def _score_kernel(first_ref, q_ref, w_ref, k_ref, o_ref, *, bt: int,
                  heads: int, bl: int):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    top = first_ref[b] + i * bt + bt - 1   # the block's last query position

    @pl.when(j * bl <= top)
    def _score():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bt * heads, bl)
        s = jnp.maximum(s, 0.0) * w_ref[0]
        s = s.reshape(bt, heads, bl).sum(axis=1)         # (bt, bl)
        qpos = (first_ref[b] + i * bt
                + jax.lax.broadcasted_iota(jnp.int32, (bt, bl), 0))
        kpos = j * bl + jax.lax.broadcasted_iota(jnp.int32, (bt, bl), 1)
        o_ref[0] = jnp.where(kpos <= qpos, s, NEG_INF)

    @pl.when(j * bl > top)
    def _dead():
        o_ref[0] = jnp.full((bt, bl), NEG_INF, jnp.float32)


def index_scores(q, w, keys, first, *, interpret: bool | None = None,
                 name: str | None = None):
    """Index scores ``(B, T, L)`` float32 of queries ``q`` (B, T, Hi, D)
    with head weights ``w`` (B, T, Hi) over index keys ``keys`` (B, L, D):
    ``sum_j w_j ReLU(q_j . k_l)`` where key ``l`` lies at or before the
    query's position ``first[b] + t``, ``-inf`` elsewhere. ``q`` and
    ``keys`` share one dtype; the products accumulate in float32."""
    return _index_scores(q, w, keys, jnp.asarray(first, jnp.int32),
                         interpret=_interpret(interpret),
                         name=name or "index_scores")


@partial(jax.jit, static_argnames=("interpret", "name"))
def _index_scores(q, w, keys, first, *, interpret: bool, name: str):
    b, t, heads, d = q.shape
    length = keys.shape[1]
    bt = INDEX_ROW_BLOCK if t % INDEX_ROW_BLOCK == 0 else t
    bl = INDEX_KEY_BLOCK if length % INDEX_KEY_BLOCK == 0 else length
    if keys.shape != (b, length, d) or w.shape != (b, t, heads):
        raise ValueError(
            f"index_scores takes q (B, T, Hi, D), w (B, T, Hi) and keys (B, "
            f"L, D), got {q.shape}, {w.shape}, {keys.shape}")

    def key_im(bi, i, j, first):
        # a key block past the query block's last position is not fetched:
        # the block before it stays where it is
        last = (first[bi] + i * bt + bt - 1) // bl
        return (bi, jnp.minimum(j, jnp.maximum(last, 0)), 0)

    return pl.pallas_call(
        partial(_score_kernel, bt=bt, heads=heads, bl=bl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // bt, length // bl),
            in_specs=[
                pl.BlockSpec((1, bt * heads, d),
                             lambda bi, i, j, first: (bi, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bt * heads, 1),
                             lambda bi, i, j, first: (bi, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bl, d), key_im, memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, bt, bl),
                                   lambda bi, i, j, first: (bi, i, j),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, length), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=bool(interpret),
        name=name,
    )(first, q.reshape(b, t * heads, d),
      w.astype(jnp.float32).reshape(b, t * heads, 1), keys)


def _first_k(scores, at, k: int):
    """The ``k`` highest of ``scores`` and their positions ``at``, highest
    first, the lower position first among equals: one stable sort of the
    pair, no gather."""
    neg, at = jax.lax.sort((-scores, at), dimension=scores.ndim - 1,
                           num_keys=1, is_stable=True)
    return -neg[..., :k], at[..., :k]


def running_top_k(scores, k: int, live):
    """``jax.lax.top_k(scores, k)[1]`` for scores ``(..., L)`` whose columns
    from ``live`` (a traced count) on are ``-inf``: the choice so far is
    merged with one block of ``INDEX_MERGE_BLOCK`` columns at a time, over
    the live blocks alone, so a sort never spans the dead part of the
    cache. The same positions in the same order: the choice so far stands
    before the block in every merge, so equal scores keep the lower
    position first, as ``top_k`` does."""
    length, m = scores.shape[-1], INDEX_MERGE_BLOCK
    if length <= m or length % m or m < k:
        return jax.lax.top_k(scores, k)[1].astype(jnp.int32)
    at = jax.lax.broadcasted_iota(jnp.int32, scores.shape[:-1] + (m,),
                                  scores.ndim - 1)
    blocks = jnp.clip((live + m - 1) // m, 1, length // m)

    def merge(j, carry):
        block = jax.lax.dynamic_slice_in_dim(scores, j * m, m, axis=-1)
        return _first_k(jnp.concatenate((carry[0], block), -1),
                        jnp.concatenate((carry[1], at + j * m), -1), k)

    return jax.lax.fori_loop(1, blocks, merge,
                             _first_k(scores[..., :m], at, k))[1]


def index_select(q, w, keys, first, topk: int, *, name: str):
    """The ``topk`` positions of highest index score for every query
    (:func:`index_scores`): ``(B, T, k)`` int32, ``k = min(topk, L)``, in
    descending order of score, so that a query at position ``p`` finds its
    ``min(k, p + 1)`` live choices first (the rest are positions it may not
    see, which no read takes). A prefill's queries are scored and chosen
    ``INDEX_QUERY_CHUNK`` at a time, each block's choice a running top-k
    over the key blocks at or before its last query
    (:func:`running_top_k`); a decode step's one query a slot sorts its
    slot's whole row."""
    b, t = q.shape[:2]
    k = min(int(topk), keys.shape[1])
    first = jnp.asarray(first, jnp.int32)

    def choose(qc, wc, at):
        scores = index_scores(qc, wc, keys, at, name=name)
        if t == 1:
            return jax.lax.top_k(scores, k)[1].astype(jnp.int32)
        return running_top_k(scores, k, jnp.max(at) + qc.shape[1])

    with jax.named_scope(name):
        if t <= INDEX_QUERY_CHUNK or t % INDEX_QUERY_CHUNK:
            return choose(q, w, first)
        n = t // INDEX_QUERY_CHUNK
        chunks = jax.lax.map(
            lambda c: choose(c[0], c[1], first + c[2] * INDEX_QUERY_CHUNK),
            (q.reshape(b, n, INDEX_QUERY_CHUNK, *q.shape[2:]).swapaxes(0, 1),
             w.reshape(b, n, INDEX_QUERY_CHUNK, -1).swapaxes(0, 1),
             jnp.arange(n, dtype=jnp.int32)))
        return chunks.swapaxes(0, 1).reshape(b, t, k)


def sparse_latent_read(q, rows, sel, count, *, sink=None, scale: float,
                       values: int, name: str):
    """Attention of absorbed queries ``q`` (B, T, H, W) over the latent
    rows ``rows`` (B, L, W) that ``sel`` (B, T, k) chose, the first
    ``count`` (B, T) of each query's choices: ``softmax(scale q . r)``
    with ``sink`` ((H,), or None) in the denominator, over the values, the
    rows' first ``values`` columns. Returns (B, T, H, values) in ``q``'s
    dtype. The chosen rows are gathered a block of queries at a time and
    read by the grouped decode kernel, each query a slot of its own."""
    b, t, h, width = q.shape
    k = sel.shape[-1]

    def read(qc, selc, cc, bidx):
        # (n, k, W): every query's own rows, read as a slot's cache
        picked = rows[bidx[:, None], jnp.maximum(selc, 0)]
        return flash_decode_grouped(
            qc[:, None], picked[:, None], None, cc, sink=sink, scale=scale,
            name=name, values_in_keys=values)[:, 0]

    n = b * t
    flat = (q.reshape(n, h, width), sel.reshape(n, k),
            count.reshape(n).astype(jnp.int32),
            jnp.repeat(jnp.arange(b, dtype=jnp.int32), t))
    with jax.named_scope(name):
        if n <= READ_QUERY_CHUNK or n % READ_QUERY_CHUNK:
            out = read(*flat)
        else:
            c = n // READ_QUERY_CHUNK
            out = jax.lax.map(
                lambda args: read(*args),
                tuple(a.reshape(c, READ_QUERY_CHUNK, *a.shape[1:])
                      for a in flat))
    return out.reshape(b, t, h, values)


def stage_block(rows) -> int:
    """The rows a staging copy of :func:`staged_latent_read` moves for
    latent rows ``rows`` (B, L, W) (a shape and dtype do), or 0 where the
    read cannot stage them: the kernel reads bfloat16 rows through a
    32-bit view (a word holds two rows), so ``L`` has to fill whole tiles
    of 16 rows, and the staged rows have to fit ``STAGE_VMEM``."""
    length, width = rows.shape[-2:]
    nbytes = length * (-(-width // 128) * 128) * jnp.dtype(rows.dtype).itemsize
    if (jnp.dtype(rows.dtype).itemsize != 2 or length % 16
            or nbytes > STAGE_VMEM):
        return 0
    return max(n for n in range(16, min(STAGE_BLOCK, length) + 1, 16)
               if length % n == 0)


def prefill_read_rows(first: int, t: int, rows, k: int) -> tuple[int, int]:
    """``(staged, picked)`` of one sparse layer's prefill read of ``t``
    queries from position ``first`` over latent rows ``rows`` (B = 1), each
    query choosing ``k`` rows: the rows :func:`staged_latent_read` copies
    into VMEM, summed over its calls (0 where it cannot stage,
    :func:`stage_block`), and the rows its queries read, ``min(k, p + 1)``
    a query at position ``p``. Counted on the host, as the kernel does."""
    length, k = rows.shape[-2], min(k, rows.shape[-2])
    lo, hi = min(first, k), min(first + t, k)   # p + 1 rows from lo to hi
    picked = (hi * (hi + 1) - lo * (lo + 1)) // 2 + k * (t - hi + lo)
    stage = stage_block(rows)
    if not stage:
        return 0, picked
    m = min(READ_QUERY_CHUNK, t)
    staged = sum(-(-min(first + min(c + m, t), length) // stage) * stage
                 for c in range(0, t, m))
    return staged, picked


def _staged_read_kernel(at_ref, count_ref, word_ref, shift_ref, q_ref,
                        sink_ref, rows_hbm, o_ref, staged, keys, m_scr, l_scr,
                        acc_scr, sem, *,
                        scale: float, blk: int, values: int, stage: int):
    # grid step i reads query i of the call. Step 0 first copies the live
    # prefix, rows [0, at[1]) of batch row at[0], into ``staged`` in whole
    # blocks of ``stage`` rows. Each step then gathers its query's chosen
    # rows into ``keys`` and reads them in blocks of ``blk``, as
    # flash_decode_grouped reads a slot's cache: the same products, the
    # same online softmax, the same sink. The rows are bfloat16, two to a
    # 32-bit word (row 2j in the low half): Mosaic loads one word row at
    # any offset, not one packed row, so a row's word is loaded, shifted
    # and masked, and two rows make a word of ``keys``
    i = pl.program_id(0)
    live = (at_ref[1] + stage - 1) // stage * stage   # rows staged

    def copy(j):
        return pltpu.make_async_copy(
            rows_hbm.at[at_ref[0], pl.ds(j * stage, stage)],
            staged.at[pl.ds(j * stage, stage)], sem)

    @pl.when(i == 0)
    def _stage():
        # rows of a block past a query's choices are read with weight 0:
        # they must hold numbers, never what VMEM held before
        keys[...] = jnp.zeros_like(keys)

        def start(j, c):
            copy(j).start()
            return c

        def wait(j, c):
            copy(j).wait()
            return c

        jax.lax.fori_loop(0, live // stage, start, 0)
        jax.lax.fori_loop(0, live // stage, wait, 0)

    count = count_ref[i]
    words = staged.bitcast(jnp.uint32)

    def row(r):
        # choice r in the low half of a word, the high half not yet
        # cleared: the word that holds the row, and the shift that brings
        # it down, come computed (the scalar unit's work a row bounds the
        # gather), the word as a row of ``words``
        word = words[pl.ds(word_ref[0, 0, r], 1), :]
        return jax.lax.shift_right_logical(
            word, shift_ref[0, 0, r].astype(jnp.uint32))

    def gather(g, c):
        # whole tiles of ``keys``: its address is the tile's, once a tile
        for t in range(GATHER_ROWS // 16):
            tile = g * (GATHER_ROWS // 16) + t
            for j in range(8):
                r = 16 * tile + 2 * j
                keys[tile, pl.ds(j, 1), :] = (row(r) & 0xFFFF) | (
                    row(r + 1) << 16)
        return c

    jax.lax.fori_loop(0, (count + GATHER_ROWS - 1) // GATHER_ROWS, gather, 0)
    m_scr[...] = jnp.full_like(m_scr, KERNEL_NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    for kb in range(word_ref.shape[-1] // blk):

        @pl.when(kb * blk < count)
        def _update():
            k = pltpu.bitcast(
                keys[pl.ds(kb * blk // 16, blk // 16)].reshape(
                    blk // 2, keys.shape[-1]), staged.dtype)
            s = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos >= count, KERNEL_NEG_INF, s)
            m_prev = m_scr[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = jnp.broadcast_to(
                l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True),
                l_scr.shape)
            v = k[:, :values]
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    o_ref[0] = _with_sink(m_scr[:, :1], l_scr[:, :1], acc_scr[...],
                          sink_ref[:, :1]).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("scale", "values", "interpret", "name"))
def _staged_read(q, rows, sel, count, at, sink, *, scale: float, values: int,
                 interpret: bool, name: str):
    """One call: queries ``q`` (m, H, W) of batch row ``at[0]`` over rows
    ``[0, at[1])`` of ``rows`` (B, L, W), each its choice ``sel`` (m, k)
    and ``count`` (m,). Returns (m, H, values)."""
    m, h, width = q.shape
    length, k = rows.shape[1], sel.shape[1]
    # the grouped kernel's blocks, in whole tiles of ``keys`` (16 rows)
    blk = -(-_decode_block(k, _DECODE_GROUP_BLOCK) // 16) * 16
    # whole steps of the gather and blocks of the read: a choice padded
    # past k is never read
    kp = -(-k // math.lcm(GATHER_ROWS, blk)) * math.lcm(GATHER_ROWS, blk)
    stage = stage_block(rows)
    # a choice past a query's count may name any row: it is held to the
    # rows the call stages
    held = (at[1] + stage - 1) // stage * stage
    sel = jnp.clip(sel, 0, held - 1)
    # the word that holds a chosen row, and the shift to its half
    word, shift = (jnp.pad(a, ((0, 0), (0, kp - k)))[:, None]
                   for a in (sel >> 1, (sel & 1) * 16))
    lanes = -(-width // LANES) * LANES
    # the staged rows and the chosen ones, and room for the blocks of q
    # and of the result, the scratch and a block's float32 scores
    vmem = (length + kp) * lanes * 2 + (8 << 20)
    sink = jnp.broadcast_to(
        (jnp.full((h,), KERNEL_NEG_INF, jnp.float32) if sink is None
         else sink.astype(jnp.float32))[:, None], (h, LANES))
    return pl.pallas_call(
        partial(_staged_read_kernel, scale=scale, blk=blk, values=values,
                stage=stage),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m,),
            in_specs=[
                pl.BlockSpec((1, 1, kp), lambda i, at, cnt: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, kp), lambda i, at, cnt: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, h, width), lambda i, at, cnt: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((h, LANES), lambda i, at, cnt: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, values),
                                   lambda i, at, cnt: (i, 0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((length, width), rows.dtype),   # the prefix
                pltpu.VMEM((kp // 16, 8, width), jnp.uint32),  # chosen
                pltpu.VMEM((h, LANES), jnp.float32),       # running max
                pltpu.VMEM((h, LANES), jnp.float32),       # normalizer
                pltpu.VMEM((h, values), jnp.float32),      # accumulator
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, h, values), q.dtype),
        # step 0 stages what the later steps read: one core, in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,), vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(at, count, word, shift, q, sink, rows)


def staged_latent_read(q, rows, sel, count, first, *, sink=None,
                       scale: float, values: int, name: str,
                       interpret: bool | None = None):
    """:func:`sparse_latent_read` for a prefill: the queries ``q`` (B, T,
    H, W) of batch row ``b`` lie at positions ``first[b] + t`` and read
    rows at or before their own. ``READ_QUERY_CHUNK`` queries a call (all
    ``T`` where fewer): a call copies its batch row's rows up to its last
    query into VMEM once (:func:`stage_block` rows a copy) and gathers each
    query's chosen rows from there; the rows are never copied in HBM. The
    same products and online softmax as :func:`sparse_latent_read`'s, in
    the same blocks; rows :func:`stage_block` cannot stage are read by
    it."""
    b, t, h, width = q.shape
    k = sel.shape[-1]
    if not stage_block(rows):
        return sparse_latent_read(q, rows, sel, count, sink=sink, scale=scale,
                                  values=values, name=name)
    m = min(READ_QUERY_CHUNK, t)
    pad = -t % m
    calls = (t + pad) // m
    if pad:
        # queries that read nothing (count 0), cut from the result
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sel = jnp.pad(sel, ((0, 0), (0, pad), (0, 0)))
        count = jnp.pad(count, ((0, 0), (0, pad)))
    first = jnp.broadcast_to(jnp.asarray(first, jnp.int32), (b,))
    # a call's batch row, and the rows up to its last query
    ends = jnp.minimum(jnp.arange(1, calls + 1, dtype=jnp.int32) * m, t)
    at = jnp.stack([jnp.repeat(jnp.arange(b, dtype=jnp.int32), calls),
                    jnp.minimum(first[:, None] + ends, rows.shape[1]
                                ).reshape(-1)], axis=1)

    def read(qc, selc, cc, atc):
        return _staged_read(qc, rows, selc, cc, atc, sink, scale=scale,
                            values=values, interpret=_interpret(interpret),
                            name=name)

    flat = (q.reshape(b * calls, m, h, width), sel.reshape(b * calls, m, k),
            count.reshape(b * calls, m).astype(jnp.int32), at)
    with jax.named_scope(name):
        if b * calls == 1:
            out = read(*(a[0] for a in flat))[None]
        else:
            out = jax.lax.map(lambda a: read(*a), flat)
    return out.reshape(b, t + pad, h, values)[:, :t]
