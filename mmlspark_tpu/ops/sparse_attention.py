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
``values_in_keys``): a decode step's query is one row a slot, and a
prefill's queries are read as slots of one row each, 16 at a time, so
that the gathered rows stay in VMEM as a decode step's do. Under
``jax.named_scope`` and by the kernels' names a trace shows
``index_decode`` / ``index_prefill`` and ``attn_dsa_decode`` /
``attn_dsa_prefill``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
#: a prefill's queries whose chosen rows are gathered at once: 16 x 2,048
#: rows of 640 lanes in bfloat16 is 42 MB, which the compiler keeps in
#: VMEM for the read kernel, as it does a decode step's 16 slots: the
#: gathered rows then never go to HBM and back
READ_QUERY_CHUNK = 16

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
    from mmlspark_tpu.ops.flash_attention import _interpret

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
    from mmlspark_tpu.ops.flash_attention import flash_decode_grouped

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
