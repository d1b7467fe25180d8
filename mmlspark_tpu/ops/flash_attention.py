"""Flash attention as Pallas TPU kernels — forward AND backward.

The hot op the reference never had (no attention code exists in the
reference tree — SURVEY.md §5): blockwise streaming-softmax attention that
keeps the running (max, normalizer, accumulator) in VMEM scratch across the
K-block grid dimension, so the (S, S) score matrix never hits HBM. Q/K/V
tiles stream HBM→VMEM via the grid BlockSpecs; every matmul feeds the MXU
native-dtype operands (bf16 in → f32 accumulate, the systolic array's fast
path — upcasting operands first would force multi-pass f32 matmuls), with
the softmax algebra kept in float32.

Backward pass (FlashAttention-2 recipe): the forward additionally emits the
per-row log-sum-exp (lanes-replicated, the same layout trick as the
reference pallas kernel in jax.experimental.pallas.ops.tpu.flash_attention),
and two Pallas kernels recompute P blockwise from (Q, K, LSE) —

  - dK/dV kernel: grid (batch·heads, k-block, q-block), accumulating
    ``dV += Pᵀ·dO`` and ``dK += dSᵀ·Q`` in VMEM scratch over the q dim;
  - dQ kernel: grid (batch·heads, q-block, k-block), accumulating
    ``dQ += dS·K`` over the k dim;

with ``dS = P ⊙ (dO·Vᵀ − D)`` and ``D = rowsum(dO ⊙ O)`` precomputed in
XLA. Memory stays O(S·d) end to end — nothing (S, S) is ever materialized
in either direction.

Block size. One grid step of the three kernels takes ``blk`` rows of Q
and ``blk`` rows of K of one (batch, head). ``_flash_block`` chooses
``blk`` from the sequence length, the head widths and the VMEM the tiles
need; ``block=`` overrides it (the tests do, to span several blocks). It
takes the most rows it may, the whole sequence up to 1,024: a step costs
0.4 us before it does anything, and rescales its accumulators once for
every block of K, so at 128 rows the steps took the time, not the
products. On a v5e, device time of one call at train-dp4's shape,
``(8, 1024, 16, 64)`` bfloat16, causal (``tools/flash_block_timing.py``,
chip run of PR 32; us):

    block    forward    dK/dV      dQ     all three
      128      3,306    3,089   2,995      9,391
      256      1,758    1,650   1,228      4,636
      512        973      827     714      2,514
    1,024        474      821     585      1,881

At 1,024 a call is one step a head and computes the masked upper
triangle too, yet is the fastest of the four; XLA's own attention
(``dense_attention``), forward and backward in one program, took 6,841.
Two heads a step at 1,024 were slower (forward 568), four at 512 no
faster (2,081 for the three) than one at 1,024. The forward kernel alone
at mimo-v2-flash's prefill (64 query heads, q/k 192, v 128) over 1,024,
2,048 and 4,096 rows: full layers 518, 1,676, 6,093 at a block of 512 and
277, 1,089, 3,634 at 1,024; window layers (128 rows, a sink) 536, 1,272,
3,160 and 285, 1,156, 2,781.

Off-TPU (the unit-test CPU mesh) the kernels run in interpreter mode, so
the same code path is tested everywhere.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# the finite in-kernel masking value (-inf minus -inf would poison the
# running max); ONE home for both masking conventions lives in
# ops/attention.py — see the note there before touching either
from mmlspark_tpu.ops.attention import KERNEL_NEG_INF as NEG_INF
from mmlspark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

LANES = 128
SUBLANES = 8  # min f32 sublane tile; single-row decode broadcasts to it

#: what one grid step of the forward and backward kernels may take of
#: VMEM (a v5e core has 128 MiB): the budget ``_flash_block`` holds a
#: block to, and the kernels' ``vmem_limit_bytes``, so that the compiler
#: does not refuse at its own default what the budget allows
_FLASH_VMEM = 48 << 20

# all three kernels share a (batch·heads, outer-block, streamed-block)
# grid: the first two dims own disjoint outputs/scratch, only the last
# carries accumulator state across iterations
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
    vmem_limit_bytes=_FLASH_VMEM,
)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


#: the most rows of Q and of K one grid step takes: train-dp4's whole
#: sequence (the timings are in the module's docstring)
_FLASH_BLOCK_MOST = 1024


def _flash_vmem_bytes(blk: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM one grid step holds at ``blk`` rows of Q and of K, by the
    widest of the three kernels (dK/dV), as VMEM holds the tiles: the
    minor dimension in whole lanes, every operand double-buffered."""
    wide = _round_up(max(d, dv), LANES)
    row = 6 * 2 * wide * itemsize   # Q, dO, K, V in; dK, dV out
    row += 2 * 2 * LANES * 4        # LSE and D, lanes-replicated float32
    row += 2 * wide * 4             # the two float32 accumulators
    # the (blk, blk) tiles a step makes: scores, P, dP, dS in float32,
    # P and dS again in the operands' dtype, the mask's two iotas
    return blk * row + blk * blk * (6 * 4 + 2 * itemsize)


def _flash_block(s: int, d: int, dv: int, itemsize: int) -> int:
    """Rows of Q and of K a grid step of the forward and both backward
    kernels takes, for ``s`` rows of ``d``-wide keys and ``dv``-wide
    values: the one place that chooses it (callers pass ``block=`` only
    to override).

    A sequence that fits one lane tile is one block, padded to whole
    sublanes. A longer one is padded to whole lane tiles and takes the
    most lane tiles, ``_FLASH_BLOCK_MOST`` rows at most, that divide the
    padded length, so that a larger block never adds padded rows, and
    that ``_flash_vmem_bytes`` puts inside ``_FLASH_VMEM``."""
    if s <= LANES:
        return _round_up(s, SUBLANES)
    tiles = _round_up(s, LANES) // LANES
    return LANES * max(
        n for n in range(1, min(tiles, _FLASH_BLOCK_MOST // LANES) + 1)
        if tiles % n == 0 and (n == 1 or _flash_vmem_bytes(
            n * LANES, d, dv, itemsize) <= _FLASH_VMEM))


def _kernel_axes(mesh, batch: int, kv_heads: int):
    """Mesh axes a kernel call splits its batch and head dims over.

    The TPU compiler cannot partition a Mosaic kernel by itself ("wrap
    the call in a shard_map"), so under a mesh every public op below
    runs its kernel per shard: batch rows over the data axis, heads
    over the model axis — the layout the Megatron param rules and the
    serve pools already give the operands. A dim the axis does not
    divide stays whole (the operand is gathered, as GSPMD would)."""
    def axis(name, size):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and size % n == 0 else None

    return axis(DATA_AXIS, batch), axis(MODEL_AXIS, kv_heads)


# ---------------------------------------------------------------------------
# masking geometry, shared by the forward kernel, both backward kernels,
# and the dead-block index-map clamps — one definition of which (query,
# key) pairs attend, in three granularities:
#   _block_live    — does K block ki intersect Q block qi's span at all?
#   _dead_mask     — per-element mask inside a (blk, blk) score tile
#   _live_k_range  — [lo, hi] of live K blocks for Q block qi (clamps)


def _block_live(qi, ki, *, causal: bool, window: int | None, blk: int):
    live = True
    if causal:
        live = ki * blk <= qi * blk + blk - 1
    if window is not None:
        # the OLDEST query row in block qi (pos qi*blk) attends the
        # block's oldest keys, >= qi*blk - window + 1; a K block whose
        # last position is older than even that is fully outside the
        # window for every row in the block
        live = live & (ki * blk + blk - 1 >= qi * blk - window + 1)
    return live


def _dead_mask(qi, ki, shape, *, causal: bool, window: int | None,
               seq_len: int, blk: int, with_q_pad: bool = False):
    """Boolean (blk, blk) mask of entries that must NOT attend (always
    includes the padded-key mask; callers skip the call entirely on the
    pad-free non-causal no-window path)."""
    need_q = causal or window is not None or with_q_pad
    kpos = ki * blk + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    dead = kpos >= seq_len  # padded keys never attend
    if need_q:
        qpos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        if with_q_pad:
            dead = dead | (qpos >= seq_len)
        if causal:
            dead = dead | (kpos > qpos)
        if window is not None:
            dead = dead | (kpos <= qpos - window)
    return dead


def _live_k_range(qi, *, window: int | None, blk: int):
    """[lo, hi_unbounded) of K blocks live for Q block qi under causal
    (+ optional window) masking; used to clamp streamed-side index maps
    so dead iterations re-reference a resident tile (no DMA)."""
    hi = qi  # causal: nothing right of the diagonal block
    if window is None:
        lo = jnp.zeros_like(qi)
    else:
        lo = jnp.maximum(0, (qi * blk - window + 1) // blk)
    return lo, hi


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, window: int | None, blk: int,
                seq_len: int, with_lse: bool, masked: bool,
                has_sink: bool = False):
    # a learned per-head sink (inference only) rides in as one more
    # operand, a (1, 1, LANES) lanes-replicated logit of this head
    sink_ref = None
    if has_sink:
        sink_ref, *rest = rest
    o_ref, *rest = rest
    # the LSE residual exists only on the grad path (with_lse): the
    # inference-only forward skips computing AND writing the
    # lanes-replicated f32 (bh, s, 128) tensor, which would otherwise
    # cost 4x the HBM write bytes of the bf16 output itself
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = _block_live(qi, ki, causal=causal, window=window, blk=blk)

    @pl.when(live)
    def _update():
        # MXU wants NATIVE-dtype operands with f32 accumulation: bf16 in,
        # f32 out is the systolic array's fast path, while upcasting the
        # operands first forces multi-pass f32 matmuls at a fraction of
        # the throughput (f32 inputs still work — they just skip the cast)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (blk, blk) f32
        if masked or causal or window is not None:
            s = jnp.where(
                _dead_mask(qi, ki, s.shape, causal=causal, window=window,
                           seq_len=seq_len, blk=blk),
                NEG_INF, s,
            )

        m_prev = m_scr[:, :1]  # (blk, 1), lanes replicated
        m_cur = s.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:, :1]
        if has_sink:
            # the sink joins the denominator only: rescale to the
            # maximum taken with it (ops/attention.py sink_denominator)
            o_ref[0] = _with_sink(
                m_scr[:, :1], l, acc_scr[:], sink_ref[0][:, :1]
            ).astype(o_ref.dtype)
            return
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype
        )
        if with_lse:
            # log-sum-exp residual for the backward; padded rows (l == 0)
            # get NEG_INF so recomputed p vanishes there
            lse_ref[0] = jnp.where(
                l_scr[:] == 0.0,
                NEG_INF,
                m_scr[:] + jnp.log(
                    jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
                ),
            )


def _with_sink(m, l, acc, sink):
    """The normalised output with a per-row sink logit joined to the
    denominator: ``m``, ``l``, ``sink`` broadcast against ``acc``'s
    rows. A sink of ``NEG_INF`` adds nothing; a row that saw no key
    comes out as zeros."""
    m_new = jnp.maximum(m, sink)
    corr = jnp.exp(m - m_new)
    denom = l * corr + jnp.exp(sink - m_new)
    return acc * corr / jnp.where(denom == 0.0, 1.0, denom)


def _to_bh(t, s_pad):
    b, s, h, d = t.shape
    t = jnp.moveaxis(t, 2, 1).reshape(b * h, s, d)
    if s_pad != s:
        t = jnp.pad(t, ((0, 0), (0, s_pad - s), (0, 0)))
    return t


def _from_bh(t, b, h, s):
    return jnp.moveaxis(t[:, :s].reshape(b, h, s, -1), 1, 2)


def _block_rows(block: int | None, q, v) -> int:
    """The block of one call: the caller's ``block`` (at most the
    sequence in whole sublanes), or the module's choice."""
    s = q.shape[1]
    if block is None:
        return _flash_block(s, q.shape[-1], v.shape[-1], q.dtype.itemsize)
    return min(block, _round_up(s, SUBLANES))


def _flash_forward(q, k, v, *, causal: bool, window: int | None,
                   scale: float, block: int | None, interpret: bool,
                   with_lse: bool = True, sink=None):
    b, s, h, d = q.shape
    dv = v.shape[-1]  # the values (and the output) may differ in width
    # grouped-query attention: K/V may carry fewer heads (h_kv) than Q;
    # the group factor g maps query-head grid index bh -> kv row bh // g
    # in the index maps, so K/V are never materialized per query head
    g = h // k.shape[2]
    blk = _block_rows(block, q, v)
    s_pad = _round_up(s, blk)
    qb, kb, vb = (_to_bh(t, s_pad) for t in (q, k, v))
    n_blk = s_pad // blk
    grid = (b * h, n_blk, n_blk)
    tile = lambda im, width=d: pl.BlockSpec((1, blk, width), im,
                                            memory_space=pltpu.VMEM)
    lse_tile = pl.BlockSpec((1, blk, LANES), lambda bh, i, j: (bh, i, 0),
                            memory_space=pltpu.VMEM)
    out_shape = [jax.ShapeDtypeStruct((b * h, s_pad, dv), q.dtype)]
    out_specs = [tile(lambda bh, i, j: (bh, i, 0), dv)]
    if with_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_pad, LANES), jnp.float32)
        )
        out_specs.append(lse_tile)
    # causal: K blocks above the diagonal (j > i) are fully masked — their
    # compute is skipped via pl.when, and clamping the index map to the
    # last LIVE block makes consecutive dead iterations re-reference the
    # resident tile, so the pipeline skips their HBM→VMEM DMAs too
    # (~halving causal K/V traffic)
    if causal:
        def kv_im(bh, i, j):
            lo, hi = _live_k_range(i, window=window, blk=blk)
            return (bh // g, jnp.clip(j, lo, hi), 0)
    else:
        kv_im = lambda bh, i, j: (bh // g, j, 0)  # noqa: E731
    in_specs = [
        tile(lambda bh, i, j: (bh, i, 0)),  # Q: row block
        tile(kv_im),                        # K: column block
        tile(kv_im, dv),                    # V: column block
    ]
    operands = [qb, kb, vb]
    extra = {}
    if sink is not None:
        # one logit a query head, lanes-replicated, row bh = batch*h + head
        operands.append(jnp.broadcast_to(
            jnp.tile(sink.astype(jnp.float32), b)[:, None, None],
            (b * h, 1, LANES),
        ))
        in_specs.append(pl.BlockSpec((1, 1, LANES),
                                     lambda bh, i, j: (bh, 0, 0),
                                     memory_space=pltpu.VMEM))
        extra["has_sink"] = True
    res = pl.pallas_call(
        partial(_fwd_kernel, scale=scale, causal=causal, window=window,
                blk=blk, seq_len=s, with_lse=with_lse,
                masked=s_pad != s, **extra),
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((blk, LANES), jnp.float32),  # running max
            pltpu.VMEM((blk, LANES), jnp.float32),  # running normalizer
            pltpu.VMEM((blk, dv), jnp.float32),     # accumulator
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(*operands)
    if with_lse:
        out, lse = res
        return _from_bh(out, b, h, s), lse
    return _from_bh(res[0], b, h, s), None


# ---------------------------------------------------------------------------
# backward


def _recompute_p(q_ref, k_ref, lse_ref, qi, ki, *, scale, causal, window,
                 blk, seq_len):
    """Rebuild the (blk_q, blk_k) probability block from Q, K and the saved
    row log-sum-exp; masked/padded entries come back exactly zero."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    lse = lse_ref[0][:, :1]  # (blk, 1), lanes replicated
    p = jnp.exp(s - lse)
    dead = _dead_mask(qi, ki, s.shape, causal=causal, window=window,
                      seq_len=seq_len, blk=blk, with_q_pad=True)
    return jnp.where(dead, 0.0, p)


def _bwd_kv_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *,
                   scale: float, causal: bool, window: int | None,
                   blk: int, seq_len: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _block_live(qi, kj, causal=causal, window=window, blk=blk)

    @pl.when(live)
    def _update():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, kj, scale=scale,
                         causal=causal, window=window, blk=blk,
                         seq_len=seq_len)
        # native-dtype MXU operands, f32 accumulation (see _fwd_kernel);
        # p/ds are f32 from the softmax algebra and cast down to the
        # input dtype for their matmuls, as the XLA reference path does
        # dV += Pᵀ · dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dS = P ⊙ (dO·Vᵀ − D)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0][:, :1])
        # dK += dSᵀ · Q · scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_q_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dd_ref,
                  dq_ref, dq_scr, *,
                  scale: float, causal: bool, window: int | None,
                  blk: int, seq_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _block_live(qi, kj, causal=causal, window=window, blk=blk)

    @pl.when(live)
    def _update():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, kj, scale=scale,
                         causal=causal, window=window, blk=blk,
                         seq_len=seq_len)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0][:, :1])
        # dQ += dS · K · scale (native-dtype operands, f32 accumulation)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal: bool,
                    window: int | None, scale: float, block: int | None,
                    interpret: bool):
    b, s, h, d = q.shape
    # GQA: the dK/dV kernel runs per QUERY head (accumulating across the
    # group inside the kernel would race the parallel bh grid dim), so
    # its outputs are per-query-head and reduced over the group in XLA
    # afterwards; K/V inputs are group-indexed via bh // grp, never
    # materialized per query head
    h_kv = k.shape[2]
    grp = h // h_kv
    blk = _block_rows(block, q, v)
    s_pad = _round_up(s, blk)
    qb, kb, vb, dob = (_to_bh(t, s_pad) for t in (q, k, v, g))
    # D = rowsum(dO ⊙ O): (bh, s_pad), lanes-replicated like the LSE
    dd = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (b, s, h)
    dd = jnp.moveaxis(dd, 2, 1).reshape(b * h, s)
    if s_pad != s:
        dd = jnp.pad(dd, ((0, 0), (0, s_pad - s)))
    dd = jnp.broadcast_to(dd[:, :, None], (b * h, s_pad, LANES))

    n_blk = s_pad // blk
    tile = lambda im: pl.BlockSpec((1, blk, d), im,
                                   memory_space=pltpu.VMEM)
    rep = lambda im: pl.BlockSpec((1, blk, LANES), im,
                                  memory_space=pltpu.VMEM)

    # causal dead blocks (see _flash_forward): clamp streamed-side index
    # maps to the nearest live block so dead iterations skip their DMAs
    if causal:
        def q_side_kv(bh, j, i):
            # live q blocks for K block j: i in [j, hi] (hi bounded by
            # the window: the newest query that still sees block j)
            if window is None:
                return (bh, jnp.maximum(i, j), 0)
            hi = (j * blk + blk + window - 2) // blk
            return (bh, jnp.clip(i, j, hi), 0)

        def kv_side_q(bh, i, j):
            lo, hi = _live_k_range(i, window=window, blk=blk)
            return (bh // grp, jnp.clip(j, lo, hi), 0)

        def kv_in_kvgrid(bh, j, i):
            return (bh // grp, j, 0)
    else:
        q_side_kv = lambda bh, j, i: (bh, i, 0)  # noqa: E731
        kv_side_q = lambda bh, i, j: (bh // grp, j, 0)  # noqa: E731
        kv_in_kvgrid = lambda bh, j, i: (bh // grp, j, 0)  # noqa: E731
    # dK / dV: fix the k block, stream q blocks (qi is the fastest grid dim)
    dkb, dvb = pl.pallas_call(
        partial(_bwd_kv_kernel, scale=scale, causal=causal,
                window=window, blk=blk, seq_len=s),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, s_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_pad, d), v.dtype),
        ),
        grid=(b * h, n_blk, n_blk),
        in_specs=[
            tile(q_side_kv),                    # Q
            tile(q_side_kv),                    # dO
            rep(q_side_kv),                     # LSE
            rep(q_side_kv),                     # D
            tile(kv_in_kvgrid),                 # K
            tile(kv_in_kvgrid),                 # V
        ],
        out_specs=(
            tile(lambda bh, j, i: (bh, j, 0)),
            tile(lambda bh, j, i: (bh, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk, d), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(qb, dob, lse, dd, kb, vb)

    # dQ: fix the q block, stream k blocks (kj fastest)
    dqb = pl.pallas_call(
        partial(_bwd_q_kernel, scale=scale, causal=causal,
                window=window, blk=blk, seq_len=s),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        grid=(b * h, n_blk, n_blk),
        in_specs=[
            tile(kv_side_q),                    # K
            tile(kv_side_q),                    # V
            tile(lambda bh, i, j: (bh, i, 0)),  # Q
            tile(lambda bh, i, j: (bh, i, 0)),  # dO
            rep(lambda bh, i, j: (bh, i, 0)),   # LSE
            rep(lambda bh, i, j: (bh, i, 0)),   # D
        ],
        out_specs=tile(lambda bh, i, j: (bh, i, 0)),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(kb, vb, qb, dob, lse, dd)

    dq = _from_bh(dqb, b, h, s)
    dk = _from_bh(dkb, b, h, s)
    dv = _from_bh(dvb, b, h, s)
    if grp > 1:
        # reduce per-query-head dK/dV over the group -> (B, S, h_kv, D);
        # sum in f32: each addend was already rounded to the input dtype
        # once leaving the kernel, and a bf16 tree of grp addends would
        # compound that rounding exactly in the large-group (MQA) configs
        dk = dk.reshape(b, s, h_kv, grp, d).astype(jnp.float32).sum(
            axis=3).astype(k.dtype)
        dv = dv.reshape(b, s, h_kv, grp, d).astype(jnp.float32).sum(
            axis=3).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op


@lru_cache(maxsize=None)
def _build(causal: bool, window: int | None, scale_key, block: int | None,
           interpret: bool):
    @jax.custom_vjp
    def f(q, k, v):
        # inference-only path: skip the LSE residual entirely (it is a
        # grad-path artifact and 4x the output's HBM write bytes)
        scale = scale_key if scale_key else q.shape[-1] ** -0.5
        out, _ = _flash_forward(q, k, v, causal=causal, window=window,
                                scale=scale, block=block,
                                interpret=interpret, with_lse=False)
        return out

    def fwd(q, k, v):
        scale = scale_key if scale_key else q.shape[-1] ** -0.5
        out, lse = _flash_forward(q, k, v, causal=causal, window=window,
                                  scale=scale, block=block,
                                  interpret=interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        scale = scale_key if scale_key else q.shape[-1] ** -0.5
        return _flash_backward(q, k, v, out, lse, g, causal=causal,
                               window=window, scale=scale, block=block,
                               interpret=interpret)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, *, causal: bool = False,
                    window: int | None = None, scale=None,
                    block: int | None = None,
                    interpret: bool | None = None, mesh=None, sink=None):
    """Blockwise fused attention, (B, S, H, D) layout, exact output AND
    exact gradients — both directions O(S·d) memory.

    Grouped-query attention is supported by passing k/v with fewer heads
    (h_kv dividing h_q): query head i attends kv head ``i // group``.
    The kernels expand K/V on the fly through their grid index maps —
    no per-query-head copy is ever materialized; dK/dV are reduced over
    the group after the per-query-head kernel pass.

    ``window=W`` restricts each query to the W most recent keys
    (positions ``qpos - W + 1 .. qpos``, Mistral-style sliding window;
    requires ``causal=True``). Work AND streamed HBM traffic then scale
    O(S·W) instead of O(S²): blocks outside the band are skipped by the
    same dead-block machinery as causal masking, on both window edges,
    in forward and both backward kernels.

    ``interpret=None`` auto-selects: compiled kernel on TPU, interpreter
    elsewhere (tests). ``block=None`` takes the rows of a grid step from
    the shapes (:func:`_flash_block`); a number overrides that.
    Sequences are padded to the block size internally; padded keys are
    masked, padded query rows are sliced away.

    ``mesh``: the caller's (data, model) mesh when the operands are
    sharded over one — the kernel then runs per shard (see
    :func:`_kernel_axes`). Leave it None inside a ``shard_map`` body.

    ``sink`` ((H,) float, one learned logit a query head) joins the
    softmax's denominator only, and ``v`` may be narrower or wider than
    ``q`` and ``k`` (the output takes its width). Either makes the call
    INFERENCE ONLY (the forward kernel alone, no VJP, no mesh): these
    are the serving prefill's variants.
    """
    if not (q.dtype == k.dtype == v.dtype):
        # matmuls feed the MXU native-dtype operands (no f32 upcast),
        # which requires a single dtype across the three inputs
        raise ValueError(
            "flash_attention requires q, k, v to share one dtype, got "
            f"{q.dtype}/{k.dtype}/{v.dtype}"
        )
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        # grouped-query attention: adjacent query heads share a kv head
        # (query head i reads kv head i // (h_q // h_kv))
        raise ValueError(
            "flash_attention needs k/v heads equal and dividing q heads, "
            f"got q={q.shape[2]} k={k.shape[2]} v={v.shape[2]}"
        )
    if window is not None:
        if not causal:
            raise ValueError(
                "flash_attention window=W is the causal sliding window; "
                "pass causal=True with it"
            )
        if int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        window = int(window)
    if interpret is None:
        from mmlspark_tpu.core.env import is_tpu

        interpret = not is_tpu()
    if sink is not None or v.shape[-1] != q.shape[-1]:
        if mesh is not None:
            raise ValueError(
                "flash_attention with a sink or with values of another "
                "width than the keys is not run under a mesh"
            )
        out, _ = _flash_forward(
            q, k, v, causal=causal, window=window,
            scale=scale if scale else q.shape[-1] ** -0.5, block=block,
            interpret=bool(interpret), with_lse=False, sink=sink,
        )
        return out
    fn = _build(causal, window, scale, block, bool(interpret))
    if mesh is None:
        return fn(q, k, v)
    b_ax, h_ax = _kernel_axes(mesh, q.shape[0], k.shape[2])
    spec = P(b_ax, None, h_ax, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# flash decode: split-KV single-token attention over slot caches
#
# The serving hot path (the ``serve`` package) decodes ONE query token per
# slot per tick against a preallocated (B, cache_len, hk, d) cache, but a
# dense read does cache_len worth of work per row no matter how little of
# the buffer is live. This kernel streams K/V in blocks with the online-
# softmax carry in VMEM scratch (same recipe as _fwd_kernel) and takes a
# per-row LIVE-LENGTH vector (B,) int32 as a SCALAR-PREFETCH argument, so
# the kv-block index map can clamp past each row's last live block —
# consecutive dead grid iterations re-reference the resident tile and
# their HBM→VMEM DMAs never issue. Work AND streamed bytes scale with
# how much each request has actually generated, not with pool capacity.

# grid (batch·heads, kv-block): only the streamed kv dim carries scratch
_DECODE_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, blk: int, heads: int):
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    length = len_ref[bh // heads]  # live positions [0, length)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb * blk < length)
    def _update():
        # the single query row broadcast to the minimum sublane tile:
        # every scratch/compute shape stays (8, ·), all 8 rows identical
        q = jnp.broadcast_to(q_ref[0], (SUBLANES, q_ref.shape[-1]))
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (8, blk) f32
        kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos >= length, NEG_INF, s)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True),
            l_scr.shape,
        )
        # P·V stays f32 (unlike _fwd_kernel's native-dtype cast): the
        # one-row decode matmul is bandwidth-bound — its FLOPs are noise
        # next to the K/V stream — and f32 operands keep the kernel
        # bit-compatible with the dense_attention oracle the serving
        # parity tests hold it to
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        # length == 0: no block ever updated, l stays 0 -> zeros, the
        # same answer dense_attention gives a fully-masked row
        l = l_scr[:1, :1]
        o_ref[0] = (
            acc_scr[:1] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def _decode_kernel_q8(len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *,
                      scale: float, blk: int, heads: int, group: int):
    """:func:`_decode_kernel` over int8 K/V with per-(row, kv-head) f32
    scales riding the scalar-prefetch channel next to the lengths
    (docs/PERFORMANCE.md "Quantized decode"). HBM→VMEM traffic is the
    int8 bytes; the dequant is an in-VMEM ``astype`` whose scale folds
    into scalars the online softmax already multiplies by — ``k_scale``
    into the softmax scale, ``v_scale`` onto each block's P·V
    contribution — so the carry algebra stays f32 and unchanged."""
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    length = len_ref[bh // heads]
    # bh // group is the flattened (batch, kv-head) row — the same
    # coordinate the kv index map fetches K/V blocks with
    ks = ks_ref[bh // group]
    vs = vs_ref[bh // group]

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb * blk < length)
    def _update():
        q = jnp.broadcast_to(
            q_ref[0].astype(jnp.float32), (SUBLANES, q_ref.shape[-1])
        )
        s = jax.lax.dot_general(
            q, k_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * ks)  # k dequant scale folded into the softmax scale
        kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos >= length, NEG_INF, s)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * vs  # v dequant scale applied per block contribution
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:1, :1]
        o_ref[0] = (
            acc_scr[:1] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def _validate_kv_scales(q, kv_dtype, hk: int, b: int, k_scale, v_scale,
                        d: int, name: str):
    """Shared int8-mode argument contract for both decode kernels:
    int8 K/V requires BOTH f32 scale arrays and a float query; float
    K/V must not pass scales (a silent no-op scale would mask a pool
    wiring bug). Returns True when the int8 path is active."""
    quantized = kv_dtype == jnp.int8
    if quantized:
        if k_scale is None or v_scale is None:
            raise ValueError(
                f"{name}: int8 K/V requires k_scale and v_scale"
            )
        if not jnp.issubdtype(q.dtype, jnp.floating):
            raise ValueError(
                f"{name}: int8 K/V needs a float query, got {q.dtype}"
            )
        if d % 2:
            raise ValueError(
                f"{name}: int8 K/V requires an even head_dim (int8 "
                f"lanes pack pairwise in the VREG tile), got {d}"
            )
    elif k_scale is not None or v_scale is not None:
        raise ValueError(
            f"{name}: k_scale/v_scale are int8-mode arguments; K/V "
            f"here are {kv_dtype}"
        )
    return quantized


def _decode_block(cache_len: int, block: int) -> int:
    """Largest KV block in [8, block] that divides ``cache_len`` AND
    that the TPU lowering accepts — whole sublanes, or the whole cache
    when it fits one block. Dividing evenly means the cache streams
    with NO pad copy, which is the point on the serving hot path;
    otherwise fall back to the padded layout (_to_bh pads, masking
    hides the tail)."""
    for cand in range(min(block, cache_len), 7, -1):
        if cache_len % cand == 0 and (
            cand % SUBLANES == 0 or cand == cache_len
        ):
            return cand
    return min(block, _round_up(cache_len, 8))


def flash_decode(q, k, v, lengths, *, scale=None, block: int = 128,
                 interpret: bool | None = None,
                 k_scale=None, v_scale=None, mesh=None):
    """Length-aware split-KV attention for ONE query token per row.

    int8 mode: when ``k``/``v`` are int8, ``k_scale``/``v_scale`` —
    (B, Hkv) f32, the dense pool's per-(slot, kv-head) quantization
    scales — must be passed; they ride the scalar-prefetch channel
    next to ``lengths`` and the kernel dequantizes in-VMEM (HBM
    streams half the bytes of bf16; softmax math stays f32). ``q``
    stays float and sets the output dtype.

    ``q`` is (B, 1, H, D) — a single decode step; ``k``/``v`` are the
    (B, L, Hkv, D) slot caches (GQA as in :func:`flash_attention`);
    ``lengths`` is (B,) int32 of LIVE positions per row — row b attends
    cache positions ``[0, lengths[b])`` and nothing else (the
    ``pos + 1`` contract of :func:`mmlspark_tpu.ops.attention.
    decode_live_lengths`). ``lengths[b] == 0`` yields zeros for that row,
    matching the dense path's fully-masked convention.

    The kv grid dimension streams L in blocks; ``lengths`` rides the
    scalar-prefetch channel so the block index map clamps at each row's
    last live block — blocks past the live length are never fetched from
    HBM, making per-row work O(lengths[b]) instead of O(L). Inference
    only (no VJP): this is the serving decode read, not a training op.

    ``interpret=None`` auto-selects like :func:`flash_attention`:
    compiled on TPU, interpreter elsewhere so CPU tests run the same
    code path. ``mesh`` as in :func:`flash_attention`: slots split over
    the data axis, heads over the model axis, one kernel per shard.
    """
    if k.dtype != v.dtype:
        raise ValueError(
            f"flash_decode requires k and v to share one dtype, got "
            f"{k.dtype}/{v.dtype}"
        )
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(
            "flash_decode takes a SINGLE query token per row: q must be "
            f"(B, 1, H, D), got {q.shape}"
        )
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise ValueError(
            "flash_decode needs k/v heads equal and dividing q heads, "
            f"got q={q.shape[2]} k={k.shape[2]} v={v.shape[2]}"
        )
    b, _, h, d = q.shape
    quantized = _validate_kv_scales(
        q, k.dtype, k.shape[2], b, k_scale, v_scale, d, "flash_decode"
    )
    if not quantized and q.dtype != k.dtype:
        raise ValueError(
            "flash_decode requires q, k, v to share one dtype, got "
            f"{q.dtype}/{k.dtype}/{v.dtype}"
        )
    if quantized:
        k_scale = jnp.asarray(k_scale, jnp.float32)
        v_scale = jnp.asarray(v_scale, jnp.float32)
        want = (b, k.shape[2])
        if k_scale.shape != want or v_scale.shape != want:
            raise ValueError(
                f"flash_decode int8 scales must be {want} — one f32 per "
                f"(row, kv head) — got {k_scale.shape}/{v_scale.shape}"
            )
    L = k.shape[1]
    lengths = jnp.asarray(lengths)
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be ({b},) — one live length per batch row — "
            f"got {lengths.shape}"
        )
    g = h // k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        from mmlspark_tpu.core.env import is_tpu

        interpret = not is_tpu()
    if mesh is not None:
        b_ax, h_ax = _kernel_axes(mesh, b, k.shape[2])
        kv_spec, sc_spec = P(b_ax, None, h_ax, None), P(b_ax, h_ax)
        scales = (k_scale, v_scale) if quantized else ()

        def local(q, k, v, lengths, *scales):
            ks, vs = scales or (None, None)
            return flash_decode(q, k, v, lengths, scale=scale, block=block,
                                interpret=interpret, k_scale=ks, v_scale=vs)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(kv_spec, kv_spec, kv_spec, P(b_ax))
            + (sc_spec,) * len(scales),
            out_specs=kv_spec, check_vma=False,
        )(q, k, v, lengths, *scales)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, L)

    blk = _decode_block(L, block)
    l_pad = _round_up(L, blk)
    qb = _to_bh(q, 1)          # (B*H, 1, D)
    kb = _to_bh(k, l_pad)      # (B*Hkv, l_pad, D)
    vb = _to_bh(v, l_pad)
    n_blk = l_pad // blk

    def kv_im(bh, j, lens, *scales):
        # clamp at the row's last LIVE block: dead iterations re-reference
        # the resident tile, so their DMAs never issue (block-level
        # early-out). bh // g maps query-head rows onto kv-head rows
        # (bh//g == batch*hkv + qh//group, g dividing h). *scales absorbs
        # the int8 mode's extra scalar-prefetch refs, unused here.
        length = lens[bh // h]
        last = jnp.maximum((length + blk - 1) // blk - 1, 0)
        return (bh // g, jnp.minimum(j, last), 0)

    if quantized:
        # per-(row, kv-head) scales flattened to the kernel's bh // g
        # coordinate, scalar-prefetched alongside the live lengths
        kernel = partial(
            _decode_kernel_q8, scale=scale, blk=blk, heads=h, group=g,
        )
        n_prefetch = 3
        operands = (
            lengths, k_scale.reshape(-1), v_scale.reshape(-1), qb, kb, vb,
        )
    else:
        kernel = partial(_decode_kernel, scale=scale, blk=blk, heads=h)
        n_prefetch = 1
        operands = (lengths, qb, kb, vb)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(b * h, n_blk),
            in_specs=[
                pl.BlockSpec((1, 1, d),
                             lambda bh, j, lens, *scales: (bh, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk, d), kv_im,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk, d), kv_im,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, d), lambda bh, j, lens, *scales: (bh, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[
                pltpu.VMEM((SUBLANES, LANES), jnp.float32),  # running max
                pltpu.VMEM((SUBLANES, LANES), jnp.float32),  # normalizer
                pltpu.VMEM((SUBLANES, d), jnp.float32),      # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        compiler_params=_DECODE_SEMANTICS,
        interpret=bool(interpret),
    )(*operands)
    return _from_bh(out, b, h, 1)


# ---------------------------------------------------------------------------
# grouped flash decode: one KV head's whole group of query heads a grid
# step, over HEAD-MAJOR slot caches
#
# flash_decode walks every KV row once for EACH query head of a group: at
# 16 query heads a KV head that is 16 walks of the same rows, and its
# (B, L, Hkv, D) operands have to be laid out anew in the kernel's tiles
# on every call. This kernel takes the caches as the pool stores them for
# blocks that declare their geometry, ``(B, Hkv, L, Dk)`` and
# ``(B, Hkv, L, Dv)``: merging the two leading dimensions is free, the
# minor two are the kernel's own tiles, and a grid step multiplies the
# group's (G, Dk) queries with one (blk, Dk) block of keys. The keys and
# the values may differ in width, and a learned per-head sink joins the
# denominator when the walk ends. The same kernel reads a full-length
# cache (``lengths = pos + 1``) and a ring (``lengths = min(pos + 1,
# W)``: every written slot of a ring is inside the window).
#
# A group of fewer than 8 query heads (MHA is a group of one) fills less
# than one sublane tile, and a grid step that holds one KV head of one
# slot costs more than the rows it streams. There a step takes SEVERAL
# KV heads of one slot, blocks ``(1, Hb, blk, D)`` of the 4-D caches, in
# one batched product: the slot's one live length clamps them all.

#: what a grid step's K and V blocks may take of VMEM, double-buffered and
#: as VMEM holds them (the minor dimension in whole lanes): a quarter of
#: the 16 MiB a kernel gets by default, which leaves the float32 copies
#: of the scores and of V their room
_DECODE_KV_VMEM = 4 << 20

# grid (slot, head group, kv-block)
_DECODE_HEADS_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
)


def _decode_group_kernel(len_ref, q_ref, k_ref, *rest,
                         scale: float, blk: int, kv_heads: int,
                         kv_axis: int = 1, values_in_keys: int = 0):
    # one KV head a step: q (G, Dk), k (blk, Dk), grid row = batch *
    # kv_heads + kv head. Several a step: q (Hb, G, Dk), k (Hb, blk, Dk),
    # the leading dimension a batch of the two products, grid row = the
    # slot (``kv_heads`` 1) and the kv-block on grid axis ``kv_axis`` 2.
    # A LATENT cache (``values_in_keys`` > 0) has no value operand: the
    # values are the first ``values_in_keys`` columns of the key rows,
    # which are fetched once and serve both products
    if values_in_keys:
        v_ref = None
        sink_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        v_ref, sink_ref, o_ref, m_scr, l_scr, acc_scr = rest
    row = pl.program_id(0)
    kb = pl.program_id(kv_axis)
    length = len_ref[row // kv_heads]  # live positions [0, length)
    heads = tuple(range(q_ref.ndim - 3))  # the products' batch dimensions
    n = len(heads)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kb * blk < length)
    def _update():
        k = k_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k, (((n + 1,), (n + 1,)), (heads, heads)),
            preferred_element_type=jnp.float32,
        ) * scale  # (..., G, blk) f32
        kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   n + 1)
        s = jnp.where(kpos >= length, NEG_INF, s)
        m_prev = m_scr[..., :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[..., :1] * corr + p.sum(axis=-1, keepdims=True),
            l_scr.shape,
        )
        if values_in_keys:
            # a whole group of query heads on ONE stream of rows is no
            # longer bound by the stream alone (32 heads x 512 columns:
            # 60 FLOP a byte), so P.V feeds the MXU the rows' own dtype
            # with a float32 accumulator, as the prefill kernel does
            v = k[..., :values_in_keys]
            p = p.astype(v.dtype)
        else:
            # P.V in f32, as _decode_kernel keeps it: the read is bound
            # by the K/V stream, not by these few rows of products
            v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((n + 1,), (n,)), (heads, heads)),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(kv_axis) - 1)
    def _finalize():
        o_ref[0] = _with_sink(
            m_scr[..., :1], l_scr[..., :1], acc_scr[...],
            sink_ref[0][..., :1]
        ).astype(o_ref.dtype)


def _heads_and_rows(hk: int, cache_len: int, block: int, dk: int, dv: int,
                    itemsize: int) -> tuple[int, int]:
    """``(heads, rows)`` of one grid step when a group is smaller than a
    sublane tile. Heads before rows: as many rows, 128 at least and
    ``block`` at most, as let ALL of a slot's heads fit
    ``_DECODE_KV_VMEM`` double-buffered, then the largest divisor of
    ``hk`` that fits beside that many rows. (On a v5e at 10 heads of 128
    lanes x 1,024 rows, live lengths 32-400: 10 heads x 256 rows took
    70 us a call, 5 x 512 99, 10 x 512 86, 1 x 256 229; all rows live,
    120 us each: my chip run, PR 30.)"""
    row = (_round_up(dk, LANES) + _round_up(dv, LANES)) * itemsize
    most = _DECODE_KV_VMEM // (2 * row)  # a step's rows over all its heads
    blk = _decode_block(cache_len, max(min(block, most // hk), LANES))
    return max(hb for hb in range(1, hk + 1)
               if hk % hb == 0 and (hb * blk <= most or hb == 1)), blk


def _row_write_kernel(at_ref, *refs, tile: int):
    # refs: the new rows, the caches and the outputs, one of each an array
    row = at_ref[pl.program_id(0)] % tile
    n = len(refs) // 3
    for new_ref, old_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                         refs[2 * n:]):
        # through float32: the select then needs no packed-dtype
        # broadcast along sublanes, and bf16 -> f32 -> bf16 is exact
        rows = jax.lax.broadcasted_iota(jnp.int32, old_ref.shape, 2)
        out_ref[...] = jnp.where(
            rows == row, new_ref[...].astype(jnp.float32),
            old_ref[...].astype(jnp.float32),
        ).astype(out_ref.dtype)


def _interpret(interpret: bool | None) -> bool:
    if interpret is None:
        from mmlspark_tpu.core.env import is_tpu

        interpret = not is_tpu()
    return bool(interpret)


def cache_row_write(k, v, k_new, v_new, at, *,
                    interpret: bool | None = None):
    """Head-major caches ``k`` (B, Hkv, L, Dk) and ``v`` (B, Hkv, L, Dv)
    with row ``at[b]`` of every head of batch row ``b`` taken from
    ``k_new`` (B, Hkv, Dk) and ``v_new`` (B, Hkv, Dv): one decode step's
    cache write, in place on donated caches.

    The XLA scatter that ``k.at[rows, :, at].set(...)`` lowers to wants
    the caches in a layout of its own, and between it and the decode
    kernel the whole cache would be copied twice a layer a micro-step.
    This kernel aliases the caches to its outputs and rewrites only the
    sublane tile that holds the row: the layout stays the decode
    kernel's."""
    return _cache_row_write((k, v), (k_new, v_new), at,
                            interpret=_interpret(interpret))


def latent_row_write(rows, new, at, *, interpret: bool | None = None):
    """A latent cache ``rows`` (B, L, W) with row ``at[b]`` of batch row
    ``b`` taken from ``new`` (B, W): :func:`cache_row_write` for the one
    array of an ``ops.kv_cache.LatentRows`` entry, one KV head."""
    (out,) = _cache_row_write((rows[:, None],), (new[:, None],), at,
                              interpret=_interpret(interpret))
    return out[:, 0]


# jitted where they stand: a decode block calls each kernel once a layer
# and the engine builds a block a ladder size, so the kernel's body is
# traced and lowered ONCE a signature, not 36 x 6 times (a traced kernel
# cost the host 0.12 s: 27 s of a warm set-up, my chip run, PR 30)
@partial(jax.jit, static_argnames=("interpret",))
def _cache_row_write(caches: tuple, news: tuple, at, *, interpret: bool):
    b, hk, L, _ = caches[0].shape
    # whole sublanes of the cache's dtype, or the whole of a shorter cache
    tile = 32 // caches[0].dtype.itemsize
    if L % tile:
        tile = L
    at = jnp.clip(jnp.asarray(at, jnp.int32), 0, L - 1)

    def new_spec(cache):
        return pl.BlockSpec((1, hk, 1, cache.shape[3]),
                            lambda i, at: (i, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    def old_spec(cache):
        return pl.BlockSpec((1, hk, tile, cache.shape[3]),
                            lambda i, at: (i, 0, at[i] // tile, 0),
                            memory_space=pltpu.VMEM)

    n = len(caches)
    return pl.pallas_call(
        partial(_row_write_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[*map(new_spec, caches), *map(old_spec, caches)],
            out_specs=[*map(old_spec, caches)],
        ),
        out_shape=tuple(jax.ShapeDtypeStruct(c.shape, c.dtype)
                        for c in caches),
        # operands count the scalar-prefetched ``at`` and then the new
        # rows: the caches follow
        input_output_aliases={1 + n + i: i for i in range(n)},
        interpret=bool(interpret),
        name="cache_row_write",
    )(at, *(new[:, :, None].astype(c.dtype)
            for new, c in zip(news, caches)), *caches)


def flash_decode_grouped(q, k, v, lengths, *, sink=None, scale=None,
                         block: int = 512, interpret: bool | None = None,
                         name: str | None = None, values_in_keys: int = 0):
    """Length-aware decode attention for ONE query token per row over
    HEAD-MAJOR caches, a KV head's whole group of query heads per grid
    step.

    ``q`` is (B, 1, H, Dk); ``k`` is (B, Hkv, L, Dk) and ``v`` is
    (B, Hkv, L, Dv), ``Hkv`` dividing ``H`` (query head ``i`` reads KV
    head ``i // (H // Hkv)``); ``lengths`` is (B,) int32, row ``b``
    attending cache rows ``[0, lengths[b])`` and nothing else
    (``lengths[b] == 0`` yields zeros). ``sink`` ((H,) float) joins the
    softmax's denominator only. Returns (B, 1, H, Dv) in ``q``'s dtype.

    Caches whose rows are ``f * Dk`` and ``f * Dv`` wide are PACKED
    (``ops.kv_cache.lane_pack``): ``(B, Hkv / f, L, f * Dk)``, ``f``
    adjacent KV heads side by side in one row. Each query head is then
    laid, among zeros, over the lanes of its own KV head, the ``f`` heads
    of a row are read as one head with ``f`` groups, and each query head
    keeps its own lanes of the result.

    The KV grid dimension streams ``L`` in blocks of ``block`` rows (the
    whole of a shorter cache) and the index map clamps at each row's
    last live block, so dead blocks are never fetched. A group of fewer
    than 8 query heads takes several KV heads of a slot a grid step
    (:func:`_heads_and_rows`). Inference only, one device only. ``name``
    names the kernel in a device trace.

    A LATENT cache (``ops.kv_cache.LatentRows``) is read with ``v`` None
    and ``values_in_keys = Dv``: the values are the first ``Dv`` columns
    of the key rows, so each live row is fetched ONCE and serves the
    scores and the weighted sum. ``Hkv`` is then 1 (a group of at least
    8 query heads), ``Dv`` whole lanes, and ``scale`` the caller's (the
    rows' width is not the width the scores were trained at)."""
    return _flash_decode_grouped(
        q, k, v, jnp.asarray(lengths), sink, scale=scale, block=block,
        interpret=_interpret(interpret), name=name,
        values_in_keys=int(values_in_keys))


@partial(jax.jit, static_argnames=("scale", "block", "interpret", "name",
                                   "values_in_keys"))
def _flash_decode_grouped(q, k, v, lengths, sink, *, scale, block: int,
                          interpret: bool, name, values_in_keys: int = 0):
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(
            "flash_decode_grouped takes a SINGLE query token per row: q "
            f"must be (B, 1, H, Dk), got {q.shape}"
        )
    b, _, h, dk = q.shape
    if values_in_keys:
        if (v is not None or scale is None or k.ndim != 4
                or k.shape[:2] != (b, 1) or k.shape[3] != dk
                or not 0 < values_in_keys <= dk or values_in_keys % LANES
                or h < SUBLANES):
            raise ValueError(
                "a latent read takes v=None, a scale, one KV head of rows "
                f"(B, 1, L, Dk={dk}) for at least {SUBLANES} query heads "
                f"and values of whole lanes inside them, got k {k.shape}, "
                f"values_in_keys {values_in_keys}, H={h}, scale {scale}"
            )
        # the validation below reads the values' shape only
        v = jax.ShapeDtypeStruct(k.shape[:3] + (values_in_keys,), k.dtype)
    f = k.shape[3] // dk if k.ndim == 4 else 0
    if (k.ndim != 4 or v.ndim != 4 or k.shape[:3] != v.shape[:3]
            or k.shape[0] != b or k.shape[3] != f * dk or v.shape[3] % f
            or h % (k.shape[1] * f)):
        raise ValueError(
            "flash_decode_grouped needs head-major caches (B, Hkv, L, Dk) "
            f"and (B, Hkv, L, Dv) with Hkv dividing H={h}, or f heads "
            f"packed in a row, got {k.shape} / {v.shape}"
        )
    if f > 1:
        # query head i reads KV head i // g, which lies in the lanes
        # [(i // g) % f * Dk, +Dk) of packed row head i // (g * f)
        g = h // (k.shape[1] * f)
        mine = jax.nn.one_hot((jnp.arange(h) // g) % f, f, dtype=q.dtype)
        wide = (q[..., None, :] * mine[:, :, None]).reshape(b, 1, h, f * dk)
        out = flash_decode_grouped(
            wide, k, v, lengths, sink=sink,
            scale=dk ** -0.5 if scale is None else scale, block=block,
            interpret=interpret, name=name)
        out = out.reshape(b, 1, h, f, v.shape[3] // f)
        return (out * mine[:, :, None]).sum(axis=3)
    if not (q.dtype == k.dtype == v.dtype):
        raise ValueError(
            "flash_decode_grouped requires q, k, v to share one dtype, "
            f"got {q.dtype}/{k.dtype}/{v.dtype}"
        )
    hk, L, dv = k.shape[1], k.shape[2], v.shape[3]
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be ({b},) — one live length per batch row — "
            f"got {lengths.shape}"
        )
    g = h // hk
    if scale is None:
        scale = dk ** -0.5
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, L)
    several = g < SUBLANES  # KV heads of a slot a grid step
    hb, blk = (_heads_and_rows(hk, L, block, dk, dv, k.dtype.itemsize)
               if several else (1, _decode_block(L, block)))
    if L % blk:
        raise ValueError(
            f"flash_decode_grouped streams the cache without a pad copy: "
            f"its {L} rows need a block of whole sublanes that divides "
            f"them (tried up to {block})"
        )
    n_blk = L // blk
    # the group's rows padded to whole sublanes; pad rows are sliced off
    gp = _round_up(g, SUBLANES)
    qb = q.reshape(b * hk, g, dk)
    if sink is None:
        sink = jnp.full((h,), NEG_INF, jnp.float32)
    sb = sink.astype(jnp.float32).reshape(hk, g)
    if gp != g:
        qb = jnp.pad(qb, ((0, 0), (0, gp - g), (0, 0)))
        sb = jnp.pad(sb, ((0, 0), (0, gp - g)), constant_values=NEG_INF)
    sb = jnp.broadcast_to(sb[:, :, None], (hk, gp, LANES))
    named = {"name": name} if name else {}
    if several:

        def heads_im(slot, hg, j, lens):
            return (slot, hg, 0, 0)

        def kv_heads_im(slot, hg, j, lens):
            last = jnp.maximum((lens[slot] + blk - 1) // blk - 1, 0)
            return (slot, hg, jnp.minimum(j, last), 0)

        out = pl.pallas_call(
            partial(_decode_group_kernel, scale=scale, blk=blk, kv_heads=1,
                    kv_axis=2),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, hk // hb, n_blk),
                in_specs=[
                    pl.BlockSpec((1, hb, gp, dk), heads_im,
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, hb, blk, dk), kv_heads_im,
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, hb, blk, dv), kv_heads_im,
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, hb, gp, LANES),
                                 lambda slot, hg, j, lens: (hg, 0, 0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((1, hb, gp, dv), heads_im,
                                       memory_space=pltpu.VMEM),
                scratch_shapes=[
                    pltpu.VMEM((hb, gp, LANES), jnp.float32),  # running max
                    pltpu.VMEM((hb, gp, LANES), jnp.float32),  # normalizer
                    pltpu.VMEM((hb, gp, dv), jnp.float32),     # accumulator
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, hk, gp, dv), q.dtype),
            compiler_params=_DECODE_HEADS_SEMANTICS,
            interpret=bool(interpret),
            **named,
        )(lengths, qb.reshape(b, hk, gp, dk), k, v,
          sb.reshape(hk // hb, hb, gp, LANES))
        return out[:, :, :g].reshape(b, 1, h, dv)
    kb = k.reshape(b * hk, L, dk)
    # a latent read has no value operand and no block of one
    vb = () if values_in_keys else (v.reshape(b * hk, L, dv),)

    def kv_im(row, j, lens):
        length = lens[row // hk]
        last = jnp.maximum((length + blk - 1) // blk - 1, 0)
        return (row, jnp.minimum(j, last), 0)

    out = pl.pallas_call(
        partial(_decode_group_kernel, scale=scale, blk=blk, kv_heads=hk,
                values_in_keys=values_in_keys),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hk, n_blk),
            in_specs=[
                pl.BlockSpec((1, gp, dk), lambda row, j, lens: (row, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk, dk), kv_im, memory_space=pltpu.VMEM),
                *(pl.BlockSpec((1, blk, dv), kv_im,
                               memory_space=pltpu.VMEM) for _ in vb),
                pl.BlockSpec((1, gp, LANES),
                             lambda row, j, lens: (row % hk, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, gp, dv), lambda row, j, lens: (row, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[
                pltpu.VMEM((gp, LANES), jnp.float32),  # running max
                pltpu.VMEM((gp, LANES), jnp.float32),  # normalizer
                pltpu.VMEM((gp, dv), jnp.float32),     # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hk, gp, dv), q.dtype),
        compiler_params=_DECODE_SEMANTICS,
        interpret=bool(interpret),
        **named,
    )(lengths, qb, kb, *vb, sb)
    return out[:, :g].reshape(b, 1, h, dv)


# ---------------------------------------------------------------------------
# paged flash decode: the same split-KV walk through a page-table
# indirection. The paged cache pool (``serve/paging.py``) stores
# K/V as (num_pages, hk, page_size, d) physical pages and maps each
# slot's logical positions through a (slots, max_pages) int32 page table.
# flash_decode already walks the KV stream block-by-block with the block
# coordinate computed in a scalar-prefetched index map — so paging costs
# ONE extra prefetch argument and one table load in that map: with
# page_size == block, logical block j of row s simply lives at physical
# page pt[s, j], the grid shape is unchanged, and the live-length clamp
# early-out carries over verbatim (dead logical blocks re-reference the
# resident tile through the same clamped coordinate).


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *,
                         scale: float, blk: int, heads: int):
    # body of _decode_kernel against (page_size, d) page faces; kpos is
    # the LOGICAL position (page index kb is logical — only the fetch
    # coordinate went through the table)
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    length = len_ref[bh // heads]

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb * blk < length)
    def _update():
        q = jnp.broadcast_to(q_ref[0], (SUBLANES, q_ref.shape[-1]))
        s = jax.lax.dot_general(
            q, k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos >= length, NEG_INF, s)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:1, :1]
        o_ref[0] = (
            acc_scr[:1] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def _paged_decode_kernel_q8(len_ref, pt_ref, q_ref, k_ref, v_ref,
                            ks_ref, vs_ref, o_ref,
                            m_scr, l_scr, acc_scr, *,
                            scale: float, blk: int, heads: int,
                            group: int):
    """:func:`_paged_decode_kernel` over int8 pages with PER-PAGE f32
    scales. The scales are NOT scalar-prefetched: a prefetched
    ``(num_pages, Hkv)`` array pads its minor dim to 128 lanes in SMEM
    (512 B per page), which the compiler refuses past ~1,000 pages.
    Instead each grid step's ``(1, 1, Hkv)`` scale row is fetched into
    SMEM through a BlockSpec driven by the same clamped page coordinate
    as the page itself, so SMEM use is one row whatever the pool size.
    V's scale varies per page, so it lands on each block's P·V
    contribution before accumulation, which is exactly where per-page
    granularity is exact."""
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    length = len_ref[bh // heads]
    kvh = (bh % heads) // group

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb * blk < length)
    def _update():
        ks = ks_ref[0, 0, kvh]
        vs = vs_ref[0, 0, kvh]
        q = jnp.broadcast_to(
            q_ref[0].astype(jnp.float32), (SUBLANES, q_ref.shape[-1])
        )
        s = jax.lax.dot_general(
            q, k_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * ks)
        kpos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos >= length, NEG_INF, s)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * vs
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:1, :1]
        o_ref[0] = (
            acc_scr[:1] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, lengths, page_table, *,
                       scale=None, interpret: bool | None = None,
                       k_scale=None, v_scale=None, mesh=None):
    """:func:`flash_decode` over PAGED caches.

    int8 mode: when the page stores are int8, ``k_scale``/``v_scale``
    — (num_pages, Hkv) f32, the paged pool's PER-PAGE quantization
    scales — must be passed; each grid step fetches its page's scale
    row beside the page and the kernel dequantizes the page face
    in-VMEM, so the page-store HBM traffic halves vs bf16 while the
    softmax carry stays f32.

    ``q`` is (B, 1, H, D); ``k_pages``/``v_pages`` are the physical page
    stores ``(num_pages, Hkv, page_size, D)`` shared by all rows;
    ``page_table`` is (B, max_pages) int32 mapping row b's logical page
    j to physical page ``page_table[b, j]`` (every entry must be a valid
    page id — the pool points unmapped entries at a trash page);
    ``lengths`` is the (B,) live-length vector of :func:`flash_decode`,
    in LOGICAL positions. The virtual cache length is ``max_pages *
    page_size``.

    ``page_size`` doubles as the KV block, so the grid is (B·H,
    max_pages) — exactly flash_decode's shape for ``block ==
    page_size`` — and both scalar-prefetch arguments feed the kv index
    map: the live-length clamp picks the logical block, the table turns
    it physical. Per-row work and HBM traffic remain O(lengths[b]).

    ``mesh`` as in :func:`flash_attention`. Pages split over the data
    axis with the rows: the pool keeps every page a row maps on the
    row's own shard, so each shard's kernel reads its local page store
    with the table rebased to local page ids.
    """
    if k_pages.dtype != v_pages.dtype:
        raise ValueError(
            f"paged_flash_decode requires k and v pages to share one "
            f"dtype, got {k_pages.dtype}/{v_pages.dtype}"
        )
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(
            "paged_flash_decode takes a SINGLE query token per row: q "
            f"must be (B, 1, H, D), got {q.shape}"
        )
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            "k_pages/v_pages must share one (num_pages, Hkv, page_size, "
            f"D) shape, got {k_pages.shape} vs {v_pages.shape}"
        )
    if k_pages.shape[1] != v_pages.shape[1] or q.shape[2] % k_pages.shape[1]:
        raise ValueError(
            "paged_flash_decode needs k/v heads equal and dividing q "
            f"heads, got q={q.shape[2]} kv={k_pages.shape[1]}"
        )
    b, _, h, d = q.shape
    quantized = _validate_kv_scales(
        q, k_pages.dtype, k_pages.shape[1], b, k_scale, v_scale, d,
        "paged_flash_decode",
    )
    if not quantized and q.dtype != k_pages.dtype:
        raise ValueError(
            "paged_flash_decode requires q, k, v to share one dtype, got "
            f"{q.dtype}/{k_pages.dtype}/{v_pages.dtype}"
        )
    if quantized:
        k_scale = jnp.asarray(k_scale, jnp.float32)
        v_scale = jnp.asarray(v_scale, jnp.float32)
        want = (k_pages.shape[0], k_pages.shape[1])
        if k_scale.shape != want or v_scale.shape != want:
            raise ValueError(
                f"paged_flash_decode int8 scales must be {want} — one "
                f"f32 per (page, kv head) — got "
                f"{k_scale.shape}/{v_scale.shape}"
            )
    ps = k_pages.shape[2]
    if ps % SUBLANES:
        raise ValueError(
            f"page_size must be a multiple of {SUBLANES} (the TPU "
            f"sublane tile), got {ps}"
        )
    page_table = jnp.asarray(page_table)
    if page_table.ndim != 2 or page_table.shape[0] != b:
        raise ValueError(
            f"page_table must be ({b}, max_pages) int32 — one row per "
            f"batch row — got {page_table.shape}"
        )
    n_pages = page_table.shape[1]
    L = n_pages * ps
    lengths = jnp.asarray(lengths)
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be ({b},) — one live length per batch row — "
            f"got {lengths.shape}"
        )
    g = h // k_pages.shape[1]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        from mmlspark_tpu.core.env import is_tpu

        interpret = not is_tpu()
    if mesh is not None:
        b_ax, h_ax = _kernel_axes(mesh, b, k_pages.shape[1])
        if b_ax is not None and k_pages.shape[0] % mesh.shape[b_ax]:
            b_ax = None  # pages cannot follow the rows: gather both
        q_spec, page_spec = P(b_ax, None, h_ax, None), P(b_ax, h_ax, None, None)
        scales = (k_scale, v_scale) if quantized else ()

        def local(q, k_pages, v_pages, lengths, page_table, *scales):
            if b_ax is not None:
                page_table = page_table - (
                    jax.lax.axis_index(b_ax) * k_pages.shape[0]
                )
            ks, vs = scales or (None, None)
            return paged_flash_decode(
                q, k_pages, v_pages, lengths, page_table, scale=scale,
                interpret=interpret, k_scale=ks, v_scale=vs,
            )

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(q_spec, page_spec, page_spec, P(b_ax), P(b_ax, None))
            + (P(b_ax, h_ax),) * len(scales),
            out_specs=q_spec, check_vma=False,
        )(q, k_pages, v_pages, lengths, page_table, *scales)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, L)
    page_table = page_table.astype(jnp.int32)

    qb = _to_bh(q, 1)  # (B*H, 1, D)

    def page_of(bh, j, lens, pt):
        # same last-live-block clamp as flash_decode, then the page
        # table makes the surviving LOGICAL coordinate physical
        row = bh // h
        last = jnp.maximum((lens[row] + ps - 1) // ps - 1, 0)
        return pt[row, jnp.minimum(j, last)]

    def kv_im(bh, j, lens, pt):
        # the head coordinate picks the kv head inside the page
        return (page_of(bh, j, lens, pt), (bh % h) // g, 0, 0)

    q_spec = pl.BlockSpec((1, 1, d), lambda bh, j, lens, pt: (bh, 0, 0),
                          memory_space=pltpu.VMEM)
    page_spec = pl.BlockSpec((1, 1, ps, d), kv_im, memory_space=pltpu.VMEM)
    in_specs = [q_spec, page_spec, page_spec]
    operands = [lengths, page_table, qb, k_pages, v_pages]
    if quantized:
        kernel = partial(
            _paged_decode_kernel_q8, scale=scale, blk=ps, heads=h, group=g,
        )
        # one (1, 1, Hkv) scale row per grid step, fetched into SMEM by
        # the page's own coordinate (see _paged_decode_kernel_q8); the
        # middle unit dim makes the block's last two dims equal the
        # array's, which the TPU lowering requires of a sub-tile block
        hk = k_pages.shape[1]
        scale_spec = pl.BlockSpec(
            (1, 1, hk),
            lambda bh, j, lens, pt: (page_of(bh, j, lens, pt), 0, 0),
            memory_space=pltpu.SMEM,
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[:, None, :], v_scale[:, None, :]]
    else:
        kernel = partial(_paged_decode_kernel, scale=scale, blk=ps, heads=h)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h, n_pages),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((SUBLANES, LANES), jnp.float32),  # running max
                pltpu.VMEM((SUBLANES, LANES), jnp.float32),  # normalizer
                pltpu.VMEM((SUBLANES, d), jnp.float32),      # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        compiler_params=_DECODE_SEMANTICS,
        interpret=bool(interpret),
    )(*operands)
    return _from_bh(out, b, h, 1)
