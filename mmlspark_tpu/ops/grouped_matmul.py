"""Grouped matrix products as one Pallas TPU kernel: the rows of ``x``
are sorted by group, every group padded to whole row tiles, and each row
tile is multiplied with ITS group's matrix.

This is the product an expert layer needs once tokens are sorted by the
expert they were routed to (parallel/expert.py ``moe_ffn_held``): no
``(tokens, D, F)`` weight copy is gathered, an expert nobody chose is
never read, and every shape is fixed. A scalar-prefetched table says
which group a row tile belongs to, so the weight block's index map picks
the matrix. The grid's row-tile axis is bounded by the TRACED count of
live tiles: a tile past the live ones takes no grid step, and its rows
of the result are never written.

Off the TPU the kernel runs in interpreter mode, as the attention
kernels do.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

#: what one grid step's blocks may take of VMEM: the ``x``, weight and
#: result blocks, each in two buffers, and the float32 product; three
#: quarters of the least scope the kernel states (``_compiler_params``).
#: A weight block is then 3 to 4 MiB where rows are few. The product reads
#: every weight once and little else, so a step that moves a few hundred
#: KB pays its fixed cost (0.4 us, and an accumulator round trip where the
#: contraction is split) on too few bytes. On a v5e, us for an expert
#: layer's THREE products under an even routing (``tools/
#: hybrid_chip_check.py time``, my chip run, PR 36; in brackets the row
#: tile; "ladder" is the blocks before PR 36, the largest of 1,024 / 512
#: / 256 / 128 dividing K and of 512 / 256 / 128 dividing N):
#:
#:     tokens; K x N              ladder,      ladder  blocks  chosen  of its
#:                                static grid          <=2 MiB          least
#:     128;   2,048 x 1,792 (32)  1,511 (128)  1,179     963     966   90.5%
#:     512;   2,048 x 1,792 (128) 2,996 (512)  1,496   1,033   1,027   89.4%
#:     64;    2,048 x 768   (16)    272 (64)     232     198     196   88.9%
#:     2,048; 2,048 x 768   (256)   808 (512)    435     313     291   74.3%
#:     64;    4,096 x 2,048 (16)    915 (64)     830     845     818   90.3%
#:     2,048; 4,096 x 2,048 (128) 3,103 (512)  1,338   1,211   1,194   86.2%
#:
#: (``lfm2-8b-a1b``: 4 of 32 experts, all held; ``kanana-2-30b-a3b``: 6
#: of 128, 16 held; ``mimo-v2-flash``: 8 of 256, 16 held; a decode step
#: and a prefill bucket each.) Chosen: 2,048 x 896 and 1,792 x 1,024
#: (3.5 MiB), an expert of 768 whole (3 MiB), 4,096 x 512 and 2,048 x
#: 1,024 (4 MiB); ``moe_down`` within 4% of ``moe_gate`` at every shape.
_GMM_VMEM = 12 << 20

#: the block a matrix is cut to holds this much at least, where the
#: matrix does
_GMM_BLOCK_LEAST = 1 << 20

#: a v5e core's VMEM
_VMEM = 128 << 20


def _compiler_params(weight_bytes: int) -> pltpu.CompilerParams:
    """The kernel's scope of VMEM, stated: ``_GMM_VMEM`` and a third for
    the compiler's own, a v5e's default of 16 MiB (so that another chip's
    default changes nothing), and MORE where the weights are small enough
    to lie in VMEM beside that. XLA may stage a custom call's whole
    operand in VMEM ahead of the call wherever it fits beside the
    kernel's scope: it did so with ``kanana-2-30b-a3b``'s 48 MiB of gate
    matrices a layer (``slice-start`` after the layer's attention,
    ``ConcatBitcast`` before ``moe_gate``; sandbox compile and my chip
    run, PR 36), all 16 experts' whether hit or not, and the trace then
    names their read under ``slice-start`` and not under the kernel:
    ``moe_gate`` took 16 us where ``moe_up`` took 56, and the roofline
    that counts the kernel's bytes read 122%. So the scope leaves XLA
    room for half the weights at the most, up to three quarters of VMEM:
    the weights stay in HBM and the kernel that multiplies them is the
    one that reads them. Matrices of 224 MiB and more (``lfm2-8b-a1b``,
    ``mimo-v2-flash``) keep the 16 MiB."""
    scope = min(max(_GMM_VMEM // 3 * 4, _VMEM - weight_bytes // 2),
                _VMEM // 4 * 3)
    # the accumulator carries over the contraction axis
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.ARBITRARY,) * 3, vmem_limit_bytes=scope)


def _cuts(n: int) -> list[int]:
    """What a dimension of ``n`` may be cut to, largest first: ``n``
    itself (a block may always span a dimension), then every multiple of
    a lane tile that divides it."""
    return [n] + [c for c in range((n - 1) // LANES * LANES, 0, -LANES)
                  if n % c == 0]


def _blocks(tm: int, k: int, n: int, itemsize: int) -> tuple[int, int]:
    """``(tk, tn)``: the weight block of a product of row tiles of ``tm``
    with ``(k, n)`` matrices, chosen BY BYTES. The contraction is whole
    where a block of a megabyte (or the matrix) then fits ``_GMM_VMEM``:
    no accumulator round trip, and the ``x`` block ``(tm, k)`` is fetched
    once a row tile. Else ``tk`` is the largest cut of ``k`` that leaves
    such a block its room. ``tn`` is the largest cut of ``n`` that fits
    beside it."""
    def taken(tk, tn):
        return (2 * (tm * tk + tk * tn + tm * tn) * itemsize
                + 4 * tm * tn)

    least = min(_GMM_BLOCK_LEAST, k * n * itemsize)
    fallback = None
    for tk in _cuts(k):
        tn = next((c for c in _cuts(n) if taken(tk, c) <= _GMM_VMEM), None)
        if tn is None:
            continue
        if tk * tn * itemsize >= least:
            return tk, tn
        fallback = fallback or (tk, tn)
    # nothing fits with a block of a megabyte: the largest that fits, or
    # the smallest cuts there are
    return fallback or (_cuts(k)[-1], _cuts(n)[-1])


def _gmm_kernel(group_ref, x_ref, w_ref, o_ref, *acc, k_tiles: int):
    def product():
        return jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if not acc:  # the contraction is whole: one product a block
        o_ref[...] = product().astype(o_ref.dtype)
        return
    (acc,) = acc
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += product()

    @pl.when(kk == k_tiles - 1)
    def _finalize():
        o_ref[...] = acc[:].astype(o_ref.dtype)


def grouped_matmul(x, w, tile_group, live_tiles, *, tm: int,
                   tn: int | None = None, tk: int | None = None,
                   interpret: bool | None = None, name: str | None = None):
    """``out[r] = x[r] @ w[group of r's tile]``.

    ``x`` is (M, K) with ``M`` a multiple of ``tm``; ``w`` is (G, K, N);
    ``tile_group`` is (M // tm,) int32, the group of each row tile;
    ``live_tiles`` is a scalar int32, traced: tiles ``>= live_tiles``
    are dead. A dead tile takes NO grid step and its rows of the result
    are not written: they hold whatever the buffer held, and a caller
    reads none of them. (One tile runs even where none is live, so that
    the grid is never empty.) Products accumulate in float32; the result
    is (M, N) in ``x``'s dtype. ``tk``/``tn`` override the block
    :func:`_blocks` chooses (tests, and the tool that timed the choice);
    ``name`` names the kernel in a device trace.

    Consecutive tiles of ONE group find their weight block in place
    where the block is the whole matrix; where the matrix is cut along
    ``N`` a group's second tile reads its blocks again."""
    m, k = x.shape
    g, k2, n = w.shape
    if k != k2 or m % tm or tile_group.shape != (m // tm,):
        raise ValueError(
            f"grouped_matmul: x {x.shape}, w {w.shape}, tile_group "
            f"{tile_group.shape} do not fit row tiles of {tm}"
        )
    if x.dtype != w.dtype:
        raise ValueError(
            f"grouped_matmul needs one dtype, got {x.dtype}/{w.dtype}"
        )
    if interpret is None:
        from mmlspark_tpu.core.env import is_tpu

        interpret = not is_tpu()
    chosen = _blocks(tm, k, n, x.dtype.itemsize)
    tk, tn = tk or chosen[0], tn or chosen[1]
    if k % tk or n % tn:
        raise ValueError(
            f"grouped_matmul: blocks of {tk} x {tn} do not divide {k} x {n}"
        )
    m_tiles, n_tiles, k_tiles = m // tm, n // tn, k // tk
    tile_group = jnp.clip(tile_group.astype(jnp.int32), 0, g - 1)
    live = jnp.clip(jnp.asarray(live_tiles, jnp.int32), 1, m_tiles)

    return pl.pallas_call(
        partial(_gmm_kernel, k_tiles=k_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(live, n_tiles, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, kk, group: (i, kk),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tk, tn),
                             lambda i, j, kk, group: (group[i], kk, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda i, j, kk, group: (i, j),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if k_tiles > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_compiler_params(w.size * w.dtype.itemsize),
        interpret=bool(interpret),
        **({"name": name} if name else {}),
    )(tile_group, x, w)
