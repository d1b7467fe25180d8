"""Grouped matrix products as one Pallas TPU kernel: the rows of ``x``
are sorted by group, every group padded to whole row tiles, and each row
tile is multiplied with ITS group's matrix.

This is the product an expert layer needs once tokens are sorted by the
expert they were routed to (parallel/expert.py ``moe_ffn_held``): no
``(tokens, D, F)`` weight copy is gathered, an expert nobody chose is
never read, and every shape is fixed. A scalar-prefetched table says
which group a row tile belongs to, so the weight block's index map picks
the matrix; tiles past the live ones are skipped, and their index maps
stay on the last live block so that nothing is fetched for them.

Off the TPU the kernel runs in interpreter mode, as the attention
kernels do.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the dead tiles' index maps lean on the grid running in order
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=(pltpu.ARBITRARY,) * 3,
)


def _tile(n: int, most: int) -> int:
    """The largest of ``most, most/2, ... 128`` that divides ``n``; the
    whole of ``n`` where none does (a block may always span a dimension)."""
    t = most
    while t >= 128:
        if n % t == 0:
            return t
        t //= 2
    return n


def _gmm_kernel(group_ref, live_ref, x_ref, w_ref, o_ref, acc, *,
                k_tiles: int):
    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    @pl.when(i < live_ref[0])
    def _update():
        acc[:] += jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == k_tiles - 1)
    def _finalize():
        # a dead tile writes zeros: its rows belong to nobody
        o_ref[...] = acc[:].astype(o_ref.dtype)


def grouped_matmul(x, w, tile_group, live_tiles, *, tm: int,
                   tn: int = 512, tk: int = 1024,
                   interpret: bool | None = None, name: str | None = None):
    """``out[r] = x[r] @ w[group of r's tile]``.

    ``x`` is (M, K) with ``M`` a multiple of ``tm``; ``w`` is (G, K, N);
    ``tile_group`` is (M // tm,) int32, the group of each row tile;
    ``live_tiles`` is a scalar int32: tiles ``>= live_tiles`` are dead
    and come out as zeros. Products accumulate in float32; the result
    is (M, N) in ``x``'s dtype. ``name`` names the kernel in a device
    trace."""
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
    tk, tn = _tile(k, tk), _tile(n, tn)
    m_tiles, n_tiles, k_tiles = m // tm, n // tn, k // tk
    tile_group = jnp.clip(tile_group.astype(jnp.int32), 0, g - 1)
    live = jnp.clip(jnp.asarray(live_tiles, jnp.int32), 0,
                    m_tiles).reshape(1)

    def last_live(i, live):
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    def x_im(i, j, kk, group, live):
        return (last_live(i, live),
                jnp.where(i < live[0], kk, k_tiles - 1))

    def w_im(i, j, kk, group, live):
        on = i < live[0]
        return (group[last_live(i, live)],
                jnp.where(on, kk, k_tiles - 1),
                jnp.where(on, j, n_tiles - 1))

    return pl.pallas_call(
        partial(_gmm_kernel, k_tiles=k_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m_tiles, n_tiles, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), x_im, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tk, tn), w_im, memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda i, j, kk, group, live: (i, j),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_SEMANTICS,
        interpret=bool(interpret),
        **({"name": name} if name else {}),
    )(tile_group, live, x, w)
