"""The stream update of a residual of several streams (manifold-constrained
hyper-connections, mHC): after a sublayer ``F`` ran on the streams' mix,
``X' = H_res X + H_post^T F``, ``n`` new streams each a weighted sum of the
``n`` old ones and the sublayer's output.

One kernel, ``hc_mix`` in a device trace: a grid step takes a block of
rows of every old stream and of ``F`` and writes one new stream's block,
the float32 streams read once a new stream. It moves the residual's bytes
(``n`` streams in, one out, a step) and is bound by them.
:func:`stream_mix_reference` is its ``jax.numpy`` oracle.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows and channels a grid step: 128 x 1,024 float32 is half a MiB a block
_ROW_BLOCK = 128
_CHANNEL_BLOCK = 1024
LANES = 128


def stream_mix_reference(res, x, post, out):
    """What :func:`stream_mix` computes, in plain ``jax.numpy``."""
    f32 = jnp.float32
    return (jnp.einsum("rij,rjc->ric", res.astype(f32), x.astype(f32),
                       precision=jax.lax.Precision.HIGHEST)
            + post.astype(f32)[..., None] * out.astype(f32)[:, None, :])


def _kernel(coef_ref, *refs, n: int):
    streams, out_ref, new_ref = refs[:n], refs[n], refs[n + 1]
    coef = coef_ref[0]                                   # (rows, lanes)
    acc = coef[:, n:n + 1] * out_ref[...].astype(jnp.float32)
    for j in range(n):
        acc = acc + coef[:, j:j + 1] * streams[j][...]
    new_ref[...] = acc


def stream_mix(res, x, post, out, *, interpret: bool | None = None,
               name: str | None = None):
    """``x`` (R, n, C) float32 streams of R rows, ``res`` (R, n, n),
    ``post`` (R, n), a sublayer's output ``out`` (R, C): returns ``x'``
    (R, n, C) float32, ``x'_i = sum_j res_ij x_j + post_i out``. ``name``
    names the kernel in a device trace (``hc_mix``)."""
    from mmlspark_tpu.ops.flash_attention import _interpret

    return _stream_mix(res, x, post, out, interpret=_interpret(interpret),
                       name=name or "hc_mix")


@partial(jax.jit, static_argnames=("interpret", "name"))
def _stream_mix(res, x, post, out, *, interpret: bool, name: str):
    rows, n, c = x.shape
    if res.shape != (rows, n, n) or post.shape != (rows, n) or (
            out.shape != (rows, c)) or n + 1 > LANES:
        raise ValueError(
            f"stream_mix takes res (R, n, n), x (R, n, C), post (R, n) and "
            f"out (R, C), got {res.shape}, {x.shape}, {post.shape}, "
            f"{out.shape}")
    br = _ROW_BLOCK if rows % _ROW_BLOCK == 0 else rows
    bc = _CHANNEL_BLOCK if c % _CHANNEL_BLOCK == 0 else c
    per = c // bc
    # new stream i's coefficients in lanes: res_i0 .. res_i(n-1), post_i
    coef = jnp.concatenate((res.astype(jnp.float32),
                            post.astype(jnp.float32)[..., None]), axis=-1)
    coef = jnp.pad(coef.transpose(1, 0, 2),
                   ((0, 0), (0, 0), (0, LANES - n - 1)))

    def stream(j):
        return pl.BlockSpec((br, bc), lambda i, r, k: (r, j * per + k),
                            memory_space=pltpu.VMEM)

    new = pl.pallas_call(
        partial(_kernel, n=n),
        grid=(n, rows // br, per),
        in_specs=[pl.BlockSpec((1, br, LANES), lambda i, r, k: (i, r, 0),
                               memory_space=pltpu.VMEM),
                  *(stream(j) for j in range(n)),
                  pl.BlockSpec((br, bc), lambda i, r, k: (r, k),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((br, bc), lambda i, r, k: (r, i * per + k),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, n * c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=bool(interpret),
        name=name,
    )(coef, *([x.astype(jnp.float32).reshape(rows, n * c)] * n), out)
    return new.reshape(rows, n, c)
