"""One decode step of a gated short convolution, in place on the serving
pool's state.

A gated short-convolution layer (the LFM2 line's ``conv`` operator) keeps,
for decoding, the last ``K - 1`` inputs of its depthwise causal filter:
``g_{t-K+1} .. g_{t-1}`` with ``g = B * u``, the two gated thirds of the
layer's input projection. One step takes the slot's ``K - 1`` rows, forms
``g_t``, emits ``y_t = C_t * (w_0 g_{t-K+1} + .. + w_{K-1} g_t)`` and keeps
``[g_{t-K+2} .. g_t]``.

:func:`conv_decode` does that for every slot of a pool entry
(:class:`mmlspark_tpu.ops.kv_cache.SlotState`: ``(S, (K - 1) * W)``, a
slot's rows side by side, oldest first) in ONE kernel whose state output
aliases its state input: the pool is updated where it lies, dead slots
keep their rows, and a device trace shows one named operation a layer a
micro-step. It moves a few megabytes a call and is bound by latency, not
by bytes: it exists so that the update is in place, visible and countable.
:func:`conv_decode_reference` is its ``jax.numpy`` oracle.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: slots a grid step: whole bfloat16 sublane tiles of a 128-slot pool
_SLOT_BLOCK = 32


def conv_decode_reference(proj, state, taps, live):
    """What :func:`conv_decode` computes, in plain ``jax.numpy``."""
    k, w = taps.shape
    f32 = jnp.float32
    b_gate, c_gate, u = (proj[:, j * w:(j + 1) * w] for j in range(3))
    g = (b_gate.astype(f32) * u.astype(f32)).astype(state.dtype)
    rows = [state[:, j * w:(j + 1) * w] for j in range(k - 1)] + [g]
    c = sum(taps[j].astype(f32) * rows[j].astype(f32) for j in range(k))
    y = (c_gate.astype(f32) * c).astype(u.dtype)
    new = jnp.concatenate(rows[1:], axis=1)
    return y, jnp.where(live[:, None], new, state)


def _kernel(b_ref, c_ref, u_ref, live_ref, taps_ref, state_ref, y_ref,
            out_ref, *, k: int, w: int):
    f32 = jnp.float32
    # through the state's dtype: the step's own input enters the sum as the
    # next step will read it back
    g = (b_ref[...].astype(f32) * u_ref[...].astype(f32)).astype(
        out_ref.dtype)
    rows = [state_ref[:, j * w:(j + 1) * w] for j in range(k - 1)] + [g]
    c = taps_ref[0:1, :] * rows[0].astype(f32)
    for j in range(1, k):
        c = c + taps_ref[j:j + 1, :] * rows[j].astype(f32)
    y_ref[...] = (c_ref[...].astype(f32) * c).astype(y_ref.dtype)
    live = live_ref[...] > 0                               # (slots, 1)
    for j in range(k - 1):
        # through float32: the select needs no packed-dtype broadcast
        out_ref[:, j * w:(j + 1) * w] = jnp.where(
            live, rows[j + 1].astype(f32), rows[j].astype(f32)
        ).astype(out_ref.dtype)


def conv_decode(proj, state, taps, live, *,
                interpret: bool | None = None, name: str | None = None):
    """One step of every slot: ``proj`` (S, 3 * W), the layer's input
    projection ``[B ; C ; u]`` as it leaves the product (the kernel reads
    its thirds where they lie: no slice is made), the pool's ``state`` (S,
    (K - 1) * W), ``taps`` (K, W) with ``taps[K - 1]`` on the current
    position, ``live`` (S,) bool. Returns ``(y (S, W) in proj's dtype,
    state)``: the state shifted by one input where the slot is live, as it
    was where it is dead, written in place on a donated pool
    (``input_output_aliases``). The filter's sum is in float32 over inputs
    held in the state's dtype. ``name`` names the kernel in a device
    trace."""
    from mmlspark_tpu.ops.flash_attention import _interpret

    return _conv_decode(proj, state, taps, live,
                        interpret=_interpret(interpret),
                        name=name or "conv_decode")


# jitted where it stands, as the decode kernels of ops/flash_attention.py
# are: a decode block calls it once a conv layer and the engine builds a
# block a ladder size, so the body is traced once a signature
@partial(jax.jit, static_argnames=("interpret", "name"))
def _conv_decode(proj, state, taps, live, *, interpret: bool, name: str):
    k, w = taps.shape
    s = proj.shape[0]
    if proj.shape != (s, 3 * w) or state.shape != (s, (k - 1) * w):
        raise ValueError(
            f"conv_decode takes a projection (S, {3 * w}) and a state (S, "
            f"{(k - 1) * w}) for {k} taps {w} wide, got {proj.shape} and "
            f"{state.shape}")
    blk = _SLOT_BLOCK if s % _SLOT_BLOCK == 0 else s

    def rows(width, third=0):
        return pl.BlockSpec((blk, width), lambda i: (i, third),
                            memory_space=pltpu.VMEM)

    y, new = pl.pallas_call(
        partial(_kernel, k=k, w=w),
        grid=(s // blk,),
        in_specs=[rows(w, 0), rows(w, 1), rows(w, 2), rows(1),
                  pl.BlockSpec((k, w), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
                  rows((k - 1) * w)],
        out_specs=[rows(w), rows((k - 1) * w)],
        out_shape=(jax.ShapeDtypeStruct((s, w), proj.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={5: 1},
        interpret=bool(interpret),
        name=name,
    )(proj, proj, proj, live.astype(jnp.int32)[:, None],
      taps.astype(jnp.float32), state)
    return y, new
