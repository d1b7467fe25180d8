"""Rotary position embeddings (RoPE) for the transformer family.

Applies the standard rotate-half formulation (GPT-NeoX convention): the
head dimension is split into two halves which form the (real, imaginary)
parts of d/2 complex pairs, and each pair is rotated by an angle
proportional to the token position — making the q·k dot product a
function of RELATIVE position only. No learned parameters, no (S, E)
positional table in the checkpoint, and positions beyond training length
extrapolate structurally.

TPU notes: the cos/sin tables are computed at trace time as (S, D/2)
f32 constants, broadcast over (B, H) — elementwise work XLA fuses
straight into the surrounding projections; no gather is involved
(positions are an iota unless explicitly provided).
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_tables(positions, head_dim: int, base: float = 10000.0):
    """cos/sin tables, each ``positions.shape + (head_dim // 2,)``
    float32.

    ``positions`` is any integer/float vector — contiguous iota for the
    common case, but arbitrary (e.g. cache offsets) values work — or a
    (B, S) matrix of PER-ROW positions (the serving engine's fused
    decode step, where each batch row is at its own absolute offset).
    """
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    half = head_dim // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions=None, *, base: float = 10000.0,
               rotary_dim: int | None = None, interleave: bool = False):
    """Rotate ``x`` of shape (B, S, H, D) by position; D must be even.

    ``positions`` defaults to 0..S-1; a (B, S) matrix applies per-row
    positions (multi-tenant decode). The rotation is applied in f32 and
    cast back to ``x.dtype`` (bf16 activations keep their dtype through
    the attention stack).

    ``rotary_dim`` (even, at most D) rotates only the FIRST
    ``rotary_dim`` dimensions of every head, as the half-rotation over
    those dimensions alone; the others pass unchanged (partial rotary
    embeddings). ``base`` is the layer's own: a model with layers of
    several kinds hands each its base.
    """
    b, s, h, d = x.shape
    if rotary_dim is not None and int(rotary_dim) != d:
        r = int(rotary_dim)
        if r % 2 or not 0 < r < d:
            raise ValueError(
                f"rotary_dim must be even and in (0, {d}], got {rotary_dim}"
            )
        rotated = apply_rope(x[..., :r], positions, base=base,
                             interleave=interleave)
        return jnp.concatenate((rotated, x[..., r:]), axis=-1)
    if positions is None:
        positions = jnp.arange(s)
    positions = jnp.asarray(positions)
    cos, sin = rope_tables(positions, d, base)  # positions.shape + (D/2,)
    if positions.ndim == 2:  # (B, S, D/2): broadcast over H only
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:  # (S, D/2): broadcast over (B, H)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    if interleave:
        pairs = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack(
            (x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1
        ).reshape(b, s, h, d).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1
    ).astype(x.dtype)
