"""Sequence/context parallelism: ring attention and all-to-all (Ulysses).

The reference scales sequence length by not scaling it (SURVEY.md §5: no
ring attention, context parallel, or Ulysses anywhere; pad-to-max in
notebook UDFs). For the TPU build long context is first-class: sequences
shard over a mesh axis and attention runs either

- **ring**: K/V blocks rotate around the ``seq`` axis with
  ``lax.ppermute`` (one ICI hop per step) while each device folds the
  visiting block into a streaming softmax — memory per device stays
  O(S/n · S/n) and the full (S, S) matrix never exists anywhere; or
- **ulysses**: two ``lax.all_to_all`` collectives re-shard from
  sequence-sharded to head-sharded, run ordinary dense attention on full
  sequences for H/n local heads, and shard back.

Both are exact (they must equal :func:`dense_attention` bit-for-bit up to
float tolerance — tested), differentiable (scan + collectives transpose
cleanly), and compose with data parallelism: the batch dimension stays on
the ``data`` axis throughout.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.ops.attention import (
    NEG_INF,
    causal_block_mask,
    dense_attention,
    finalize_softmax,
    softmax_block_update,
)
from mmlspark_tpu.parallel.mesh import DATA_AXIS, SEQUENCE_AXIS


def _ring_window_steps(n: int, chunk: int, window: int | None,
                       causal: bool) -> int:
    """Number of LIVE ring rotations. Causal+window bounds the oldest
    attended key of q chunk i at ``i*chunk - window + 1``; rotation t
    hands device i the kv chunk i - t (older positions as t grows), and
    the chunk at t is fully outside the window iff
    ``t*chunk > window + chunk - 2`` — a bound INDEPENDENT of i, so the
    dead rotations (their compute and their ppermute hops) can be
    dropped for every device at once: windowed ring attention
    communicates O(window), not O(S). Rotations t > i wrap to
    causal-dead chunks anyway, so dropping the tail is exact."""
    if not causal or window is None:
        return n
    return min(n, (window + chunk - 2) // chunk + 1)


def _ring_inner(q, k, v, *, axis_name: str, causal: bool,
                window: int | None, scale):
    """Per-shard ring attention body (runs under shard_map).

    q, k, v: local sequence chunks (B, S/n, H, D); K/V may carry FEWER
    heads (grouped-query attention) — the ring rotates the NARROW
    (B, S/n, Hkv, D) chunks, so GQA's ICI-traffic saving (the reason
    serving stacks pick it) survives sharding, and the repeat to query
    heads happens per-step inside the local softmax update where XLA
    fuses it into the score einsum. Chunk ownership after ``step``
    rotations: device i holds K/V chunk (i - step) mod n, which gives
    the global kv offset for causal masking.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    rep = h // hk
    if scale is None:
        scale = d ** -0.5

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]
    n_steps = _ring_window_steps(n, sk, window, causal)

    def body(carry, step):
        m, l, acc, kc, vc = carry
        src = (idx - step) % n
        mask = (
            causal_block_mask(sq, sk, idx * sq, src * sk, window=window)
            if causal else None
        )
        kf = kc if rep == 1 else jnp.repeat(kc, rep, axis=2)
        vf = vc if rep == 1 else jnp.repeat(vc, rep, axis=2)
        m, l, acc = softmax_block_update((m, l, acc), q, kf, vf, scale, mask)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), ()

    (m, l, acc, _, _), _ = lax.scan(
        body, (m0, l0, acc0, k, v), jnp.arange(n_steps)
    )
    return finalize_softmax(l, acc, q.dtype)


def _ulysses_inner(q, k, v, *, axis_name: str, causal: bool,
                   window: int | None, scale):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern): trade
    the sequence sharding for head sharding, attend locally, trade back.

    The local attention over the FULL sequence uses the Pallas flash
    kernel on TPU (O(S·d) memory — after the all-to-all each device sees
    the whole sequence, so dense would re-materialize (S, S) scores and
    defeat the point of sharding long contexts); off-TPU the XLA dense
    path keeps the CPU test mesh fast. Both are exact, verified against
    each other in tests/test_parallel_attention.py.
    """
    a2a = partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    # (B, S/n, H, D) -> (B, S, H/n, D): split heads, concat sequence
    q, k, v = (a2a(t, split_axis=2, concat_axis=1) for t in (q, k, v))
    from mmlspark_tpu.core.env import is_tpu

    if is_tpu():
        from mmlspark_tpu.ops.flash_attention import flash_attention

        o = flash_attention(q, k, v, causal=causal, window=window,
                            scale=scale)
    else:
        o = dense_attention(q, k, v, causal=causal, window=window,
                            scale=scale)
    # back to sequence-sharded layout
    return a2a(o, split_axis=1, concat_axis=2)


def _sharded_call(inner, q, k, v, mesh, axis: str, batch_axis: str):
    # shard the batch dim too when it divides evenly (dp × sp); otherwise
    # (e.g. the single-example init trace) replicate it within the map
    batch = (
        batch_axis
        if batch_axis in mesh.shape and q.shape[0] % mesh.shape[batch_axis] == 0
        else None
    )
    spec = P(batch, axis, None, None)
    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ring_attention(q, k, v, mesh, *, axis: str = SEQUENCE_AXIS,
                   causal: bool = False, window: int | None = None,
                   scale=None, batch_axis: str = DATA_AXIS):
    """Exact attention with q/k/v sharded on ``axis`` over ``mesh``.

    Works inside or outside an enclosing ``jit``; XLA reshards inputs to
    the sequence layout if they arrive otherwise. ``window`` is the
    causal sliding window (flash-kernel semantics), applied through the
    per-step block mask.
    """
    if window is not None:
        if not causal:
            raise FriendlyError("window requires causal=True")
        if int(window) < 1:
            raise FriendlyError(f"window must be >= 1, got {window}")
    _check_gqa(q, k, v, "ring")
    _check(mesh, axis, q.shape[1], "ring")
    inner = partial(_ring_inner, axis_name=axis, causal=causal,
                    window=window, scale=scale)
    return _sharded_call(inner, q, k, v, mesh, axis, batch_axis)


def ulysses_attention(q, k, v, mesh, *, axis: str = SEQUENCE_AXIS,
                      causal: bool = False, window: int | None = None,
                      scale=None, batch_axis: str = DATA_AXIS):
    """All-to-all sequence-parallel attention; q heads AND kv heads must
    divide by the axis size (each device attends H/n full-length query
    heads against Hkv/n key/value heads — the all-to-all re-shard
    preserves the GQA group ratio, and the local flash/dense call does
    the grouped expansion)."""
    _check_gqa(q, k, v, "ulysses")
    n = _check(mesh, axis, q.shape[1], "ulysses")
    if q.shape[2] % n or k.shape[2] % n:
        raise FriendlyError(
            f"ulysses needs q heads ({q.shape[2]}) and kv heads "
            f"({k.shape[2]}) divisible by mesh axis '{axis}' ({n})"
        )
    if window is not None:
        if not causal:
            raise FriendlyError("window requires causal=True")
        if int(window) < 1:
            raise FriendlyError(f"window must be >= 1, got {window}")
    inner = partial(_ulysses_inner, axis_name=axis, causal=causal,
                    window=window, scale=scale)
    return _sharded_call(inner, q, k, v, mesh, axis, batch_axis)


def _check_gqa(q, k, v, what: str) -> None:
    """Same grouped-query contract as dense/flash (ADVICE r4: direct
    callers used to hit an opaque einsum shape error deep in the inner
    body instead of this message)."""
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise FriendlyError(
            f"{what} attention needs k/v heads equal and dividing q "
            f"heads, got q={q.shape[2]} k={k.shape[2]} v={v.shape[2]}"
        )


def _check(mesh, axis: str, seq_len: int, what: str) -> int:
    if axis not in mesh.shape:
        raise FriendlyError(
            f"{what} attention needs axis '{axis}' in the mesh; "
            f"mesh axes: {dict(mesh.shape)}"
        )
    n = mesh.shape[axis]
    if seq_len % n:
        raise FriendlyError(
            f"{what} attention needs sequence length ({seq_len}) divisible "
            f"by mesh axis '{axis}' ({n})"
        )
    return n
