"""Sequence-dim sharding for recurrent models (BiLSTM long-context).

The reference's only sequence model is an opaque downloaded CNTK BiLSTM
run through CNTKModel with notebook-side pad-to-max batching (notebook
304 - Medical Entity Extraction; SURVEY.md §5 — the reference has no
sequence parallelism of any kind). Here long sequences shard over a mesh
axis: each device holds T/S tokens of activations, so the memory
high-water mark scales down with the axis size — the long-context story
for recurrent nets, complementing ring/Ulysses attention for
transformers (context_parallel.py).

A recurrence is sequential in time, so sharding time cannot shard the
*latency*: the design is a CHUNKED RECURRENCE CHAIN under ``shard_map``.
Every device holds one contiguous time chunk; the chain runs S rounds,
each round every device scans its local chunk and hands its final
(c, h) state to the next device via ``lax.ppermute``; device k's round-k
scan starts from the true upstream state, and a ``where`` keeps exactly
that round's outputs. Total compute per device = S * (T/S) = T steps
(same FLOPs as replicating the whole sequence), but activations stay
O(T/S) per device — compute is the price, memory is the win, and the
tiny per-round boundary state (2*B*H floats) rides the ICI.

The cell math is NOT reimplemented: each step calls the flax cell's own
``apply`` on the variables produced by ``build_model("bilstm_tagger")``,
so seq-parallel output is bit-compatible with the dense
``graph.apply`` path up to reduction order.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["bilstm_seq_parallel_apply", "bilstm_seq_parallel_train_step"]


def _chunk_scan(cell, params, carry, xs, reverse: bool):
    """Scan one local time chunk with the flax cell; returns the final
    carry and per-token hidden states. ``xs``: (B, Tc, E)."""

    def step(c, x_t):
        c2, h = cell.apply({"params": params}, c, x_t)
        return c2, h

    xs_t = jnp.swapaxes(xs, 0, 1)  # (Tc, B, E) — scan over time
    final, hs = lax.scan(step, carry, xs_t, reverse=reverse)
    return final, jnp.swapaxes(hs, 0, 1)  # (B, Tc, H)


def _chain(cell, params, x_local, hidden: int, axis: str, reverse: bool,
           vary_axes: tuple = ()):
    """Chunked recurrence chain over mesh axis ``axis`` (see module
    docstring). Runs inside shard_map; the round count is the static
    axis size, so the python loop unrolls at trace time."""
    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    b, tc, _ = x_local.shape
    # mark the zeros varying over every mesh axis for shard_map's
    # manual-axes typing: the chain's carries and outputs differ per
    # device (the scanned x_local varies over all of them)
    def varying(t):
        return lax.pcast(t, vary_axes, to="varying") if vary_axes else t

    zero = varying(jnp.zeros((b, hidden), x_local.dtype))
    # flax LSTM carry is (c, h)
    carry = (zero, zero)
    ys = varying(jnp.zeros((b, tc, hidden), x_local.dtype))
    # state flows downstream in time: to higher ranks forward, lower
    # ranks backward. No wraparound — rank 0 (resp. n-1) starts from
    # zeros, matching the dense scan's initial carry.
    if reverse:
        perm = [(i + 1, i) for i in range(n - 1)]
    else:
        perm = [(i, i + 1) for i in range(n - 1)]
    for k in range(n):
        turn = idx == (n - 1 - k if reverse else k)
        final, hs = _chunk_scan(cell, params, carry, x_local, reverse)
        ys = jnp.where(turn, hs, ys)
        if k == n - 1:
            break
        handed = tuple(lax.ppermute(c, axis, perm) for c in final)
        nxt = idx == (n - 2 - k if reverse else k + 1)
        carry = tuple(
            jnp.where(nxt, h, c) for h, c in zip(handed, carry)
        )
    return ys


def bilstm_seq_parallel_apply(
    graph: Any,
    variables: dict,
    ids: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    data_axis: str | None = "data",
) -> jax.Array:
    """Forward pass of a ``bilstm_tagger`` graph with the time dimension
    sharded over ``mesh[seq_axis]`` (and batch over ``mesh[data_axis]``
    when present). Differentiable — ppermute transposes cleanly, so the
    same function serves seq-sharded training.

    ``ids``: (B, T) int32, T divisible by the seq-axis size.
    Returns (B, T, num_tags) float32 logits, sharded like the input.
    """
    import flax.linen as nn

    params = variables["bilstm"]["params"]
    fwd_p, bwd_p = (
        params["OptimizedLSTMCell_0"], params["OptimizedLSTMCell_1"],
    )
    hidden = fwd_p["hi"]["kernel"].shape[0]
    cell = nn.OptimizedLSTMCell(hidden)
    embed = variables["embed"]["params"]["Embed_0"]["embedding"]
    head = variables["z"]["params"]["Dense_0"]

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if seq_axis not in axis_sizes:
        raise ValueError(
            f"mesh {dict(axis_sizes)} has no '{seq_axis}' axis — add one "
            "(size 1 is fine) or use graph.apply for unsharded inference"
        )
    n_seq = axis_sizes[seq_axis]
    d_ax = data_axis if data_axis in axis_sizes else None
    if ids.shape[1] % n_seq:
        raise ValueError(
            f"sequence length {ids.shape[1]} not divisible by "
            f"{seq_axis} axis size {n_seq}"
        )
    if d_ax is not None and ids.shape[0] % axis_sizes[d_ax]:
        raise ValueError(
            f"batch size {ids.shape[0]} not divisible by "
            f"{d_ax} axis size {axis_sizes[d_ax]}"
        )

    io_spec = P(d_ax, seq_axis)

    def local(embed, fwd_p, bwd_p, head, ids_local):
        x = jnp.take(embed, ids_local, axis=0)  # (b, tc, E) token-local
        vary = tuple(mesh.axis_names)
        hf = _chain(cell, fwd_p, x, hidden, seq_axis, reverse=False,
                    vary_axes=vary)
        hb = _chain(cell, bwd_p, x, hidden, seq_axis, reverse=True,
                    vary_axes=vary)
        h = jnp.concatenate([hf, hb], axis=-1)
        # TokenLogits math: bf16 compute, f32 params and output
        hb16 = h.astype(jnp.bfloat16)
        out = hb16 @ head["kernel"].astype(jnp.bfloat16)
        out = out + head["bias"].astype(jnp.bfloat16)
        return out.astype(jnp.float32)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), io_spec),
        out_specs=P(d_ax, seq_axis),
    )
    ids = jax.device_put(ids, NamedSharding(mesh, io_spec))
    return fn(embed, fwd_p, bwd_p, head, jnp.asarray(ids))


def bilstm_seq_parallel_train_step(
    graph: Any,
    variables: dict,
    ids: jax.Array,
    tags: jax.Array,
    mesh: Mesh,
    *,
    learning_rate: float = 5e-2,
    seq_axis: str = "seq",
    data_axis: str | None = "data",
):
    """One jit-compiled SGD step with batch sharded over ``data_axis``
    AND time sharded over ``seq_axis`` simultaneously — the mixed-axis
    training leg for BASELINE config #5 (the reference trains its BiLSTM
    DP-only inside CNTK; time sharding is the TPU-native long-context
    upgrade). The backward runs through the chunked recurrence chain:
    ``ppermute`` transposes to the reversed chain, and shard_map's
    transpose inserts the gradient ``psum`` over both mesh axes for the
    replicated parameters.

    Returns ``(loss, new_variables)``; call repeatedly with the returned
    variables. The compiled step is cached per (graph, mesh, lr, axes)
    so a training loop pays one trace, not one per step.
    """
    key = (mesh, float(learning_rate), seq_axis, data_axis)
    per_graph = _TRAIN_STEP_CACHE.setdefault(key, {})
    hit = per_graph.get(id(graph))
    fn = hit[0] if hit else None
    if fn is None:

        def step(variables, ids, tags):
            def loss_fn(v):
                logits = bilstm_seq_parallel_apply(
                    graph, v, ids, mesh,
                    seq_axis=seq_axis, data_axis=data_axis,
                )
                lp = jax.nn.log_softmax(logits)
                ll = jnp.take_along_axis(lp, tags[..., None], axis=-1)
                return -jnp.mean(ll)

            loss, grads = jax.value_and_grad(loss_fn)(variables)
            new_vars = jax.tree_util.tree_map(
                lambda p, g: p - learning_rate * g, variables, grads
            )
            return loss, new_vars

        fn = jax.jit(step)
        # graph ref held in the value so the id key cannot be reused by
        # a new object while this entry is alive; bound so a sweep over
        # graphs/meshes/lrs cannot pin executables without limit (each
        # entry holds compiled device buffers)
        per_graph[id(graph)] = (fn, graph)
        while sum(len(v) for v in _TRAIN_STEP_CACHE.values()) > _CACHE_MAX:
            oldest_key = next(iter(_TRAIN_STEP_CACHE))
            oldest = _TRAIN_STEP_CACHE[oldest_key]
            oldest.pop(next(iter(oldest)), None)
            if not oldest:
                del _TRAIN_STEP_CACHE[oldest_key]
    return fn(variables, jnp.asarray(ids), jnp.asarray(tags))


#: (mesh, lr, seq_axis, data_axis) -> {id(graph): (jitted step, graph)}
_TRAIN_STEP_CACHE: dict = {}
_CACHE_MAX = 16
