"""Pipeline parallelism: GPipe-style microbatched stage rotation.

The reference has no pipeline parallelism of any kind (SURVEY.md §2.5: Spark
partitions + CNTK's MPI data parallelism are the only strategies). For the
TPU build, pipeline parallelism is a first-class scaling axis: a model's
homogeneous trunk (e.g. transformer blocks) is partitioned into contiguous
stages laid out over the ``pipe`` mesh axis, and microbatches stream through
the stages with one ``lax.ppermute`` hop per tick — activations ride ICI
between neighboring devices, never the host.

Design (the scaling-book / GPipe schedule, expressed as one SPMD program):

- stage parameters are *stacked* on a leading dim of size ``n_stages`` and
  sharded over the ``pipe`` axis — each device holds exactly its stage's
  weights;
- ``pipeline_apply`` runs ``M + n_stages - 1`` ticks inside a
  ``lax.scan``. At tick ``t`` device ``i`` processes microbatch ``t - i``
  (the classic pipeline diagonal): rank 0 feeds microbatch ``t`` from the
  input buffer, every rank applies its stage, and outputs shift one rank
  down the ring via ``ppermute``;
- the final rank accumulates finished microbatches; one masked ``psum``
  broadcasts the result so every rank returns the same value (keeps the
  output spec replicated over ``pipe``);
- everything is differentiable: scan + ppermute transpose cleanly, so the
  backward pass is automatically the reverse pipeline (the 1F1B-style
  bubble optimization is left to XLA's latency-hiding scheduler).

Composes with data parallelism: the microbatch batch dim stays sharded on
``data`` throughout; mesh ``{"data": D, "pipe": P}`` gives dp × pp.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.parallel.mesh import DATA_AXIS, PIPELINE_AXIS

#: param-sharding rule stacking pipeline stages over the ``pipe`` axis
#: (leading stacked dim); used with SPMDTrainer.param_rules for the
#: pipelined transformer family (models/pipelined.py).
PIPELINE_STAGE_RULES: list[tuple[str, tuple]] = [
    (r"^stages/", (PIPELINE_AXIS,)),
]


def _pipeline_inner(
    stage_fn: Callable[[Any, Any], Any],
    params,
    mb,
    *,
    axis_name: str,
):
    """Per-device pipeline body (runs under shard_map).

    ``params``: this device's stage params (leading stacked dim of local
    size 1). ``mb``: (M, b, ...) microbatch buffer, replicated over the
    pipe axis. Returns (M, b, ...) outputs, identical on every pipe rank.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    local = jax.tree_util.tree_map(lambda a: a[0], params)
    n_micro = mb.shape[0]

    state0 = jnp.zeros_like(mb[0])
    out0 = jnp.zeros_like(mb)
    shift = [(j, j + 1) for j in range(n - 1)]

    def tick(carry, t):
        state, out = carry
        # rank 0 feeds microbatch t (re-feeds the last one on drain ticks —
        # those outputs are masked out at collection, and contribute zero
        # gradient); other ranks consume what ppermute delivered
        feed = mb[jnp.minimum(t, n_micro - 1)]
        x = jnp.where(idx == 0, feed, state)
        y = stage_fn(local, x)
        # final rank finishes microbatch t-(n-1) once the fill phase is done
        done = t - (n - 1)
        slot = jnp.clip(done, 0, n_micro - 1)
        keep = (idx == n - 1) & (done >= 0)
        out = out.at[slot].set(jnp.where(keep, y, out[slot]))
        if shift:
            state = lax.ppermute(y, axis_name, shift)
        return (state, out), ()

    (_, out), _ = lax.scan(
        tick, (state0, out0), jnp.arange(n_micro + n - 1)
    )
    # broadcast the final rank's buffer to every rank (masked all-reduce)
    return lax.psum(jnp.where(idx == n - 1, out, jnp.zeros_like(out)),
                    axis_name)


def pipeline_apply(
    stage_fn: Callable[[Any, Any], Any],
    stacked_params,
    microbatches,
    mesh,
    *,
    axis: str = PIPELINE_AXIS,
    batch_axis: str = DATA_AXIS,
):
    """Run ``microbatches`` (M, b, ...) through ``n_stages`` copies of
    ``stage_fn`` whose params are stacked on dim 0 of ``stacked_params``.

    Equivalent (up to float tolerance) to applying the stages sequentially:
    ``y = stage_fn(p[n-1], ... stage_fn(p[0], x))`` per microbatch, but the
    stages live on different devices along ``axis`` and activations move
    with one ppermute hop per tick.
    """
    if axis not in mesh.shape:
        raise FriendlyError(
            f"pipeline_apply needs axis '{axis}' in the mesh; mesh axes: "
            f"{dict(mesh.shape)}"
        )
    n = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != n:
        raise FriendlyError(
            f"stacked params have {leaves[0].shape[0]} stages but mesh axis "
            f"'{axis}' has size {n}"
        )
    if microbatches.shape[0] % n:
        raise FriendlyError(
            f"microbatch count {microbatches.shape[0]} must be a multiple "
            f"of the pipeline depth {n} (keeps the bubble fraction bounded)"
        )
    # shard the microbatch batch dim over data when it divides evenly
    # (dp × pp); otherwise replicate it within the map (tiny init traces)
    batch = (
        batch_axis
        if batch_axis in mesh.shape
        and microbatches.shape[1] % mesh.shape[batch_axis] == 0
        else None
    )
    mb_spec = P(None, batch)
    inner = partial(_pipeline_inner, stage_fn, axis_name=axis)
    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis), mb_spec),
        out_specs=mb_spec,
        check_vma=False,
    )(stacked_params, microbatches)
