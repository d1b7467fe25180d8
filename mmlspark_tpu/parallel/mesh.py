"""Device mesh construction + sharding helpers.

Replaces the reference's worker discovery (`nvidia-smi -L` count,
EnvironmentUtils.scala:45-50) and MPI topology (hostfile ``slots=N``,
CommandBuilders.scala:95-116) with a named :class:`jax.sharding.Mesh`:
axis names are the API, XLA collectives ride ICI/DCN underneath (the
scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
collectives).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from mmlspark_tpu.core.exceptions import FriendlyError

#: canonical axis names
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQUENCE_AXIS = "seq"
PIPELINE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def parse_mesh_axes(spec: str) -> dict[str, int]:
    """Parse the CLI/bench mesh spelling ``"data=4,model=2"`` into the
    axes mapping :func:`make_mesh` takes. A size of ``-1`` (one axis at
    most) is inferred from the device count, exactly as in
    :func:`make_mesh`; whitespace around entries is ignored."""
    axes: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, size = part.partition("=")
        name = name.strip()
        try:
            if not eq or not name:
                raise ValueError
            axes[name] = int(size)
        except ValueError:
            raise FriendlyError(
                f"bad mesh spec {spec!r}: each entry must be "
                f"'axis=size' (e.g. 'data=4,model=2'), got {part!r}"
            ) from None
    if not axes:
        raise FriendlyError(
            f"bad mesh spec {spec!r}: no axes (e.g. 'data=4,model=2')"
        )
    return axes


def make_mesh(
    axes: Mapping[str, int] | None = None,
    devices: Sequence | None = None,
):
    """Build a Mesh over the visible devices.

    ``axes`` maps axis name -> size, in major-to-minor order; a single axis
    may be -1 (inferred). Default: pure data-parallel over every device —
    the reference's only strategy (SURVEY.md §2.5), here just the trivial
    mesh shape.
    """
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if axes is None:
        axes = {DATA_AXIS: n}
    names = list(axes)
    sizes = list(axes.values())
    unknown = [i for i, s in enumerate(sizes) if s == -1]
    if len(unknown) > 1:
        raise FriendlyError("at most one mesh axis may be -1")
    if unknown:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        if n % known:
            raise FriendlyError(
                f"cannot infer axis '{names[unknown[0]]}': {n} devices not "
                f"divisible by {known}"
            )
        sizes[unknown[0]] = n // known
    need = int(np.prod(sizes))
    if need > n:
        raise FriendlyError(
            f"mesh {dict(zip(names, sizes))} needs {need} devices, have {n}"
        )
    # A smaller mesh uses the first `need` devices (e.g. debugging a
    # single-chip layout on a pod).
    grid = np.array(devs[:need]).reshape(sizes)
    return Mesh(grid, tuple(names))


def batch_spec(mesh, axis: str = DATA_AXIS):
    """NamedSharding splitting the leading (batch) dim over ``axis``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis))


def replicated_spec(mesh):
    """Fully-replicated NamedSharding (params under pure DP)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bring-up (replaces MultiNodeParallelLauncher's MPI
    hostfile, CommandBuilders.scala:95-116): every host runs the same
    program; JAX wires the global device view over DCN.

    Arguments default to the ``MMLSPARK_TPU_{COORDINATOR, NUM_PROCESSES,
    PROCESS_ID}`` environment contract set per worker by
    ``tools/pod/launch-pod.sh`` (the hostfile-launcher analog); with
    neither arguments nor env set this is a single-host no-op.
    """
    import os

    import jax

    if coordinator_address is None:
        coordinator_address = os.environ.get("MMLSPARK_TPU_COORDINATOR")
    if num_processes is None and "MMLSPARK_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["MMLSPARK_TPU_NUM_PROCESSES"])
    if process_id is None and "MMLSPARK_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["MMLSPARK_TPU_PROCESS_ID"])
    if coordinator_address is None:
        return  # single-host: nothing to do
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
