"""Accelerator / environment discovery.

The reference discovers workers by shelling to ``nvidia-smi -L`` and counting
lines (core/env/src/main/scala/EnvironmentUtils.scala:14-51); the worker count
drives MPI parallelism (CommandBuilders.scala:81). The TPU-native equivalent
is JAX device introspection — no subprocess, no parsing.
"""

from __future__ import annotations

import os
import platform
from dataclasses import dataclass


def device_count() -> int:
    """Global accelerator count (EnvironmentUtils.GPUCount analog)."""
    import jax

    return jax.device_count()


def local_device_count() -> int:
    import jax

    return jax.local_device_count()


def process_count() -> int:
    """Number of controller processes (multi-host)."""
    import jax

    return jax.process_count()


def backend() -> str:
    import jax

    return jax.default_backend()


def is_tpu() -> bool:
    """True when the first device's platform is ``"tpu"`` — the one
    answer every TPU-or-not choice in the package reads (compiled vs
    interpreted kernels, flash vs dense attention, buffer donation,
    bench scale). The platform decides, never ``device_kind``: a device
    that merely calls itself a TPU gets none of the TPU paths."""
    import jax

    return jax.devices()[0].platform == "tpu"


#: where compiled programs persist when the caller names no place:
#: inside the checkout, because the directory is part of the cache key —
#: a temporary, pid- or time-derived path would never hit
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile and return its directory. ``JAX_COMPILATION_CACHE_DIR``,
    when set, is the caller's placement and jax reads it itself, so
    nothing is set in code; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``. Called once by each entry point (the
    CLI, ``bench.py``, ``chip_smoke.py``), never at import."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return _DEFAULT_COMPILE_CACHE


@dataclass(frozen=True)
class TopologyInfo:
    """TPU topology introspection summary (replaces the reference's
    single-node GPU-count worldview with mesh-shaped facts)."""

    num_devices: int
    num_local_devices: int
    num_processes: int
    platform: str
    device_kind: str
    host_os: str


def topology() -> TopologyInfo:
    import jax

    devs = jax.devices()
    return TopologyInfo(
        num_devices=len(devs),
        num_local_devices=jax.local_device_count(),
        num_processes=jax.process_count(),
        platform=jax.default_backend(),
        device_kind=devs[0].device_kind if devs else "none",
        host_os=platform.system(),
    )


def describe() -> dict:
    """Topology as a plain dict (the launcher's ``mml-tpu env`` view)."""
    import dataclasses

    return dataclasses.asdict(topology())
