"""The share of a roofline, from operations, bytes, a time and the chip's
peaks: the part of the yardstick's arithmetic that no model family owns.
The operations and bytes themselves are a family's (``counts/<name>.py``,
found by :mod:`benchmark.family`). No jax.
"""

from __future__ import annotations


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str] | None:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) as a percentage of
    ``seconds``, and which side bounds it. None when there is no time."""
    if not seconds or seconds <= 0:
        return None
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    side = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, side
