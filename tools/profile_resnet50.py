"""Capture a jax.profiler trace of the ResNet-50 forward on the chip.

A device trace of the compiled forward (the same program the bench
times) written under ``profiles/resnet50/``, plus the untraced step
time. Run it on the machine that holds the TPU, in one process:

    python tools/profile_resnet50.py [--size 224 --batch 256]

With no TPU it exits 2 and traces nothing. TensorBoard reads the trace
directory.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--out", default=os.path.join(REPO, "profiles", "resnet50")
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mmlspark_tpu.core.env import is_tpu

    if not is_tpu():
        print(f"no TPU: JAX found {jax.devices()[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(2)

    from mmlspark_tpu.models import build_model

    graph = build_model("resnet50", input_size=args.size)
    variables = graph.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, args.size, args.size, 3), jnp.float32),
    )
    x = jnp.asarray(
        np.random.default_rng(0).normal(
            size=(args.batch, args.size, args.size, 3)
        ),
        jnp.bfloat16,
    )
    fwd = jax.jit(lambda v, x: graph.apply(v, x).mean())
    np.asarray(fwd(variables, x))  # compile outside the trace

    os.makedirs(args.out, exist_ok=True)
    with jax.profiler.trace(args.out):
        for _ in range(args.iters):
            np.asarray(fwd(variables, x))  # host fetch = sync per step

    t0 = time.perf_counter()
    for _ in range(args.iters):
        np.asarray(fwd(variables, x))
    dt = (time.perf_counter() - t0) / args.iters
    print(
        f"traced {args.iters} steps -> {args.out}\n"
        f"untraced step: {dt * 1e3:.2f} ms "
        f"({args.batch / dt:.0f} img/s) at ({args.batch}, {args.size})\n"
        "inspect: tensorboard --logdir "
        + args.out
    )


if __name__ == "__main__":
    main()
