"""Bound the TPUModel feed machinery's own overhead on the CPU backend.

The stage's host path (host->device queue, batch slicing, dtype
coercion, output gather) is measured apart from any device link: the
WHOLE TPUModel stage runs on the CPU backend, where host->device is a
memcpy, so the stage-vs-model-only gap IS the machinery cost. It is a
count of host overhead, not a device figure; the stage's rate on the
chip is ``chip_smoke.py``'s stage phase and the benchmark's business.

- model-only ceiling: batches pre-sliced and pre-device_put, timed loop
  of jitted forward + host fetch of each output (the stage fetches its
  outputs too, so the ceiling includes that);
- stage: ``TPUModel.transform`` end to end at feed depths 1/2/4/8 from
  the same host-RAM Dataset.

Prints one JSON line. Run it as ``JAX_PLATFORMS=cpu python
tools/feed_overhead_bench.py``; it refuses any other backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: env-overridable so bench.py's cpu-smoke mode can run a fast pass
BATCH = int(os.environ.get("MMLTPU_FEED_BATCH", "256"))
ROWS = int(os.environ.get("MMLTPU_FEED_ROWS", "4096"))
DEPTHS = (1, 2, 4, 8)
TRIALS = int(os.environ.get("MMLTPU_FEED_TRIALS", "3"))


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main() -> None:
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.data.dataset import Dataset
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.stages.dnn_model import TPUModel

    if jax.default_backend() != "cpu":
        raise SystemExit(
            "feed_overhead_bench measures host machinery on the CPU "
            f"backend; run it with JAX_PLATFORMS=cpu (got "
            f"{jax.default_backend()!r})"
        )
    graph = build_model("resnet20_cifar10")
    variables = graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3), jnp.float32)
    )
    x = np.random.default_rng(3).normal(size=(ROWS, 32, 32, 3)).astype(
        np.float32
    )

    # -- model-only ceiling ------------------------------------------------
    fwd = jax.jit(lambda v, b: graph.apply(v, b))
    batches = [
        jax.device_put(x[i:i + BATCH]) for i in range(0, ROWS, BATCH)
    ]
    np.asarray(fwd(variables, batches[0]))  # compile

    def model_only():
        for b in batches:
            np.asarray(fwd(variables, b))

    t_model = min(_timed(model_only) for _ in range(TRIALS))
    model_ips = ROWS / t_model

    # -- full stage at each feed depth ------------------------------------
    ds = Dataset({"image": x})
    per_depth = {}
    for depth in DEPTHS:
        stage = TPUModel.from_graph(
            graph, variables, "resnet20_cifar10",
            input_col="image", output_col="scores", batch_size=BATCH,
            feed_depth=depth,
        )
        stage.transform(ds)  # warmup: compile + weight put
        dt = min(_timed(lambda: stage.transform(ds)) for _ in range(TRIALS))
        per_depth[depth] = ROWS / dt

    best = max(per_depth, key=per_depth.get)
    line = {
        "metric": "feed_overhead_fraction_cpu_backend",
        # fraction of the model-only ceiling LOST to the feed machinery
        # at the best depth
        "value": round(1.0 - per_depth[best] / model_ips, 4),
        "unit": "fraction_of_ceiling_lost",
        "model_only_images_per_sec": round(model_ips, 1),
        "stage_images_per_sec_per_depth": {
            str(d): round(v, 1) for d, v in per_depth.items()
        },
        "stage_over_model_ratio_best": round(per_depth[best] / model_ips, 4),
        "best_feed_depth": best,
        "batch": BATCH,
        "rows": ROWS,
        "trials": TRIALS,
        "backend": "cpu (host->device is a memcpy, so stage-vs-model-only "
                   "isolates the machinery itself)",
    }
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
