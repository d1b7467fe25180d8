"""Read, on the chip and at a cell's own size, the numbers its limits are
set from: the program's and the control's, seed by seed, in one process.

    python3 benchmark/limits.py --workload <name> --seeds 11 12 13 [--seconds 15]

A serving cell runs as in a benchmark run, over a short window at the
cell's own load; the reference then reads, over the same prompts and served
tokens, the program's widest gap (``served_gap``) and the gap of the token
the control's precision puts first (``control_gap``). A training cell needs
no window and no program: the reference follows the first three steps, and
the control and each fault a training cell can have are put in the
program's place and held against it by the same three numbers. One chip is
enough for that. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, family, run, setup_log, traffic  # noqa: E402

TRAIN_VARIANTS = ({"mode": "control"}, {"fault": "half_batch"},
                  {"fault": "no_exchange"})


def family_of(files: dict, mode: str, root: str = run.ROOT):
    """The cell's family, checked for a control in ``mode``."""
    return family.resolve(files["config"], files["mix"]["kind"], mode, root)


def serving_seed(files: dict, seed: int, seconds: float, modes,
                 root: str = run.ROOT) -> dict:
    from benchmark import serving

    fams = [family_of(files, mode, root) for mode in modes]
    clock = setup_log.SetupClock(setup_log.process_start())
    state = serving.run(fams[0], files["mix"], seed, seconds, None,
                        clock, None, control_modes=tuple(modes))
    return state["numbers"]


def training_seed(files: dict, seed: int, variants=TRAIN_VARIANTS,
                  root: str = run.ROOT) -> dict:
    import math

    import numpy as np

    cfg, mix = files["config"], files["mix"]
    fam = family_of(files, files["control_mode"], root)
    ref, sz = fam.reference, fam.sz
    spec = cfg["program"]["trainer"]
    rows = int(mix["rows_per_chip"]) * math.prod(spec["mesh_axes"].values())
    steps = int(mix["check_steps"])
    x, y = traffic.train_batches(sz["v"], seed, rows * steps, int(mix["seq"]))
    batches = [(x[i * rows:(i + 1) * rows], y[i * rows:(i + 1) * rows])
               for i in range(steps)]
    lr = spec["learning_rate"]
    ref_run = check.reference_steps(ref, sz, seed, batches, lr)
    out = {}
    for variant in variants:
        kw = dict(variant)
        name = kw.get("fault") or "control"
        if kw.get("mode") == "control":
            kw["mode"] = files["control_mode"]
        other = check.reference_steps(ref, sz, seed, batches, lr, **kw)
        norms = check.leaf_norms(ref, other["first_grad"]).values()
        gaps = check.train_gaps(ref, ref_run, other["losses"],
                                float(np.sqrt(sum(n * n for n in norms))),
                                other["end"])
        out[name] = {k: gaps[k] for k in
                     ("loss_gap", "grad_gap", "delta_gap", "delta_worst_leaf")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--modes", nargs="+", default=None,
                    help="the control's precisions; default: the cell's own")
    args = ap.parse_args(argv)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    files = run.cell_files(manifest, args.workload)
    run.chips_or_exit(1)
    run.compile_cache()
    modes = args.modes or [files["control_mode"]]
    for seed in args.seeds:
        if files["mix"]["kind"] == "train":
            numbers = {mode: training_seed(
                dict(files, control_mode=mode), seed,
                TRAIN_VARIANTS if mode == modes[0] else TRAIN_VARIANTS[:1])
                for mode in modes}
        else:
            numbers = serving_seed(files, seed, args.seconds, modes)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
