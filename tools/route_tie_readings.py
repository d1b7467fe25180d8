"""On the chip, at a routed cell's own size: how the widest gap of a sound
run's served tokens, and the share of positions left out of the comparison,
move with the width of the router's near-tie rule (``TIE`` of the cell's
reference; ``sz["tie"]``). One short window of the cell through the
harness's own run, then the reference over the same sampled requests once
for every width, and with ``--modes`` once more for each control precision
or planted fault: at every width the gap of the token THAT puts first, the
number a limit has to stay under. What
``benchmark/references/deepseek_v3.py``'s ``TIE`` and the cell's limit rest
on (``PERF.md`` section 2 has the readings).

    chiprun --timeout 1800 -- python tools/route_tie_readings.py \\
        --workload kanana-2-30b-a3b.report-backlog --seed 11 \\
        --seconds 45 --ties 0.003 0.006 0.012 --modes fp8 unscaled_route

One JSON line a width, to stdout and ``chiprun_out/route_tie_readings.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmark import check, family, run, serving, setup_log  # noqa: E402
from tools import long_ticks  # noqa: E402

OUT = ROOT / "chiprun_out" / "route_tie_readings.jsonl"


def emit(out: Path, **row) -> None:
    """One JSON line to stdout and to ``out``."""
    line = json.dumps(row)
    print(line, flush=True)
    out.parent.mkdir(exist_ok=True)
    with out.open("a") as f:
        f.write(line + "\n")


def window(fam, mix: dict, seed: int, seconds: float) -> tuple:
    """One window of the cell through the harness's own run: its state,
    the (prompt, served) samples its comparison took and the length it
    padded them to."""
    seen = {}
    served_gaps = check.served_gaps

    def spy(ref, sz, seed, samples, length, mode="f32"):
        seen.update(samples=samples, length=length)
        return served_gaps(ref, sz, seed, samples, length, mode)

    check.served_gaps = spy
    try:
        state = serving.run(fam, mix, seed, seconds, None,
                            setup_log.SetupClock(setup_log.process_start()),
                            None)
    finally:
        check.served_gaps = served_gaps
    return state, seen["samples"], seen["length"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--ties", type=float, nargs="+", required=True)
    ap.add_argument("--modes", nargs="*", default=[])
    args = ap.parse_args(argv)
    manifest = run.load_json(str(ROOT), "BENCHMARK.json")
    files = run.cell_files(manifest, args.workload, str(ROOT))
    run.chips_or_exit(1)
    run.compile_cache()
    fam = family.resolve(files["config"], files["mix"]["kind"],
                         files["control_mode"])
    state, samples, length = window(fam, files["mix"], args.seed,
                                    args.seconds)
    emit(OUT, workload=args.workload, seed=args.seed, run=state["numbers"],
         long_ticks=long_ticks.report(state, long_ticks.OVER_MS))
    key = family.seed_key(args.seed)

    def read(tie, mode):
        """Every sampled position's gap at this width: the served
        token's, and the token's that ``mode`` puts first."""
        fn = fam.reference.served_gaps_fn(dict(fam.sz, tie=tie), key, mode)
        out = ([], [])
        for prompt, served in samples:
            seq = np.zeros((1, length), np.int32)
            seq[0, :len(prompt)] = prompt
            seq[0, len(prompt):len(prompt) + len(served)] = served
            for kept, got in zip(out, fn(seq, len(prompt), len(served))):
                kept.append(np.asarray(got)[:len(served)])
        return [np.concatenate(kept) for kept in out]

    for tie in args.ties:
        t0 = time.monotonic()
        gaps = read(tie, "f32")[0]
        firsts = {mode: float(read(tie, mode)[1].max())
                  for mode in args.modes}
        # a position left out reads exactly 0; so does a served token that
        # IS the reference's best, at every width: the widest width's zeros
        # bound the share from above, the growth between widths is exact
        emit(OUT, tie=tie, tokens=int(gaps.size), served_gap=float(gaps.max()),
             top5=[round(float(g), 4) for g in np.sort(gaps)[-5:]],
             over_0p1=int((gaps > 0.1).sum()), over_0p2=int((gaps > 0.2).sum()),
             zeros_share=round(float((gaps == 0).mean()), 4),
             **{f"control_gap.{mode}": gap for mode, gap in firsts.items()},
             seconds=round(time.monotonic() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
