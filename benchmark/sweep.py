"""Find, once, the highest rate an open-loop cell's engine sustains: one
engine, the cell's own mix at each of several rates in turn, each for a
short window.

    python3 benchmark/sweep.py --workload <name> --rates 2 3 4 5 6 [--seconds 20]

For each rate it prints the requests sent and finished, the queue's depth
at the window's two ends (a queue that grows is a rate not sustained) and
the time to first token's median and 95th percentile. The rate written
into the traffic file is a share of the highest sustained one; the
benchmark's own runs never search.
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

from benchmark import family, run, serving, setup_log  # noqa: E402
from benchmark.readers import quantile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    files = run.cell_files(manifest, args.workload)
    cfg, mix = files["config"], files["mix"]
    fam = family.resolve(cfg, mix["kind"])
    run.chips_or_exit(1)
    run.compile_cache()
    clock = setup_log.SetupClock(setup_log.process_start())
    top = dict(mix, rate_per_s=max(args.rates))
    _, max_queue = serving.requests_for(fam, top, args.seed, args.seconds)
    engine = serving.prepare(fam, mix, args.seed, max_queue, clock)
    for rate in args.rates:
        at = dict(mix, rate_per_s=rate)
        reqs, _ = serving.requests_for(fam, at, args.seed, args.seconds)
        loop, t_open, t_close = serving.drive(
            engine, cfg, at, reqs, args.seconds, serving.Tracer(None))
        rows = serving.request_table(engine.recorder.events(), loop)
        timed = [r for r in rows if r["timed"]]
        ttft = [(r["first_token"] - r["due"]) * 1e3 for r in timed
                if "first_token" in r]
        waiting = [sum(1 for r in rows if r["sent"] <= t
                       and r.get("admitted", float("inf")) > t)
                   for t in (t_open, t_close)]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(timed), "answered": len(ttft),
            "queue_at_open": waiting[0], "queue_at_close": waiting[1],
            "ttft_ms_p50": quantile(ttft, 0.5),
            "ttft_ms_p95": quantile(ttft, 0.95),
            "drained_after_s": max(r.get("finished", t_close)
                                   for r in timed) - t_close,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
