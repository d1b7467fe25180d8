"""One run of a serving cell of ``BENCHMARK.json`` exactly as
``benchmark/run.py`` makes it (this calls its ``main``), and then, for
every engine tick of the window that took longer than a second, WHERE
the time went: the ``serve.*`` regions (``SpanTracer.region``; the
recorder holds them with the profiler off) that lie inside the tick, summed
by name, the tick's own time outside every region under it, and the time
the load generator spent between that tick and the one before.

    chiprun -- python tools/long_ticks.py --workload \\
        kanana-2-30b-a3b.report-backlog --seed 11 --seconds 45 --trace 0

An untraced run of a serving cell now and then holds a tick of seconds
where its like take a few hundred milliseconds (``PERF.md`` section 7); the
result line's ``ticks`` say THAT it happened, this says in which region.
The result line is printed as ``run.py`` prints it; one JSON line a long
tick follows on standard error and in ``chiprun_out/long_ticks.jsonl``,
and for a cell that routes tokens to experts one line of the window's
``dispatch`` counters: pairs over rows is how full the expert products'
row tiles were (no metric reads ``expert_rows`` yet: ``PERF.md`` section 7).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

OUT = ROOT / "chiprun_out" / "long_ticks.jsonl"
TICK = "serve.tick"
#: a tick of the cells' traffic takes 20 to 530 ms; the stalled ones 1.2 s
#: and more
OVER_MS = 1000.0


def _regions(state: dict) -> list[dict]:
    """The window's region events as ``{name, t0, t1, ms, parent}``."""
    out = []
    for ev in state["events"]:
        attrs = ev.get("attrs") or {}
        if "t0" not in attrs or "ms" not in attrs:
            continue
        t0 = float(attrs["t0"])
        t1 = t0 + float(attrs["ms"]) / 1e3
        if t1 > state["t_open"] and t0 < state["t_close"]:
            out.append({"name": ev["name"], "t0": t0, "t1": t1,
                        "ms": float(attrs["ms"]),
                        "parent": attrs.get("parent")})
    return sorted(out, key=lambda r: r["t0"])


def report(state: dict, over_ms: float) -> list[dict]:
    """One row for every tick of the window whose region, or whose wait
    for the load generator before it, is longer than ``over_ms``."""
    regions = _regions(state)
    ticks = [r for r in regions if r["name"] == TICK]
    typical = sorted(t["ms"] for t in ticks)
    rows = []
    for before, tick in zip([None] + ticks[:-1], ticks):
        outside = 0.0 if before is None else (tick["t0"] - before["t1"]) * 1e3
        if max(tick["ms"], outside) <= over_ms:
            continue
        inside = [r for r in regions if r is not tick
                  and r["t0"] >= tick["t0"] and r["t1"] <= tick["t1"] + 1e-6]
        by_name: dict[str, dict] = {}
        for r in inside:
            row = by_name.setdefault(r["name"], {"ms": 0.0, "n": 0,
                                                 "longest_ms": 0.0})
            row["ms"] = round(row["ms"] + r["ms"], 1)
            row["n"] += 1
            row["longest_ms"] = round(max(row["longest_ms"], r["ms"]), 1)
        direct = sum(r["ms"] for r in inside if r["parent"] == TICK)
        rows.append({
            "tick_ms": round(tick["ms"], 1),
            "s_after_open": round(tick["t0"] - state["t_open"], 2),
            "generator_before_ms": round(outside, 1),
            "tick_outside_its_regions_ms": round(tick["ms"] - direct, 1),
            "regions": dict(sorted(by_name.items(),
                                   key=lambda kv: -kv[1]["ms"])),
            "ticks_in_window": len(ticks),
            "tick_ms_median": typical[len(typical) // 2],
            "tick_ms_longest_other": max(
                (t["ms"] for t in ticks if t is not tick), default=None),
        })
    return rows


def tile_fill(state: dict) -> list[dict]:
    """The window's mean ``expert_pairs`` and ``expert_rows`` (a routed
    layer and micro-step) over its ``dispatch`` events, and their ratio."""
    from benchmark.readers import in_window

    blocks = [e["attrs"] for e in in_window(state)
              if e["name"] == "dispatch" and "expert_rows" in e["attrs"]]
    if not blocks:
        return []
    pairs, rows = (sum(float(a[name]) for a in blocks) / len(blocks)
                   for name in ("expert_pairs", "expert_rows"))
    return [{"dispatches": len(blocks), "expert_pairs_mean": pairs,
             "expert_rows_mean": rows,
             "expert_tile_fill_pct": 100.0 * pairs / max(rows, 1e-9)}]


def emit(rows: list[dict], **head) -> None:
    OUT.parent.mkdir(exist_ok=True)
    with OUT.open("a") as f:
        for row in rows:
            line = json.dumps({**head, **row})
            print(line, file=sys.stderr, flush=True)
            f.write(line + "\n")


def main(argv=None) -> int:
    from benchmark import run, serving

    argv = list(sys.argv[1:] if argv is None else argv)
    seen = {}
    serving_run = serving.run

    def spy(*args, **kwargs):
        seen["state"] = serving_run(*args, **kwargs)
        return seen["state"]

    serving.run = spy
    try:
        rc = run.main(argv)
    finally:
        serving.run = serving_run
    if "state" in seen:
        rows = report(seen["state"], OVER_MS) or [{"long_ticks": 0}]
        emit(rows + tile_fill(seen["state"]), argv=" ".join(argv))
    return rc


if __name__ == "__main__":
    sys.exit(main())
