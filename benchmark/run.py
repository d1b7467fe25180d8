"""Run one cell of ``BENCHMARK.json`` once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

(``python -m benchmark.run`` is the same.) Everything that belongs to one
configuration, one traffic mix, one cell or one metric is a data file that
this runner finds by name, and whatever belongs to one model family or one
kind of reading is a module found the same way, under the same root; see
``benchmark/README.md``. It needs a TPU with
as many chips as the cell asks for and exits 2, printing no result,
without one. The last line of standard output is the result; the line
before it says how ``setup_s`` divides.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:   # started as a file: make ``benchmark`` importable
    sys.path.insert(0, ROOT)

from benchmark import family, setup_log  # noqa: E402

T_START = setup_log.process_start()
SERVING_KINDS = ("backlog", "open-loop")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def cell_files(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, its traffic mix and its
    own file (``limits``, ``control_mode``), each found by name."""
    cell = find(manifest["workloads"], workload, "workload")
    config = find(manifest["configs"], cell["config"], "config")
    return {"cell": cell,
            "config": load_json(root, config["file"]),
            "mix": load_json(root, "benchmark", "traffic",
                             f"{cell['traffic']}.json"),
            **load_json(root, "benchmark", "cells", f"{workload}.json")}


def metrics_for(manifest: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's metrics of this run: end-to-end without a trace,
    per-layer with one. An entry with no ``workloads`` is every cell's."""
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def readers_for(entries: list[dict], root: str = ROOT) -> list[tuple]:
    """Each metric with its file and the reader that file names."""
    out = []
    for entry in entries:
        spec = load_json(root, "benchmark", "metrics", f"{entry['name']}.json")
        out.append((entry, spec, family.load(root, "readers", spec["reader"])))
    return out


def counts_needed(entries: list[dict], root: str = ROOT) -> list[str]:
    """The counting functions these metrics' readers will ask the family's
    counts module for."""
    return sorted({name for _, spec, reader in readers_for(entries, root)
                   if hasattr(reader, "counts_needed")
                   for name in reader.counts_needed(spec)})


def evaluate(entries: list[dict], state: dict, root: str = ROOT) -> dict:
    """Each metric through the reader its file names. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for entry, spec, reader in readers_for(entries, root):
        value = reader.read(state, spec)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def chips_or_exit(chips: int) -> list:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        devices, why = [], str(e)
    else:
        why = (f"JAX found {len(devices)} x "
               f"{devices[0].platform if devices else 'nothing'}")
    if not devices or devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: this cell needs {chips} TPU chip(s); {why}. "
              "Nothing was run.", file=sys.stderr)
        raise SystemExit(2)
    return devices


def compile_cache() -> str:
    """The persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or a fixed
    path inside the checkout, keeping every compile however small."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(manifest: dict, files: dict, workload: str, seed: int,
             seconds: float, trace: bool, device: dict, peak: dict,
             root: str = ROOT) -> tuple[dict, dict]:
    """Everything after the look for a chip: the run, the reading of the
    trace, the comparison and the metrics. Returns the result line and the
    line that divides ``setup_s``."""
    cell, cfg, mix = files["cell"], files["config"], files["mix"]
    entries = metrics_for(manifest, workload, trace)
    # what the family's modules lack is said now, not after the window
    fam = family.resolve(cfg, mix["kind"], files["control_mode"], root)
    family.require(fam.counts, counts_needed(
        metrics_for(manifest, workload, True), root), "the counts module")
    log = setup_log.CompileLog()
    clock = setup_log.SetupClock(T_START)
    clock.mark("import_and_backend")
    trace_dir = os.path.join(root, ".bench_trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    module = "serving" if mix["kind"] in SERVING_KINDS else "training"
    runner = importlib.import_module(f"benchmark.{module}")
    state = runner.run(fam, mix, seed, seconds, trace_dir, clock, log)
    state.update(peak=peak, compile_log=log, chips=int(cell["chips"]),
                 setup_s=state["t_open"] - T_START, mix=mix,
                 sz=fam.sz, counts=fam.counts)
    device = dict(device, memory_peak_bytes=state["peak_bytes"])
    line = {}
    if trace_dir:
        from benchmark import trace_reduce

        t0 = time.monotonic()
        state["trace"] = trace_reduce.load(trace_dir, int(cell["chips"]))
        device.update(busy_s=state["trace"].busy_s,
                      window_s=state["trace"].window_s)
        line["breakdown"] = state["trace"].breakdown()
        line["trace_read_s"] = time.monotonic() - t0
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(trace_dir, ignore_errors=True)

    from benchmark import check

    correct, compared = check.verdict(state["numbers"], files["limits"])
    metrics = evaluate(entries, state, root)
    result = {
        "correct": correct, "attempted": state["attempted"],
        "failed": state["failed"], "metrics": metrics, "device": device,
        **line,
        "details": {**{k: v for k, v in state["numbers"].items()
                       if k not in compared},
                    **({"ticks": state["ticks"]} if "ticks" in state else {})},
        "compared": compared,
    }
    division = {"setup_division": clock.division(log),
                "setup_s": state["setup_s"]}
    return result, division


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    files = cell_files(manifest, args.workload)
    devices = chips_or_exit(int(files["cell"]["chips"]))
    cache_dir = compile_cache()
    peaks = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         "benchmark/peaks.json")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    result, division = run_cell(manifest, files, args.workload, args.seed,
                                args.seconds, bool(args.trace), device,
                                peaks[kind])
    print(json.dumps(dict(division, compile_cache_dir=cache_dir)),
          flush=True)
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
