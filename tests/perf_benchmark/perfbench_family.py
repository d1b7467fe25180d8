"""A second model family added to the throwaway tree of ``perfbench_tiny``
as new files only: its reference, its counts, its adapter (a second one
over the builder ``transformer_lm``), a configuration that names them and a
cell on the ``tiny-backlog`` mix. The files are kept under
``data/gqa_rope/`` and copied into the temporary root, where the harness
finds the modules as it finds the data files."""

from __future__ import annotations

import json
import os
import shutil

import perfbench_tiny as tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "gqa_rope")
CONFIG, CELL = "tiny-gqa", "tiny-gqa.tiny-backlog"
#: the metrics of a backlog cell whose numbers come from a family's counts
COUNTED = ("decode_step_mfu_pct", "decode_hbm_roofline",
           "attn_decode_roofline")


def build(root: str) -> dict:
    """``perfbench_tiny``'s tree under ``root`` with the family added;
    returns the manifest. No file that was there is changed."""
    manifest = tiny.build(root)
    before = tiny._listing(root)
    for base, _dirs, files in os.walk(DATA):
        for name in files:
            src = os.path.join(base, name)
            dst = os.path.join(root, "benchmark", os.path.relpath(src, DATA))
            assert not os.path.exists(dst), f"{dst} is already there"
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(src, dst)
    with open(os.path.join(DATA, "configs", f"{CONFIG}.json")) as f:
        source = json.load(f)["source"]
    manifest["configs"].append({
        "name": CONFIG, "source": source,
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": "a test's throwaway: a second family over one builder"})
    manifest["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "tiny-backlog",
        "chips": 1, "why": "a test's throwaway"})
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if "tiny.tiny-backlog" in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    after = tiny._listing(root)
    assert all(after[p] == h for p, h in before.items()), \
        "a file the benchmark already had was changed"
    return manifest
