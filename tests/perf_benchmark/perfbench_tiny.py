"""A throwaway benchmark tree at a size the CPU holds: the real harness and
its data files copied into a temporary root, plus one tiny configuration,
two tiny mixes and their cells, ADDED as new files and new entries only,
the way a later PR has to add them."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONFIG = {
    "vocab_size": 96, "n_positions": 64, "n_embd": 32, "n_layer": 2,
    "n_head": 4, "n_inner": None, "layer_norm_epsilon": 1e-06,
    "source": "https://huggingface.co/openai-community/gpt2",
    "program": {
        "adapter": "transformer_lm",
        "model": {"vocab_size": 96, "d_model": 32, "heads": 4, "depth": 2,
                  "d_ff": 128, "max_len": 64},
        "engine": {"slots": 4, "cache_len": 64, "decode_block": 4},
        "trainer": {"learning_rate": 1e-4, "optimizer": "adam",
                    "mesh_axes": {"data": 4}},
    },
}
LENGTHS = {"dist": "loguniform", "lo": 8, "hi": 24, "multiple_of": 8}
MIXES = {
    "tiny-backlog": {
        "kind": "backlog", "count": 16, "group": 4, "order_seed": 1,
        "prompt_len": LENGTHS,
        "output_len": {"dist": "loguniform", "lo": 8, "hi": 24},
        "queued": 2, "fill_s": 0.3, "stagger_first_slotful": True,
        "check_requests": 4},
    "tiny-open": {
        "kind": "open-loop", "rate_per_s": 8.0, "group": 4, "order_seed": 1,
        "prompt_len": LENGTHS,
        "output_len": {"dist": "loguniform", "lo": 4, "hi": 12},
        "gap": {"dist": "exponential"}, "fill_s": 0.3, "drain_s": 20.0,
        "check_requests": 4},
    "tiny-train": {
        "kind": "train", "rows_per_chip": 2, "seq": 32, "check_steps": 3,
        "warm_steps": 2, "steps_per_s_ceiling": 2000.0},
}
#: set from readings at this size on the CPU (see test_perfbench_check.py)
LIMITS = {
    "tiny-backlog": {"served_gap": 0.002, "unanswered": 0},
    "tiny-open": {"served_gap": 0.002, "unanswered": 0},
    "tiny-train": {"loss_gap": 3e-5, "grad_gap": 0.005, "delta_gap": 0.06},
}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 16e9}


def build(root: str) -> dict:
    """The tree under ``root``; returns its manifest. Nothing that the
    repository's benchmark already holds is changed: files and entries are
    only added."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    before = _listing(root)

    def add(rel: str, obj: dict) -> None:
        path = os.path.join(root, "benchmark", rel)
        assert not os.path.exists(path), f"{rel} is already there"
        with open(path, "w") as f:
            json.dump(obj, f)

    add("configs/tiny.json", CONFIG)
    manifest["configs"].append({
        "name": "tiny", "source": CONFIG["source"],
        "file": "benchmark/configs/tiny.json",
        "reduced": ["n_embd", "n_layer"], "why": "a test's throwaway"})
    for mix, body in MIXES.items():
        cell = f"tiny.{mix}"
        add(f"traffic/{mix}.json", body)
        add(f"cells/{cell}.json", {"limits": LIMITS[mix],
                                   "control_mode": "int8"})
        manifest["workloads"].append({
            "name": cell, "config": "tiny", "traffic": mix, "chips": 1,
            "why": "a test's throwaway"})
        for group in ("end_to_end", "per_layer"):
            for metric in manifest[group]:
                like = {"backlog": "chat-backlog", "train": "train-dp4",
                        "open-loop": "no cell yet"}[body["kind"]]
                cells = metric.get("workloads")
                if cells and any(c.endswith(like) for c in cells):
                    cells.append(cell)
    # the open-loop cell brings its end-to-end metric and a per-layer one
    add("metrics/ttft_ms_p50.json", {
        "reader": "request_quantile", "from": "due", "to": "first_token",
        "q": 0.5, "missing_is_slowest": True})
    manifest["end_to_end"].append({
        "name": "ttft_ms_p50", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["tiny.tiny-open"]})
    add("metrics/gen_late_ms_p99.json", {
        "reader": "request_quantile", "from": "due", "to": "sent",
        "q": 0.99})
    manifest["per_layer"].append({
        "name": "gen_late_ms_p99", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "serve host loop",
        "moves": "ttft_ms_p50", "workloads": ["tiny.tiny-open"]})
    # a per-layer metric of its own, from a reader that is already there
    add("metrics/tiny_block_len.json", {
        "reader": "event_mean", "event": "dispatch", "attr": "family",
        "match": r"decode\[T=(\d+)\]"})
    manifest["per_layer"].append({
        "name": "tiny_block_len", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "serve host loop",
        "moves": "out_tokens_per_s", "workloads": ["tiny.tiny-backlog"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    after = _listing(root)
    assert all(after[p] == h for p, h in before.items()), \
        "a file the benchmark already had was changed"
    return manifest


def _listing(root: str) -> dict:
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[path] = hash(f.read())
    return out


def run_cell(root: str, manifest: dict, workload: str, seed: int = 11,
             seconds: float = 1.0, trace: bool = False):
    from benchmark import run

    files = run.cell_files(manifest, workload, root)
    return run.run_cell(manifest, files, workload, seed, seconds, trace,
                        DEVICE, PEAK, root)
