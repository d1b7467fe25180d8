"""``BENCHMARK.json`` against the files it names and the contract's rules
of form; the runner's refusal without a TPU; and a cell, a configuration,
a mix and a per-layer metric added by new files and entries alone."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import run, traffic

import perfbench_tiny as tiny

ROOT = tiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(
        1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


def test_every_cell_resolves_to_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for cell in manifest["workloads"]:
        files = run.cell_files(manifest, cell["name"])
        used.add(cell["config"])
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert files["mix"]["kind"] in traffic.KINDS
        assert files["limits"] and len(cell["why"]) <= 200
        cfg = files["config"]
        entry = configs[cell["config"]]
        assert entry["file"].startswith(tuple(manifest["paths"]))
        # what the file changes from its source is listed, and no width is
        for key in entry["reduced"]:
            assert key in cfg.get("published", {}), key
            assert not key.endswith(("_dim", "_rank", "n_embd", "n_inner",
                                     "n_head"))
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_names_units_and_sources_use_only_what_is_allowed(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for cell in manifest["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in manifest["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_every_metric_has_its_file_and_its_cells_report_what_it_moves(
        manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    end = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    assert "setup_s" in end and end["setup_s"] == cells
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        spec = run.load_json(ROOT, "benchmark", "metrics",
                             f"{metric['name']}.json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", f"{spec['reader']}.py"))
    for metric in manifest["per_layer"]:
        assert metric["workloads"], metric["name"]
        assert set(metric["workloads"]) <= cells
        assert set(metric["workloads"]) <= end[metric["moves"]], metric
    for cell in cells:
        assert len(run.metrics_for(manifest, cell, trace=False)) >= 2
        assert run.metrics_for(manifest, cell, trace=True)
    # metrics of one layer spell it alike, and a kernel's roofline has the
    # whole step's share of the peak beside it, moving the same metric
    for metric in manifest["per_layer"]:
        if metric["name"].startswith("attn_"):
            beside = [m for m in manifest["per_layer"]
                      if "mfu" in m["name"].split("_")
                      and m["moves"] == metric["moves"]
                      and set(metric["workloads"]) <= set(m["workloads"])]
            assert beside, metric["name"]


@pytest.mark.parametrize("how", ["file", "module"])
def test_the_runner_refuses_to_report_without_a_tpu(how):
    entry = (["benchmark/run.py"] if how == "file"
             else ["-m", "benchmark.run"])
    proc = subprocess.run(
        [sys.executable, *entry, "--workload", "gpt2-large.chat-backlog",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_a_cell_a_config_a_mix_and_a_metric_come_as_new_files_only(
        tmp_path):
    manifest = tiny.build(str(tmp_path))
    result, division = tiny.run_cell(str(tmp_path), manifest,
                                     "tiny.tiny-backlog", trace=False)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert result["metrics"]["out_tokens_per_s"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    parts = division["setup_division"]
    assert {"weights_s", "engine_s", "warmup_s", "fill_s"} <= set(parts)
    assert "trace_s" in parts["of_which"]
    # the metric that only this test's files define is found by its name
    files = run.cell_files(manifest, "tiny.tiny-backlog", str(tmp_path))
    assert files["config"]["n_embd"] == 32
    names = [m["name"] for m in run.metrics_for(
        manifest, "tiny.tiny-backlog", trace=True)]
    assert "tiny_block_len" in names and "queue_wait_ms_p50" not in names
