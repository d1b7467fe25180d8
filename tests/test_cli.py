"""The mml-tpu launcher (the mml-exec analog, tools/bin/mml-exec:1-40)."""

from __future__ import annotations

import json
import os
import subprocess
import sys


def _run(*args, timeout=240):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "mmlspark_tpu", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd="/root/repo",
    )


def test_env_on_cpu_mesh():
    res = _run("--cpu-mesh", "4", "env")
    assert res.returncode == 0, res.stderr
    info = json.loads(res.stdout)
    assert info["num_devices"] == 4
    assert info["platform"] == "cpu"


def test_config_listing():
    res = _run("config")
    assert res.returncode == 0, res.stderr
    conf = json.loads(res.stdout)
    assert conf["native_cc"]["value"] == "c++"
    assert "doc" in conf["cache_dir"]


def test_run_script(tmp_path):
    script = tmp_path / "user.py"
    script.write_text(
        "import sys\n"
        "from mmlspark_tpu.data.dataset import Dataset\n"
        "ds = Dataset({'a': [1.0, 2.0]})\n"
        "print('rows', ds.num_rows, 'argv', sys.argv[1:])\n"
    )
    res = _run("run", str(script), "--flag", "x")
    assert res.returncode == 0, res.stderr
    assert "rows 2 argv ['--flag', 'x']" in res.stdout


def test_zoo_list_and_download(tmp_path):
    res = _run(
        "zoo", "list",
        "--local-repo", str(tmp_path / "repo"),
        "--remote", "/root/repo/models/zoo_repo",
    )
    assert res.returncode == 0, res.stderr
    assert "ResNet20_Blobs" in res.stdout
    res = _run(
        "zoo", "download", "ResNet20_Blobs",
        "--local-repo", str(tmp_path / "repo"),
        "--remote", "/root/repo/models/zoo_repo",
    )
    assert res.returncode == 0, res.stderr
    assert "ResNet20_Blobs ->" in res.stdout


def test_multihost_env_contract(monkeypatch):
    """launch-pod.sh's env vars reach jax.distributed.initialize."""
    calls = {}

    import mmlspark_tpu.parallel.mesh as mesh

    class FakeDistributed:
        @staticmethod
        def initialize(coordinator_address, num_processes, process_id):
            calls.update(
                addr=coordinator_address, n=num_processes, pid=process_id
            )

    import jax

    monkeypatch.setenv("MMLSPARK_TPU_COORDINATOR", "10.0.0.1:8476")
    monkeypatch.setenv("MMLSPARK_TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("MMLSPARK_TPU_PROCESS_ID", "2")
    monkeypatch.setattr(jax, "distributed", FakeDistributed)
    mesh.initialize_distributed()
    assert calls == {"addr": "10.0.0.1:8476", "n": 4, "pid": 2}


def test_evidence_profile_refuses_without_a_tpu():
    """`mml-tpu evidence profile` reaches the tool; on a CPU-only backend
    it exits 2 before tracing anything, in the same process (no probe
    child)."""
    r = _run("evidence", "profile")
    assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)
    assert "no TPU" in r.stderr


def test_serve_demo_takes_a_model_shape():
    """`serve --demo --model` runs the demo at any shape through the CLI
    (GPT-2 small on the chip); a bad field is a typed error."""
    r = _run("serve", "--demo", "--slots", "2", "--requests", "3",
             "--max-new-tokens", "3", "--model",
             "vocab=96:d_model=48:heads=4:depth=1:cache_len=64:max_prompt=40")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["completed"] == 3
    assert line["model_config"] == {"vocab": 96, "d_model": 48, "heads": 4,
                                    "depth": 1}
    assert line["cache_len"] == 64
    bad = _run("serve", "--demo", "--model", "width=3")
    assert bad.returncode != 0 and "bad --model field" in bad.stderr
