"""Execute the sample notebooks headless — the reference harness analog.

Reference contract (tools/notebook/tester/NotebookTestSuite.py:12-13,
40-72 + TestNotebooksLocally.py:46-52): every sample notebook runs
through nbconvert's ExecutePreprocessor with a 600 s timeout, shardable
across processes with ``PROC_SHARD=i/m``. Same contract here; the
kernel inherits the virtual 8-device CPU mesh environment so notebooks
exercise the same sharded paths as the test suite.

Usage:
    python tools/notebook_tester.py            # run all samples
    PROC_SHARD=0/2 python tools/notebook_tester.py
    python tools/notebook_tester.py 301 305    # run by number prefix
"""

from __future__ import annotations

import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLES = os.path.join(REPO, "notebooks", "samples")
TIMEOUT_S = 600  # NotebookTestSuite.py:13


def discover(selectors: list[str]) -> list[str]:
    names = sorted(
        n for n in os.listdir(SAMPLES) if n.endswith(".ipynb")
    )
    if selectors:
        names = [
            n for n in names
            if any(n.startswith(s) for s in selectors)
        ]
    shard = os.environ.get("PROC_SHARD")
    if shard:
        i, m = (int(p) for p in shard.split("/"))
        names = [n for k, n in enumerate(names) if k % m == i]
    return names


def run_one(name: str) -> tuple[bool, float, str]:
    import nbformat
    from nbconvert.preprocessors import ExecutePreprocessor

    # kernel env: CPU mesh before any jax import, repo on sys.path.
    # FORCE cpu (not setdefault): notebooks are validated on the CPU mesh
    # whatever the ambient env pins
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["PYTHONPATH"] = (
        REPO + os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else REPO
    )

    path = os.path.join(SAMPLES, name)
    nb = nbformat.read(path, as_version=4)
    ep = ExecutePreprocessor(timeout=TIMEOUT_S, kernel_name="python3")
    t0 = time.time()
    try:
        # notebooks resolve repo-relative paths (zoo, fixtures) from the
        # examples dir, matching the scripts they are generated from
        ep.preprocess(
            nb, {"metadata": {"path": os.path.join(REPO, "examples")}}
        )
        return True, time.time() - t0, ""
    except Exception as e:  # noqa: BLE001 — harness reports, not raises
        msg = re.sub(r"\x1b\[[0-9;]*m", "", str(e))  # strip ANSI
        return False, time.time() - t0, msg[-2000:]


def main() -> None:
    names = discover(sys.argv[1:])
    if not names:
        raise SystemExit("no notebooks matched")
    failures = []
    for name in names:
        ok, dt, err = run_one(name)
        status = "ok" if ok else "FAIL"
        print(f"[{status}] {name} ({dt:.1f}s)")
        if not ok:
            failures.append((name, err))
    if failures:
        for name, err in failures:
            print(f"\n--- {name} ---\n{err}")
        raise SystemExit(f"{len(failures)}/{len(names)} notebooks failed")
    print(f"all {len(names)} notebooks passed")


if __name__ == "__main__":
    main()
