"""Two-process jax.distributed smoke test (multi-host bring-up).

The reference launches multi-node training by writing an MPI hostfile and
shelling out to ``mpiexec`` (CommandBuilders.scala:95-116
``MultiNodeParallelLauncher``). The TPU-native equivalent is
``jax.distributed.initialize`` + GSPMD collectives over the global device
view. This test actually EXECUTES that path: two OS processes on
localhost, one CPU device each, form a 2-process cluster through
``mmlspark_tpu.parallel.mesh.initialize_distributed`` and run a psum over
the global mesh — multi-host is exercised code, not a claim.

Runs in subprocesses so the parent's jax backend state is untouched.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

_WORKER = r"""
import os, sys
# one CPU device per process
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.parallel.mesh import initialize_distributed

coord = sys.argv[1]
pid = int(sys.argv[2])
initialize_distributed(
    coordinator_address=coord, num_processes=2, process_id=pid
)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils

mesh = Mesh(np.array(jax.devices()).reshape(2), ("data",))

# one global array sharded over the two processes; psum over the mesh
local = jnp.full((1, 4), float(pid + 1))
glob = multihost_utils.host_local_array_to_global_array(
    np.asarray(local), mesh, P("data")
)

@jax.jit
def total(x):
    return jnp.sum(x)  # GSPMD inserts the cross-host all-reduce

out = float(total(glob))
assert out == (1.0 + 2.0) * 4, out
print(f"proc {pid} ok: global sum {out}", flush=True)
"""


def test_two_process_psum(tmp_path):
    with socket.socket() as s:  # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the worker script lives in tmp_path, so python puts tmp_path (not our
    # cwd) on sys.path — the repo root must be importable even when the
    # package isn't pip-installed in this interpreter
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    existing = env.get("PYTHONPATH")
    env["PYTHONPATH"] = (
        repo_root + os.pathsep + existing if existing else repo_root
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), coord, str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd="/root/repo",
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} ok: global sum 12.0" in out, out
