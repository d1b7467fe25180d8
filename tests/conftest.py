"""Test fixture configuration.

Unit tests run on a virtual 8-device CPU mesh — the JAX analog of the
reference's shared ``local[*]`` SparkSession per suite
(core/test/base/src/main/scala/SparkSessionFactory.scala:40-51): multi-worker
parallelism exercised in one process, no real pod needed.

The suite always runs on the CPU, whatever the caller's environment pins:
XLA_FLAGS is set before the first backend initialization and the platform
is forced through jax.config as well as the environment.
"""

import atexit
import contextlib
import faulthandler
import hashlib
import os
import re
import shutil
import signal
import sys
import tempfile
import threading

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# One compile cache a run: the suite's time is XLA compiling tiny programs,
# and many tests build the SAME ones (the tiny model's training step, an
# engine's decode block, eager generate()'s operations). The first process
# to meet a program compiles it; the other workers, later tests and the
# subprocesses tests start read it back. The directory is new every run
# and removed at its end, so no entry of an earlier tree decides a test.
# The run's first process makes it (under xdist the controller, whose
# workers inherit the variables jax itself reads).
if "PYTEST_XDIST_WORKER" not in os.environ:
    _compile_cache = tempfile.mkdtemp(prefix="mmltpu-tests-xla-")
    atexit.register(shutil.rmtree, _compile_cache, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _compile_cache
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    "tests require the virtual 8-device CPU mesh; backend was initialized "
    f"too early (got {jax.devices()})"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


#: Seconds one phase of a case (its fixtures' set-up, its body, its
#: tear-down) may take before it FAILS, so that a hang costs one case and
#: not the whole run's clock. Some four times the longest cases PR 37 left
#: (the driver's command, six workers: a sandbox compile for a described
#: v5e 67 s, a tiny benchmark cell 62 s; CHANGES.md), because a case that
#: reaches it lowers the count and the driver's machine has read a third
#: slower than the builder's. ``@pytest.mark.limit(seconds)`` sets a
#: case's own.
CASE_LIMIT = 240.0

#: A main thread that waits inside C runs no signal handler. This long after
#: the limit a watchdog thread ends the worker with every thread's
#: traceback; xdist reports the case as failed and starts another worker.
HARD_GRACE = 60.0


def _ended_marker(nodeid):
    """Where a worker that ends itself leaves the case's name. Under
    ``--dist loadfile`` xdist hands the crashed case, with the rest of its
    file, to the next worker (measured, xdist 3.8: the same case ended
    worker after worker until the restarts ran out), so the next worker
    must know not to run it again. The run's compile-cache directory is
    the one place every worker of a run shares."""
    name = hashlib.sha1(nodeid.encode()).hexdigest()
    return os.path.join(os.environ["JAX_COMPILATION_CACHE_DIR"], "ended-" + name)


def _end_worker(nodeid, phase, limit):
    with open(_ended_marker(nodeid), "w") as f:
        f.write(f"{nodeid} ({phase})\n")
    print(f"\n{nodeid} ({phase}) still ran {HARD_GRACE:g} s past its limit "
          f"of {limit:g} s: ending this worker", file=sys.__stderr__)
    faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
    os._exit(1)


@contextlib.contextmanager
def _limited(item, phase):
    marker = item.get_closest_marker("limit")
    limit = float(marker.args[0]) if marker else CASE_LIMIT
    if threading.current_thread() is not threading.main_thread():
        yield  # signals reach the main thread only
        return
    if phase == "set-up" and os.path.exists(_ended_marker(item.nodeid)):
        pytest.fail(f"{item.nodeid} ended a worker at its hard limit in "
                    "this run, and is not run again", pytrace=False)

    def over(signum, frame):
        pytest.fail(
            f"{item.nodeid} ({phase}) ran past its limit of {limit:g} s "
            "(tests/conftest.py CASE_LIMIT, @pytest.mark.limit)",
            pytrace=False,
        )

    previous = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, limit)
    watchdog = threading.Timer(limit + HARD_GRACE, _end_worker,
                               (item.nodeid, phase, limit))
    watchdog.daemon = True
    watchdog.start()
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        watchdog.cancel()
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _limited(item, "set-up"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _limited(item, "call"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _limited(item, "tear-down"):
        yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def basic_dataset():
    """Tiny mixed-type dataset (reference TestBase.makeBasicDF,
    core/test/base/src/main/scala/TestBase.scala:138-152)."""
    from mmlspark_tpu.data.dataset import Dataset

    return Dataset(
        {
            "numbers": np.array([0, 1, 2, 3], dtype=np.int64),
            "doubles": np.array([0.0, 1.5, 3.0, 4.5]),
            "words": ["guitars", "drums", "bass", "keys"],
            "flags": np.array([True, False, True, False]),
        }
    )
