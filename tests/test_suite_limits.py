"""The suite's own limit on every case (tests/conftest.py ``_limited``):
a case past its limit fails by name, and the file goes on."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)


def run_pytest(path, text, *options):
    """pytest in a subprocess on one file, under this suite's conftest (as
    a plugin: the file lies outside ``tests/``) and its registered markers."""
    path.write_text(textwrap.dedent(text))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([TESTS, ROOT]))
    return subprocess.run(
        [sys.executable, "-m", "pytest", str(path), "-q", "-p", "conftest",
         "-c", os.path.join(ROOT, "pyproject.toml"), "--strict-markers",
         "-p", "no:cacheprovider", "-p", "no:randomly", *options],
        env=env, capture_output=True, text=True, timeout=200,
    )


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    return run_pytest(tmp_path_factory.mktemp("limits") / "test_waits.py", """
        import threading
        import pytest

        @pytest.mark.limit(1)
        def test_waits_on_what_never_comes():
            threading.Event().wait(30)

        def test_after_it():
            pass
    """, "-p", "no:xdist")


def test_a_case_past_its_limit_fails_by_name(run):
    assert run.returncode == 1, run.stdout + run.stderr
    assert ("test_waits_on_what_never_comes (call) ran past its limit of 1 s"
            in run.stdout), run.stdout


def test_the_next_case_of_the_file_still_runs(run):
    assert "1 failed, 1 passed" in run.stdout, run.stdout


def test_a_case_no_signal_reaches_ends_one_worker_once(tmp_path):
    """Under ``--dist loadfile`` xdist hands a crashed case back with the
    rest of its file: the next worker must refuse it and run the rest."""
    run = run_pytest(tmp_path / "test_blocks.py", """
        import signal
        import time
        import conftest
        import pytest

        conftest.HARD_GRACE = 1.0

        @pytest.mark.limit(1)
        def test_blocks_the_alarm():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(60)

        def test_after_it():
            pass
    """, "-p", "xdist", "-n", "1", "--dist", "loadfile")
    assert "crashed while running" in run.stdout, run.stdout + run.stderr
    assert "is not run again" in run.stdout, run.stdout
    assert "1 failed, 1 passed, 1 error" in run.stdout, run.stdout
