"""Example-script tier: every examples/e*.py must run headless within the
per-notebook timeout — the analog of the reference's local notebook tests
(tools/notebook/tester/TestNotebooksLocally.py: each sample notebook
executes via nbconvert with a 600 s timeout)."""

import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "examples")
)
import harness  # noqa: E402

#: the two long-context examples, a third of this tier's time, run from
#: test_examples_long_context.py: under ``--dist loadfile`` a file is one
#: worker's, and this one alone was over a third of the suite's wall
LONG_CONTEXT = ("e306", "e307")

# ignore PROC_SHARD here: the pytest tier always covers every example
EXAMPLES = [
    path for path in harness.discover([], use_shard=False)
    if not os.path.basename(path).startswith(LONG_CONTEXT)
]


def test_examples_discovered():
    assert len(EXAMPLES) >= 7


@pytest.mark.parametrize(
    "path", EXAMPLES, ids=[os.path.basename(p) for p in EXAMPLES]
)
def test_example_runs(path):
    ok, dt, detail = harness.run_one(path)
    assert ok, f"{os.path.basename(path)} failed after {dt:.1f}s: {detail}"
