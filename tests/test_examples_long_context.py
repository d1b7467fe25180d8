"""The example-script tier's two long-context examples (ring attention,
KV-cache generation); the rest and the tier's contract: test_examples.py."""

import os

import pytest

from tests.test_examples import LONG_CONTEXT, harness

EXAMPLES = harness.discover(list(LONG_CONTEXT), use_shard=False)


def test_both_are_discovered():
    assert len(EXAMPLES) == len(LONG_CONTEXT)


@pytest.mark.parametrize(
    "path", EXAMPLES, ids=[os.path.basename(p) for p in EXAMPLES]
)
def test_example_runs(path):
    ok, dt, detail = harness.run_one(path)
    assert ok, f"{os.path.basename(path)} failed after {dt:.1f}s: {detail}"
