"""The arrows of the module tree point one way: ``ops``, ``models``,
``parallel`` and ``core`` sit below ``serve`` and ``train`` and import
neither, at module level or inside a function. Read with ``ast``: no
module is imported, no backend touched."""

from __future__ import annotations

import ast
from pathlib import Path

PACKAGE = Path(__file__).resolve().parents[1] / "mmlspark_tpu"
LOWER = ("ops", "models", "parallel", "core")
UPPER = ("mmlspark_tpu.serve", "mmlspark_tpu.train")

#: upward imports that stand as named debts in ROADMAP.md, each ``(file
#: under mmlspark_tpu/, module it imports)``. Empty since PR 31, whose
#: walk found ``models/transformer.py`` -> ``serve.cache_pool`` only
ALLOWED: set = set()


def _imports(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and not node.level:
            yield node.module
            # ``from mmlspark_tpu import serve``
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


def _upward(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    return {name for name in _imports(tree)
            if any(name == up or name.startswith(up + ".") for up in UPPER)}


def test_lower_layers_import_neither_serve_nor_train():
    found = set()
    for layer in LOWER:
        for path in sorted((PACKAGE / layer).rglob("*.py")):
            rel = path.relative_to(PACKAGE).as_posix()
            # a relative import cannot climb out of its own layer
            # without naming it, and none here does
            found |= {(rel, name) for name in _upward(path)}
    modules = {(rel, ".".join(name.split(".")[:3])) for rel, name in found}
    assert modules - ALLOWED == set(), sorted(modules - ALLOWED)
    # an allowance that nothing needs any more is taken out
    assert ALLOWED - modules == set(), sorted(ALLOWED - modules)
