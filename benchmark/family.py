"""A model family as data: the three modules a configuration's file names,
found under the root the runner was given, and checked for what they have
to answer before any weight is made.

``program.reference`` (absent: ``gpt2``) names ``references/<name>.py``,
the plain reference's equations, and ``counts/<name>.py``, the operations
and bytes the algorithm needs. ``program.adapter`` names
``adapters/<name>.py``, where the reference's leaves sit in the program's
tree; ``program.builder`` names the program's model builder where that is
not the adapter's name. ``benchmark/README.md`` ("A model family") says
what each module answers. No module of the harness imports a family's
module by name: each gets them from here.
"""

from __future__ import annotations

import importlib
import importlib.util
import os
import sys
import zlib
from typing import Any, NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what a reference module answers, by the kind of cell it is asked to judge
REFERENCE = {
    "serving": ("sizes", "init_params", "MODES", "served_gaps_fn"),
    "training": ("sizes", "init_params", "MODES", "loss_sum", "split_leaves"),
}
ADAPTER = ("to_program", "from_program")
#: the sizes the harness itself reads: the vocabulary that traffic draws
#: ids from and logits are over, and the number of layers
SIZES = ("v", "layers")


class Family(NamedTuple):
    cfg: dict        # the configuration's file
    sz: dict         # reference.sizes(cfg) with what counts.sizes adds
    reference: Any
    counts: Any
    adapter: Any
    builder: str     # the program's model builder


def seed_key(seed: int):
    """A key from any whole number up to 2**63: a seed past 31 bits is
    folded in two halves. The one key of a run: the harness makes the
    program's weights from it, every reference its own."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def load(root: str, kind: str, name: str):
    """The module ``<root>/benchmark/<kind>/<name>.py``. Under the
    harness's own root that is the package's module; under any other (a
    test's temporary tree) the file is loaded by its path."""
    root = os.path.abspath(root)
    path = os.path.join(root, "benchmark", kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(
            f"benchmark: no module {name!r} under benchmark/{kind}/ "
            f"({path} is not there)")
    if root == ROOT:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    known = f"benchmark_at_{zlib.crc32(root.encode()):08x}.{kind}.{name}"
    if known not in sys.modules:
        spec = importlib.util.spec_from_file_location(known, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[known] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[known]
            raise
    return sys.modules[known]


def require(module, names, what: str) -> None:
    """Fail, naming the module and what it lacks."""
    lacks = [n for n in names if not hasattr(module, n)]
    if lacks:
        raise SystemExit(
            f"benchmark: {what} {module.__name__} ({module.__file__}) "
            f"lacks {', '.join(lacks)}")


def resolve(cfg: dict, mix_kind: str, control_mode: str | None = None,
            root: str = ROOT) -> Family:
    """The family of ``cfg``, checked for a cell of ``mix_kind`` whose
    control runs in ``control_mode``."""
    program = cfg["program"]
    name = program.get("reference", "gpt2")
    reference = load(root, "references", name)
    counts = load(root, "counts", name)
    adapter = load(root, "adapters", program["adapter"])
    kind = "training" if mix_kind == "train" else "serving"
    require(reference, REFERENCE[kind],
            f"a {mix_kind} cell's {kind} reference")
    require(counts, ("sizes",), "the counts module")
    require(adapter, ADAPTER, "the adapter")
    if control_mode is not None and control_mode not in reference.MODES:
        raise SystemExit(
            f"benchmark: reference {reference.__name__} rounds its linear "
            f"layers to {', '.join(reference.MODES)}, not to the cell's "
            f"control_mode {control_mode!r}")
    sz = counts.sizes(cfg, reference.sizes(cfg))
    lacks = [k for k in SIZES if k not in sz]
    if lacks:
        raise SystemExit(
            f"benchmark: sizes() of reference {reference.__name__} lacks "
            f"{', '.join(lacks)}")
    return Family(cfg, sz, reference, counts, adapter,
                  program.get("builder", program["adapter"]))
