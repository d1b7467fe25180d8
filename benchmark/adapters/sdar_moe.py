"""Where the ``sdar_moe`` reference's leaves sit in the tree that
``mmlspark_tpu.models.build_model("hybrid_lm", ...)`` builds for ``full``
layers with ``qk_norm`` and a ``softmax`` router, cast to the width the
configuration stores its parameters in. The tree's walk and the leaves the
families share (gains, q/k/v/o, router, experts, embedding, head) are the
``mimo_v2_flash`` adapter's; the heads' norms are where the ``lfm2_moe``
adapter puts them. A softmax router has no selection bias."""

from __future__ import annotations

import inspect

from benchmark.adapters import lfm2_moe
from benchmark.adapters import mimo_v2_flash as base

BUILDER = base.BUILDER


def _require_blocks() -> None:
    """A program whose ``hybrid_lm`` has no block length or no softmax
    router (a commit before they came) says so when the family is
    resolved, before any weight is made."""
    from mmlspark_tpu.models import hybrid

    lacks = [name for name in ("block", "router")
             if name not in inspect.signature(hybrid.hybrid_lm).parameters]
    if lacks:
        raise SystemExit(
            f"benchmark: this program's model builder {BUILDER!r} "
            "(mmlspark_tpu/models/hybrid.py) has no "
            f"{' and no '.join(lacks)} argument: it generates no block by "
            "diffusion, or routes by no softmax; the adapter "
            f"{__name__} lays its leaves out for no other. Nothing was run.")


_require_blocks()

#: reference leaf -> path inside one ``block{i}``'s params
_LAYER = {name: path for name, path in lfm2_moe._LAYER.items()
          if name not in ("select_bias", "in_w", "taps", "out_w", "gate_w",
                          "up_w", "down_w")}


def to_program(params: dict, sz: dict) -> dict:
    """The reference's parameters as the program's variables, at the
    stored width. Traceable."""
    dtype = base._stored(sz)
    out: dict = {}
    for name, path in base._GLOBAL.items():
        base._put(out, path, params["globals"][name].astype(dtype))
    for i, layer in enumerate(params["layers"]):
        for name, leaf in layer.items():
            base._put(out, (f"block{i}", "params") + _LAYER[name],
                      leaf.astype(dtype))
    return out


def from_program(variables: dict, sz: dict, stack=None) -> dict:
    """The program's variables under the reference's names, float32."""
    return base.from_program(variables, sz)
