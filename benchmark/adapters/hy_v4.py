"""Where the ``hy_v4`` reference's leaves sit in the tree that
``mmlspark_tpu.models.build_model("hybrid_lm", ...)`` builds for sparse
latent layers (``index_topk``) with a query low rank, a gate, a sink and
several residual streams, cast to the width the configuration stores its
parameters in; the head kept in float32 (``head_fp32``). The tree's walk
and the leaves the families share (gains, dense FFN, router, experts,
shared expert, embedding) are the ``mimo_v2_flash`` and ``deepseek_v3``
adapters'."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters import deepseek_v3 as latent
from benchmark.adapters import mimo_v2_flash as base

BUILDER = base.BUILDER


def _require_sparse() -> None:
    """A program whose ``hybrid_lm`` has no sparse latent attention (a
    commit before it came) says so when the family is resolved, before
    any weight is made."""
    from mmlspark_tpu.models import hybrid

    if not hasattr(hybrid, "Indexer"):
        raise SystemExit(
            f"benchmark: this program's model builder {BUILDER!r} "
            "(mmlspark_tpu/models/hybrid.py) has no sparse latent attention "
            "(index_topk, an indexer) and no residual streams (hc_streams); "
            f"the adapter {__name__} lays its leaves out for no other. "
            "Nothing was run.")


_require_sparse()

#: reference leaf -> path inside one ``block{i}``'s params
_LAYER = {
    **{name: path for name, path in latent._LAYER.items()
       if name not in ("q_w",)},
    "qa_w": ("attn", "q_a", "kernel"), "qan_g": ("attn", "q_a_norm", "scale"),
    "qb_w": ("attn", "q_b", "kernel"),
    "ag_w": ("attn", "gate", "kernel"), "sink": ("attn", "sink"),
    "iq_w": ("attn", "indexer", "wq", "kernel"),
    "ik_w": ("attn", "indexer", "wk", "kernel"),
    "ikn_g": ("attn", "indexer", "k_norm", "scale"),
    "ikn_b": ("attn", "indexer", "k_norm", "bias"),
    "iw_w": ("attn", "indexer", "weights", "kernel"),
    **{f"hc{i}_{leaf}": (f"hc_{part}", name)
       for i, part in ((1, "attn"), (2, "ffn"))
       for leaf, name in (("phi", "phi"), ("b", "bias"), ("a", "alpha"))},
}
#: leaves kept in float32 whatever the stored width
_FLOAT32 = ("head_w",)


def to_program(params: dict, sz: dict) -> dict:
    """The reference's parameters as the program's variables, at the
    stored width. Traceable."""
    dtype = base._stored(sz)
    out: dict = {}
    for name, path in base._GLOBAL.items():
        base._put(out, path, params["globals"][name].astype(
            jnp.float32 if name in _FLOAT32 else dtype))
    for i, layer in enumerate(params["layers"]):
        for name, leaf in layer.items():
            base._put(out, (f"block{i}", "params") + _LAYER[name],
                      leaf.astype(dtype))
    return out


def from_program(variables: dict, sz: dict, stack=None) -> dict:
    """The program's variables under the reference's names, float32."""
    def f32(leaf):
        return jnp.asarray(leaf, jnp.float32)

    layers = []
    for i in range(sz["layers"]):
        block = variables[f"block{i}"]["params"]
        layer = {}
        for name, path in _LAYER.items():
            try:
                layer[name] = f32(base._get(block, path))
            except KeyError:
                continue
        layers.append(layer)
    return {"globals": {name: f32(base._get(variables, path))
                        for name, path in base._GLOBAL.items()},
            "layers": layers}
