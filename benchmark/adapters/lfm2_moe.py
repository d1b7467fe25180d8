"""Where the ``lfm2_moe`` reference's leaves sit in the tree that
``mmlspark_tpu.models.build_model("hybrid_lm", ...)`` builds for layers of
kind ``conv`` and for ``full`` layers with ``qk_norm``, cast to the width
the configuration stores its parameters in. The tree's walk and the leaves
the families share (gains, q/k/v/o, dense FFN, router, experts, embedding,
head) are the ``mimo_v2_flash`` adapter's. The filter is published
``(d, K)``, a channel's taps side by side; the program holds it ``(K, d)``,
a tap's channels in the lanes."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters import mimo_v2_flash as base

BUILDER = base.BUILDER


def _require_conv() -> None:
    """A program whose ``hybrid_lm`` has no short convolution (a commit
    before it came) says so when the family is resolved, before any
    weight is made."""
    from mmlspark_tpu.models import hybrid

    if not hasattr(hybrid, "ShortConv"):
        raise SystemExit(
            f"benchmark: this program's model builder {BUILDER!r} "
            "(mmlspark_tpu/models/hybrid.py) has no layer kind 'conv'; "
            f"the adapter {__name__} lays its leaves out for no other. "
            "Nothing was run.")


_require_conv()

#: reference leaf -> path inside one ``block{i}``'s params
_LAYER = {
    **{name: path for name, path in base._LAYER.items() if name != "sink"},
    "in_w": ("conv", "in_proj", "kernel"), "taps": ("conv", "taps"),
    "out_w": ("conv", "out_proj", "kernel"),
    "qn_g": ("attn", "q_norm", "scale"), "kn_g": ("attn", "k_norm", "scale"),
}
#: leaves the two sides hold transposed
_TRANSPOSED = ("taps",)


def to_program(params: dict, sz: dict) -> dict:
    """The reference's parameters as the program's variables, at the
    stored width. Traceable."""
    dtype = base._stored(sz)
    out: dict = {}
    for name, path in base._GLOBAL.items():
        base._put(out, path, params["globals"][name].astype(dtype))
    for i, layer in enumerate(params["layers"]):
        for name, leaf in layer.items():
            if name in _TRANSPOSED:
                leaf = leaf.T
            base._put(out, (f"block{i}", "params") + _LAYER[name],
                      leaf.astype(dtype))
    return out


def from_program(variables: dict, sz: dict, stack=None) -> dict:
    """The program's variables under the reference's names, float32."""
    layers = []
    for i in range(sz["layers"]):
        block = variables[f"block{i}"]["params"]
        layer = {}
        for name, path in _LAYER.items():
            try:
                leaf = jnp.asarray(base._get(block, path), jnp.float32)
            except KeyError:
                continue
            layer[name] = leaf.T if name in _TRANSPOSED else leaf
        layers.append(layer)
    return {"globals": {name: jnp.asarray(base._get(variables, path),
                                          jnp.float32)
                        for name, path in base._GLOBAL.items()},
            "layers": layers}
