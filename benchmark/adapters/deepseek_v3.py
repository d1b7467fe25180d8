"""Where the ``deepseek_v3`` reference's leaves sit in the tree that
``mmlspark_tpu.models.build_model("hybrid_lm", ...)`` builds for layers of
attention kind ``mla`` with a shared expert, cast to the width the
configuration stores its parameters in. The tree's walk and the leaves the
two families share (gains, dense FFN, router, experts, embedding, head) are
the ``mimo_v2_flash`` adapter's."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.adapters import mimo_v2_flash as base

BUILDER = base.BUILDER


def _require_latent() -> None:
    """A program whose ``hybrid_lm`` has no latent attention (a commit
    before it came) says so when the family is resolved, before any
    weight is made."""
    from mmlspark_tpu.models import hybrid

    if not hasattr(hybrid, "LatentAttention"):
        raise SystemExit(
            f"benchmark: this program's model builder {BUILDER!r} "
            "(mmlspark_tpu/models/hybrid.py) has no attention kind 'mla'; "
            f"the adapter {__name__} lays its leaves out for no other. "
            "Nothing was run.")


_require_latent()

#: reference leaf -> path inside one ``block{i}``'s params
_LAYER = {
    **{name: path for name, path in base._LAYER.items()
       if name not in ("q_w", "k_w", "v_w", "o_w", "sink")},
    "q_w": ("attn", "q", "kernel"), "kva_w": ("attn", "kv_a", "kernel"),
    "kvn_g": ("attn", "kv_norm", "scale"), "kvb_w": ("attn", "kv_b"),
    "o_w": ("attn", "attn_out", "kernel"),
    "s_gate_w": ("moe", "shared_gate", "kernel"),
    "s_up_w": ("moe", "shared_up", "kernel"),
    "s_down_w": ("moe", "shared_out", "kernel"),
}


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
