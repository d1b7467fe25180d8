"""Where the ``mimo_v2_flash`` reference's leaves sit in the tree that
``mmlspark_tpu.models.build_model("hybrid_lm", ...)`` builds, cast to the
width the configuration stores its parameters in (``sz["param_bytes"]``:
2 is bfloat16; the reference draws matrices that bfloat16 holds exactly,
so the cast loses nothing). Under the harness's one ``jax.jit`` with
``init_params`` the float32 leaves are never all alive."""

from __future__ import annotations

import jax.numpy as jnp

BUILDER = "hybrid_lm"


def _require_builder() -> None:
    """A program that lacks the builder (a commit before it came) says so
    when the family is resolved, before any weight is made."""
    from mmlspark_tpu.models.registry import registered_models

    if BUILDER not in registered_models():
        raise SystemExit(
            f"benchmark: this program has no model builder {BUILDER!r} "
            f"(mmlspark_tpu/models/hybrid.py); the adapter {__name__} "
            "lays its leaves out for no other. Nothing was run.")


_require_builder()

#: reference leaf -> path inside one ``block{i}``'s params
_LAYER = {
    "ln1_g": ("ln1", "scale"), "ln2_g": ("ln2", "scale"),
    "q_w": ("attn", "q", "kernel"), "k_w": ("attn", "k", "kernel"),
    "v_w": ("attn", "v", "kernel"), "o_w": ("attn", "attn_out", "kernel"),
    "sink": ("attn", "sink"),
    "gate_w": ("mlp_gate", "kernel"), "up_w": ("mlp_up", "kernel"),
    "down_w": ("mlp_out", "kernel"),
    "router_w": ("moe", "router"), "select_bias": ("moe", "select_bias"),
    "e_gate_w": ("moe", "experts", "w_gate"),
    "e_up_w": ("moe", "experts", "w_up"),
    "e_down_w": ("moe", "experts", "w_down"),
}
_GLOBAL = {
    "wte": ("embed", "params", "token", "embedding"),
    "lnf_g": ("z", "params", "ln_f", "scale"),
    "head_w": ("z", "params", "head", "kernel"),
}


def _put(tree: dict, path: tuple, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _get(tree: dict, path: tuple):
    for key in path:
        tree = tree[key]
    return tree


def _stored(sz: dict):
    return jnp.bfloat16 if sz.get("param_bytes", 4) == 2 else jnp.float32


def to_program(params: dict, sz: dict) -> dict:
    """The reference's parameters as the program's variables, at the
    stored width. Traceable."""
    dtype = _stored(sz)
    out: dict = {}
    for name, path in _GLOBAL.items():
        _put(out, path, params["globals"][name].astype(dtype))
    for i, layer in enumerate(params["layers"]):
        for name, leaf in layer.items():
            _put(out, (f"block{i}", "params") + _LAYER[name],
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
                layer[name] = f32(_get(block, path))
            except KeyError:
                continue
        layers.append(layer)
    return {"globals": {name: f32(_get(variables, path))
                        for name, path in _GLOBAL.items()},
            "layers": layers}
