"""Where the ``gpt2`` reference's leaves sit in the tree that
``mmlspark_tpu.models.build_model("transformer_lm", ...)`` builds: the one
place the benchmark knows the program's parameter layout. A model family
with another tree, or other leaves over the same builder, brings an
adapter file of its own. ``sz`` is the reference's sizes, of which this
adapter reads ``layers``."""

from __future__ import annotations

#: reference leaf -> path inside one ``block{i}`` of the program's tree
_LAYER = {
    "ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
    "qkv_w": ("attn", "qkv", "kernel"), "qkv_b": ("attn", "qkv", "bias"),
    "proj_w": ("attn", "attn_out", "kernel"),
    "proj_b": ("attn", "attn_out", "bias"),
    "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
    "fc_w": ("mlp_in", "kernel"), "fc_b": ("mlp_in", "bias"),
    "out_w": ("mlp_out", "kernel"), "out_b": ("mlp_out", "bias"),
}
_GLOBAL = {
    "wte": ("embed", "params", "token", "embedding"),
    "wpe": ("embed", "params", "pos"),
    "lnf_g": ("z", "params", "ln_f", "scale"),
    "lnf_b": ("z", "params", "ln_f", "bias"),
    "head_w": ("z", "params", "head", "kernel"),
    "head_b": ("z", "params", "head", "bias"),
}


def _put(tree: dict, path: tuple, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _get(tree: dict, path: tuple):
    for key in path:
        tree = tree[key]
    return tree


def to_program(params: dict, sz: dict) -> dict:
    """The reference's (layer-stacked) parameters as the program's
    variables. Traceable."""
    out: dict = {}
    for name, path in _GLOBAL.items():
        _put(out, path, params[name])
    for i in range(sz["layers"]):
        for name, path in _LAYER.items():
            _put(out, (f"block{i}", "params") + path, params[name][i])
    return out


def from_program(variables: dict, sz: dict, stack) -> dict:
    """The program's variables under the reference's names, per-layer
    leaves stacked with ``stack`` (``numpy.stack`` or ``jnp.stack``)."""
    out = {name: _get(variables, path) for name, path in _GLOBAL.items()}
    for name, path in _LAYER.items():
        out[name] = stack([
            _get(variables, (f"block{i}", "params") + path)
            for i in range(sz["layers"])])
    return out
