"""Where the ``gqa_rope`` reference's leaves sit in the tree that
``build_model("transformer_lm", pos_embedding="rope", kv_heads=...)``
builds: the ``transformer_lm`` adapter's places without the table of
learned positions, so a second adapter over the same builder."""

from __future__ import annotations

_BLOCK = {
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
    "lnf_g": ("z", "params", "ln_f", "scale"),
    "lnf_b": ("z", "params", "ln_f", "bias"),
    "head_w": ("z", "params", "head", "kernel"),
    "head_b": ("z", "params", "head", "bias"),
}


def _paths(sz: dict):
    """(reference leaf, layer or None, path in the program's tree)."""
    for name, path in _GLOBAL.items():
        yield name, None, path
    for i in range(sz["layers"]):
        for name, path in _BLOCK.items():
            yield name, i, (f"block{i}", "params") + path


def to_program(params: dict, sz: dict) -> dict:
    out: dict = {}
    for name, layer, path in _paths(sz):
        tree = out
        for key in path[:-1]:
            tree = tree.setdefault(key, {})
        tree[path[-1]] = params[name] if layer is None else params[name][layer]
    return out


def from_program(variables: dict, sz: dict, stack) -> dict:
    rows: dict = {}
    for name, layer, path in _paths(sz):
        leaf = variables
        for key in path:
            leaf = leaf[key]
        rows.setdefault(name, []).append(leaf)
    return {name: got[0] if name in _GLOBAL else stack(got)
            for name, got in rows.items()}
