"""A second family, for the harness's tests: the plain float32 reference of
a decoder with grouped KV heads and rotary positions, as the builder
``transformer_lm`` makes one with ``pos_embedding="rope"`` and ``kv_heads``
under ``heads``. Pre-LayerNorm blocks, one fused projection whose columns
are the query heads, then the key heads, then the value heads; rotate-half
RoPE over the whole head at base ``rope_theta`` on queries and keys; query
head ``i`` reads key and value head ``i // (heads // kv_heads)``; tanh-GELU
MLP, final LayerNorm, untied head with a bias. No learned positions.

It reads its own configuration keys and brings its own leaves; the
arithmetic of a linear layer and its rounding ``mode``s are the ``gpt2``
reference's, imported. Nothing of the program is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references import gpt2 as plain

HI, MODES = plain.HI, plain.MODES
gelu, layer_norm, linear = plain.gelu, plain.layer_norm, plain.linear

#: leaf -> (shape from sizes, init kind, a per-layer leaf)
LEAVES = {
    "wte": (("v", "d"), "weight", False),
    "lnf_g": (("d",), "gain", False), "lnf_b": (("d",), "bias", False),
    "head_w": (("d", "v"), "weight", False),
    "head_b": (("v",), "bias", False),
    "ln1_g": (("d",), "gain", True), "ln1_b": (("d",), "bias", True),
    "qkv_w": (("d", "qkv"), "weight", True),
    "qkv_b": (("qkv",), "bias", True),
    "proj_w": (("d", "d"), "weight", True), "proj_b": (("d",), "bias", True),
    "ln2_g": (("d",), "gain", True), "ln2_b": (("d",), "bias", True),
    "fc_w": (("d", "f"), "weight", True), "fc_b": (("f",), "bias", True),
    "out_w": (("f", "d"), "weight", True), "out_b": (("d",), "bias", True),
}


def sizes(cfg: dict) -> dict:
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    head = d // heads
    return {"d": d, "f": int(cfg["intermediate_size"]),
            "v": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"]), "heads": heads,
            "kv_heads": kv_heads, "head": head, "kv_d": kv_heads * head,
            "qkv": (heads + 2 * kv_heads) * head,
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["layer_norm_epsilon"])}


def init_params(key, sz: dict) -> dict:
    """Every leaf from the run's key, per-layer leaves stacked on a leading
    layer axis. Traceable."""
    out = {}
    for i, name in enumerate(sorted(LEAVES)):
        dims, kind, per_layer = LEAVES[name]
        shape = tuple(sz[k] for k in dims)
        if per_layer:
            shape = (sz["layers"],) + shape
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = 1.0 + 0.1 * n if kind == "gain" else 0.02 * n
    return out


def rope(x, theta: float):
    """Rotate-half over the whole head of ``x`` (B, T, H, D), positions
    0 .. T-1."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), -1)


def block(x, p, sz: dict, mode: str):
    b, t, d = x.shape
    h, hk, hd = sz["heads"], sz["kv_heads"], sz["head"]
    y = layer_norm(x, p["ln1_g"], p["ln1_b"], sz["eps"])
    qkv = linear(y, p["qkv_w"], p["qkv_b"], mode).reshape(b, t, h + 2 * hk, hd)
    q = rope(qkv[:, :, :h], sz["theta"])
    k = jnp.repeat(rope(qkv[:, :, h:h + hk], sz["theta"]), h // hk, axis=2)
    v = jnp.repeat(qkv[:, :, h + hk:], h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(b, t, d)
    x = x + linear(o, p["proj_w"], p["proj_b"], mode)
    y = layer_norm(x, p["ln2_g"], p["ln2_b"], sz["eps"])
    y = gelu(linear(y, p["fc_w"], p["fc_b"], mode))
    return x + linear(y, p["out_w"], p["out_b"], mode)


def forward(params: dict, ids, sz: dict, mode: str = "f32"):
    """Logits (B, T, V) in float32 for token ids (B, T)."""
    x = params["wte"][ids]
    stacked = {n: params[n] for n, leaf in LEAVES.items() if leaf[2]}
    x, _ = jax.lax.scan(lambda x, p: (block(x, p, sz, mode), None), x,
                        stacked)
    x = layer_norm(x, params["lnf_g"], params["lnf_b"], sz["eps"])
    return linear(x, params["head_w"], params["head_b"], mode)


def served_gaps(params: dict, seq, first: int, n: int, sz: dict,
                mode: str = "f32"):
    """As the ``gpt2`` reference's: per served token of ``seq`` (1, L) the
    gap of its logit below the reference's best, and the same gap for the
    token that ``mode`` puts first."""
    ref = forward(params, seq, sz, "f32")[0]
    last = seq.shape[1] - 1
    pos = jnp.clip(first - 1 + jnp.arange(seq.shape[1]), 0, last)
    at = ref[pos]
    toks = seq[0][jnp.clip(pos + 1, 0, last)]
    best = at.max(-1)
    served = best - jnp.take_along_axis(at, toks[:, None], axis=1)[:, 0]
    if mode == "f32":
        return served, served
    pick = jnp.argmax(forward(params, seq, sz, mode)[0][pos], axis=-1)
    return served, best - jnp.take_along_axis(at, pick[:, None], axis=1)[:, 0]


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    params = jax.jit(lambda k: init_params(k, sz))(key)
    fn = jax.jit(functools.partial(served_gaps, sz=sz, mode=mode))
    return lambda seq, first, n: fn(params, seq, first, n)
