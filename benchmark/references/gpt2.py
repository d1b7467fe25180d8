"""The plain reference of the ``gpt2`` family (what a configuration with no
``program.reference`` means): GPT-2 in straightforward ``jax.numpy``,
float32.

It follows the published description (Radford et al. 2019; the
``config.json`` each configuration file names): learned positions,
pre-LayerNorm blocks, fused QKV projection, causal softmax attention
scaled by ``1/sqrt(head)``, tanh-GELU MLP, a final LayerNorm and an output
head. No kernels, no cache, no batching tricks; every matrix product runs
at ``precision=HIGHEST`` so that the TPU does not round it to bfloat16.

Departures from the published model, each listed in the configuration
files under ``assumed``: the output head is untied from the token
embedding and has a bias (this system builds every LM so), and the
weights are random from a seed.

This module imports nothing of the program and takes nothing the program
has made. The weights come from :func:`init_params` and the run's key
(``benchmark.family.seed_key``); the harness calls the same function, in
one jitted call, to make the weights it hands to the program.

``mode`` selects the arithmetic of the linear layers and is what the
CONTROL changes: ``"f32"`` is the reference; ``"bf16"``, ``"int8"`` and
``"fp8"`` round both operands of every linear layer (int8: per-token and
per-output-channel scales, symmetric; fp8: e4m3 with the same scales)
with a straight-through gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
#: the arithmetic the linear layers can run in: a cell's ``control_mode``
#: is one of these, and ``f32`` is the reference itself
MODES = ("f32", "bf16", "int8", "fp8")

#: per-layer leaves: name -> (shape from sizes, init kind)
LAYER_LEAVES = {
    "ln1_g": (("d",), "gain"), "ln1_b": (("d",), "bias"),
    "qkv_w": (("d", "3d"), "weight"), "qkv_b": (("3d",), "bias"),
    "proj_w": (("d", "d"), "weight"), "proj_b": (("d",), "bias"),
    "ln2_g": (("d",), "gain"), "ln2_b": (("d",), "bias"),
    "fc_w": (("d", "f"), "weight"), "fc_b": (("f",), "bias"),
    "out_w": (("f", "d"), "weight"), "out_b": (("d",), "bias"),
}
GLOBAL_LEAVES = {
    "wte": (("v", "d"), "weight"), "wpe": (("p", "d"), "weight"),
    "lnf_g": (("d",), "gain"), "lnf_b": (("d",), "bias"),
    "head_w": (("d", "v"), "weight"), "head_b": (("v",), "bias"),
}


def sizes(cfg: dict) -> dict:
    """The published config's sizes under the short names used here."""
    d = int(cfg["n_embd"])
    return {"d": d, "3d": 3 * d, "f": int(cfg.get("n_inner") or 4 * d),
            "v": int(cfg["vocab_size"]), "p": int(cfg["n_positions"]),
            "layers": int(cfg["n_layer"]), "heads": int(cfg["n_head"]),
            "eps": float(cfg.get("layer_norm_epsilon", 1e-5))}


def leaf_shape(name: str, sz: dict) -> tuple:
    dims = (LAYER_LEAVES.get(name) or GLOBAL_LEAVES[name])[0]
    return tuple(sz[k] for k in dims)


def _name_id(name: str) -> int:
    names = sorted(list(LAYER_LEAVES) + list(GLOBAL_LEAVES))
    return names.index(name)


def make_leaf(key, name: str, sz: dict):
    """One float32 leaf from the seed's key: weights and embeddings
    N(0, 0.02), LayerNorm gains 1 + N(0, 0.1), biases N(0, 0.02), so that
    no leaf is a constant a fault could hide behind. A per-layer leaf
    comes stacked on a leading layer axis, from one draw. Traceable."""
    kind = (LAYER_LEAVES.get(name) or GLOBAL_LEAVES[name])[1]
    shape = leaf_shape(name, sz)
    if name in LAYER_LEAVES:
        shape = (sz["layers"],) + shape
    k = jax.random.fold_in(key, _name_id(name))
    n = jax.random.normal(k, shape, jnp.float32)
    if kind == "gain":
        return 1.0 + 0.1 * n
    return 0.02 * n


def init_params(key, sz: dict) -> dict:
    """The parameters from the run's key: per-layer leaves
    stacked on a leading layer axis (for ``lax.scan``), global leaves as
    they are. Traceable: call it under ``jax.jit``."""
    return {n: make_leaf(key, n, sz)
            for n in list(GLOBAL_LEAVES) + list(LAYER_LEAVES)}


# -- arithmetic of the linear layers -----------------------------------------


def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)


def _round_operand(x, axis: int, mode: str):
    """``x`` rounded as ``mode`` would store it, scales along ``axis``."""
    if mode == "bf16":
        return _ste(x, x.astype(jnp.bfloat16).astype(jnp.float32))
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.maximum(amax, 1e-30)
    if mode == "int8":
        s = amax / 127.0
        return _ste(x, jnp.clip(jnp.round(x / s), -127, 127) * s)
    if mode == "fp8":
        s = amax / 448.0
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return _ste(x, q)
    raise ValueError(f"unknown mode {mode!r}")


def linear(x, w, b, mode: str):
    """``x @ w + b`` for ``x`` (..., K) and ``w`` (K, N)."""
    if mode != "f32":
        x = _round_operand(x, -1, mode)   # a scale for each token
        w = _round_operand(w, 0, mode)    # a scale for each output channel
    return jnp.matmul(x, w, precision=HI) + b


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def block(x, p, heads: int, eps: float, mode: str):
    """One pre-LayerNorm GPT-2 block on ``x`` (B, T, D)."""
    b, t, d = x.shape
    hd = d // heads
    h = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = linear(h, p["qkv_w"], p["qkv_b"], mode).reshape(b, t, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HI).reshape(b, t, d)
    x = x + linear(o, p["proj_w"], p["proj_b"], mode)
    h = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    h = gelu(linear(h, p["fc_w"], p["fc_b"], mode))
    return x + linear(h, p["out_w"], p["out_b"], mode)


def forward(params: dict, ids, sz: dict, mode: str = "f32",
            remat: bool = False):
    """Logits (B, T, V) in float32 for token ids (B, T)."""
    t = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:t][None]
    layer = functools.partial(block, heads=sz["heads"], eps=sz["eps"],
                              mode=mode)
    if remat:
        layer = jax.checkpoint(layer)

    def body(x, p):
        return layer(x, p), None

    stacked = {n: params[n] for n in LAYER_LEAVES}
    x, _ = jax.lax.scan(body, x, stacked)
    x = layer_norm(x, params["lnf_g"], params["lnf_b"], sz["eps"])
    return linear(x, params["head_w"], params["head_b"], mode)


# -- serving: the gap of served tokens below the reference's best ------------


def served_gaps(params: dict, seq, first: int, n: int, sz: dict,
                mode: str = "f32"):
    """For one request: ``seq`` (1, L) holds prompt + served tokens, padded;
    the served tokens sit at positions ``first .. first + n - 1``. Returns
    per served token: the gap of its reference logit below the reference's
    best at that position, and (for a control) the same gap for the token
    that ``mode`` puts first. Both (Lmax,) arrays, valid for ``i < n``;
    ``seq.shape[1] - 1`` bounds the positions read."""
    ref = forward(params, seq, sz, "f32")[0]
    pos = jnp.clip(first - 1 + jnp.arange(seq.shape[1]), 0, seq.shape[1] - 1)
    at = ref[pos]                                    # logits predicting token i
    toks = seq[0][jnp.clip(pos + 1, 0, seq.shape[1] - 1)]
    best = at.max(-1)
    served = best - jnp.take_along_axis(at, toks[:, None], axis=1)[:, 0]
    if mode == "f32":
        return served, served
    low = forward(params, seq, sz, mode)[0][pos]
    pick = jnp.argmax(low, axis=-1)
    control = best - jnp.take_along_axis(at, pick[:, None], axis=1)[:, 0]
    return served, control


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    """What ``check.served_gaps`` calls for each ``(seq, first, n)``: the
    parameters made whole from ``key`` in one jitted call, and one
    compiled :func:`served_gaps` over them."""
    params = jax.jit(lambda k: init_params(k, sz))(key)
    fn = jax.jit(functools.partial(served_gaps, sz=sz, mode=mode))
    return lambda seq, first, n: fn(params, seq, first, n)


# -- training: the loss, and the grain leaves are compared at -----------------


def loss_sum(params: dict, x, y, sz: dict, mode: str = "f32"):
    """Summed next-token cross-entropy over all tokens of (x, y)."""
    logits = forward(params, x, sz, mode, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def split_leaves(tree: dict) -> dict:
    """Leaves at the grain they are compared at: every layer of a stacked
    leaf apart, and the fused QKV leaves in their three parts (the key
    bias has no gradient under softmax while the query's and the value's
    have). Values are 1-D float64 numpy arrays."""
    out = {}
    for name, leaf in tree.items():
        leaf = np.asarray(leaf, np.float64)
        layers = leaf if name in LAYER_LEAVES else leaf[None]
        for i, row in enumerate(layers):
            tag = f"{name}[{i}]" if name in LAYER_LEAVES else name
            if name.startswith("qkv_"):
                for part, piece in zip("qkv", np.split(row, 3, axis=-1)):
                    out[f"{tag}.{part}"] = piece.reshape(-1)
            else:
                out[tag] = row.reshape(-1)
    return out
