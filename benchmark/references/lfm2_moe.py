"""The plain reference of the ``lfm2_moe`` family (the ``model_type`` of
LiquidAI/LFM2-8B-A1B): its layer equations in straightforward
``jax.numpy``, float32, every product at ``precision=HIGHEST``. No kernels,
no cache, no state carried from step to step; nothing of the program is
imported (the ``gpt2`` reference's linear layer and rounding modes are, and
the ``mimo_v2_flash`` reference's arithmetic where it is the same: the
norm, SwiGLU, the half-rotation, the head, how a leaf is drawn and how
served tokens are held against logits).

The equations, from the published ``config.json`` and the catalog's
``described_as``; ``x`` is the float32 residual stream, ``RMSNorm`` has a
gain and no bias, eps ``norm_eps``. Every layer: ``x += Op(RMSNorm(x))``,
then ``x += FFN(RMSNorm(x))``.

- Conv operator (``layer_types[i] == "conv"``; ``conv_bias`` false, ``K =
  conv_L_cache``), ``h`` the normed stream: ``[B_t ; C_t ; u_t] = h_t
  W_in`` (``d -> 3d``, in that order); ``g_t = B_t * u_t``; ``c_t = sum_j
  w_j * g_{t - (K - 1) + j}`` for ``j = 0 .. K - 1``, ``g`` nought before
  position 0 and ``w`` the depthwise filter ``(d, K)`` (a
  cross-correlation: ``w_{K-1}`` multiplies the current position); ``y_t =
  C_t * c_t``; ``Op = y_t W_out``. No activation, no bias. The filter is
  written as an explicit sum over shifted copies of ``g``.
- Attention operator (``"full_attention"``): ``q = h W_q`` as ``H`` heads
  of ``hidden_size / H``, ``k = h W_k``, ``v = h W_v`` as
  ``num_key_value_heads`` heads; no biases; an RMSNorm over each query and
  each key head's numbers (one gain for all heads of a kind), then rotary
  positions on all of a head's dimensions, half-rotation, base
  ``rope_theta``; scores over ``sqrt(head)``, causal, plain softmax, each
  KV head serving its group of query heads; ``Op = concat(o_h) W_o``.
- Dense FFN (the first ``num_dense_layers`` layers): ``(silu(h W_1) * (h
  W_3)) W_2``, ``intermediate_size`` wide.
- Routed FFN (the others): ``z = sigmoid(h W_r)`` over ``num_experts``
  experts in float32; the chosen set is the ``num_experts_per_tok``
  largest of ``z + b`` (``use_expert_bias``); weights
  ``routed_scaling_factor * z_e / (sum of the chosen z + 1e-6)``
  (``norm_topk_prob``); the bias enters no weight; ``FFN = sum_e w_e
  SwiGLU_e(h)``, experts ``moe_intermediate_size`` wide, no shared expert.
- A final RMSNorm and an untied head without bias.

ALL experts are held here (``sz["held"] = (0, num_experts)``): nothing of
a layer is left out. ``share`` lets a test give every holder's part of a
deployment that divides them. NEAR-TIES are left out by the
``deepseek_v3`` reference's rule, which sees every held expert, so here
every expert (:data:`TIE`).

``mode`` is the arithmetic of the linear layers (``f32``; ``bf16``,
``int8``, ``fp8`` round both operands: the control) or one of the five
PLANTED FAULTS, each the reference with one piece left out:
``state_zero`` (the filter sees no earlier position: what a decode step
that ignores its carried rows computes), ``taps_reversed`` (``w_j`` read
as ``w_{K-1-j}``), ``no_b_gate`` (``g = u``), ``no_qk_norm`` (the heads'
norms left out), ``no_bias`` (the selection bias left out of the choice).

Parameters are made layer by layer from the key, every number one that
bfloat16 holds exactly (the ``mimo_v2_flash`` reference says why and how).
The filter's taps are N(0, 0.5) each: all three then carry a comparable
part of ``c``, so a state left out or the taps in another order moves a
token as far as any other fault.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references import gpt2 as plain
from benchmark.references import mimo_v2_flash as mimo

HI = plain.HI
ROUNDINGS = plain.MODES
FAULTS = ("state_zero", "taps_reversed", "no_b_gate", "no_qk_norm",
          "no_bias")
MODES = ROUNDINGS + FAULTS

#: how many query heads attention handles at a time: (heads, T, T) scores
#: of 4,096 positions are 1 GiB at 16 heads
HEAD_BLOCK = mimo.HEAD_BLOCK

CONV_LEAVES = {
    "ln1_g": (("d",), "gain"),
    "in_w": (("d", "3d"), "weight"), "taps": (("d", "K"), "taps"),
    "out_w": (("d", "d"), "weight"),
    "ln2_g": (("d",), "gain"),
}
ATTN_LEAVES = {
    "ln1_g": (("d",), "gain"),
    "q_w": (("d", "qd"), "weight"), "k_w": (("d", "kvd"), "weight"),
    "v_w": (("d", "kvd"), "weight"),
    "qn_g": (("dk",), "gain"), "kn_g": (("dk",), "gain"),
    "o_w": (("qd", "d"), "weight"),
    "ln2_g": (("d",), "gain"),
}
OPERATOR_LEAVES = {"conv": CONV_LEAVES, "full": ATTN_LEAVES}
DENSE_LEAVES = mimo.DENSE_LEAVES
ROUTED_LEAVES = mimo.ROUTED_LEAVES
GLOBAL_LEAVES = mimo.GLOBAL_LEAVES
WEIGHT_STD = mimo.WEIGHT_STD
#: the deviation of every tap of the filter: with ``g`` of deviation 0.8
#: the three taps' sum then has one near 0.7 and the operator's output is
#: of the size an attention's is
TAPS_STD = 0.5
#: the router's sum of chosen scores has this added, as published
ROUTE_EPS = 1e-6
#: a selection score closer than this to the far side of the choice is a
#: TIE, by the ``deepseek_v3`` reference's rule (its ``TIE`` says why a
#: rule has to see every held expert): a position where, in any layer of
#: the reference's OWN routing, an expert is chosen within ``TIE`` of the
#: first one left out, or left out within ``TIE`` of the last one chosen,
#: is left out of the comparison. With all 32 experts held that is: the
#: fourth and the fifth selection score lie within ``TIE``, which with ten
#: routed layers is most positions: 97.7% at 0.006, 99.7% at 0.012, all
#: at 0.024 (``tools/route_tie_readings.py`` on the chip at the cell's
#: size, my chip run, PR 35, two seeds of 8,565 and 8,649 served tokens).
#: The widest gap of a sound run's kept tokens read 0.418 and 0.459 at
#: 0.006 and 0.369 and 0.316 at 0.012: the rule does not take the gap
#: away here as it does for an attention-only stack, because a short
#: convolution hands a neighbour's flipped expert on to the next two
#: positions at a third of its weight each, near-tie or not. The float8
#: control reads 2.30 and 1.83 at 0.006 (1.50 and 1.67 at 0.012), a state
#: left out 6.5, so 0.006 it stays: wider keeps too few tokens to compare
#: (22 of 8,565 at 0.012), narrower was not read (PERF.md section 7)
TIE = 0.006


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under the short names used here."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hk, dk = int(cfg["num_key_value_heads"]), d // heads
    layers = int(cfg["num_hidden_layers"])
    kinds = tuple("conv" if k == "conv" else "full"
                  for k in cfg["layer_types"])
    assert len(kinds) == layers, "layer_types and depth disagree"
    assert not cfg.get("conv_bias"), "the filter has no bias here"
    assert cfg.get("norm_topk_prob", True) and cfg.get("use_expert_bias")
    dense = int(cfg["num_dense_layers"])
    experts = int(cfg["num_experts"])
    return {
        "d": d, "3d": 3 * d, "v": int(cfg["vocab_size"]), "layers": layers,
        "kinds": kinds,
        "ffns": tuple("dense" if i < dense else "routed"
                      for i in range(layers)),
        "heads": heads, "hk": hk, "dk": dk, "qd": heads * dk,
        "kvd": hk * dk, "theta": float(cfg["rope_theta"]),
        "K": int(cfg["conv_L_cache"]),
        "f": int(cfg["intermediate_size"]),
        "ef": int(cfg["moe_intermediate_size"]),
        "experts": experts, "held": (0, experts), "held_n": experts,
        "top_k": int(cfg["num_experts_per_tok"]),
        "route_scale": float(cfg.get("routed_scaling_factor") or 1.0),
        "eps": float(cfg["norm_eps"]),
        "init_std": float(cfg.get("initializer_range", WEIGHT_STD)),
        "tie": TIE,
    }


def layer_leaves(sz: dict, kind: str, ffn: str) -> dict:
    """The leaves of a layer whose operator is of ``kind`` and whose FFN of
    ``ffn``, with their shapes and init kinds."""
    leaves = dict(OPERATOR_LEAVES[kind])
    leaves.update(DENSE_LEAVES if ffn == "dense" else ROUTED_LEAVES)
    return {name: (tuple(sz[k] for k in shape), init)
            for name, (shape, init) in leaves.items()}


def _names() -> list:
    return sorted({**CONV_LEAVES, **ATTN_LEAVES, **DENSE_LEAVES,
                   **ROUTED_LEAVES, **GLOBAL_LEAVES})


def make_leaf(key, shape: tuple, init: str, sz: dict):
    """The ``mimo_v2_flash`` reference's draw, and the filter's taps at
    their own deviation (:data:`TAPS_STD`)."""
    if init == "taps":
        return mimo._exact(
            TAPS_STD * jax.random.normal(key, shape, jnp.float32))
    return mimo.make_leaf(key, shape, init, sz)


def _make(key, leaves: dict, sz: dict) -> dict:
    names = _names()
    return {name: make_leaf(jax.random.fold_in(key, names.index(name)),
                            shape, init, sz)
            for name, (shape, init) in leaves.items()}


def init_layer(key, sz: dict, i, kind: str | None = None,
               ffn: str | None = None) -> dict:
    """Layer ``i``'s leaves from the run's key. Traceable, in ``i`` too
    where the layer's kinds are given."""
    return _make(jax.random.fold_in(key, 1000 + i),
                 layer_leaves(sz, kind or sz["kinds"][i],
                              ffn or sz["ffns"][i]), sz)


def init_globals(key, sz: dict) -> dict:
    return _make(key, {name: (tuple(sz[k] for k in shape), init)
                       for name, (shape, init) in GLOBAL_LEAVES.items()}, sz)


def init_params(key, sz: dict) -> dict:
    """Every parameter from the run's key: ``{"globals": {...}, "layers":
    [{...}, ...]}``. Traceable."""
    return {"globals": init_globals(key, sz),
            "layers": [init_layer(key, sz, i) for i in range(sz["layers"])]}


# -- the equations -------------------------------------------------------------


def linear(x, w, mode: str):
    """The ``gpt2`` reference's linear layer without a bias; a planted
    fault leaves the arithmetic as the reference's own."""
    return plain.linear(x, w, jnp.zeros((), jnp.float32),
                        mode if mode in ROUNDINGS else "f32")


def short_conv(h, p, sz: dict, mode: str):
    """The gated short convolution over ``h`` (B, T, d): the filter as an
    explicit sum over shifted copies of its input."""
    d, k, t = sz["d"], sz["K"], h.shape[1]
    proj = linear(h, p["in_w"], mode)
    b_gate, c_gate, u = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    g = u if mode == "no_b_gate" else b_gate * u
    taps = p["taps"][:, ::-1] if mode == "taps_reversed" else p["taps"]
    conv = jnp.zeros_like(g)
    for j in range(k):
        back = k - 1 - j           # tap j sees the position ``back`` before
        if back and mode == "state_zero":
            continue
        shifted = jnp.pad(g, ((0, 0), (back, 0), (0, 0)))[:, :t]
        conv = conv + taps[:, j] * shifted
    return linear(c_gate * conv, p["out_w"], mode)


def attention(h, p, sz: dict, mode: str):
    b, t, _ = h.shape
    heads, hk, dk = sz["heads"], sz["hk"], sz["dk"]
    group = heads // hk
    q = linear(h, p["q_w"], mode).reshape(b, t, heads, dk)
    k = linear(h, p["k_w"], mode).reshape(b, t, hk, dk)
    v = linear(h, p["v_w"], mode).reshape(b, t, hk, dk)
    if mode != "no_qk_norm":
        q = mimo.rms_norm(q, p["qn_g"], sz["eps"])
        k = mimo.rms_norm(k, p["kn_g"], sz["eps"])
    q = mimo.rope(q, sz["theta"], dk)
    k = mimo.rope(k, sz["theta"], dk)
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    outs = []
    # in blocks of query heads, so that (heads, T, T) scores fit
    for lo in range(0, heads, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, heads)
        kv = jnp.arange(lo, hi) // group
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, lo:hi], k[:, :, kv],
                       precision=HI) / jnp.sqrt(jnp.float32(dk))
        s = jnp.where(keep[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                               v[:, :, kv], precision=HI))
    o = jnp.concatenate(outs, axis=2).reshape(b, t, heads * dk)
    return linear(o, p["o_w"], mode)


def route(h, p, sz: dict, mode: str):
    """The chosen experts (B, T, k), their weights, and where the choice
    is a NEAR-TIE (B, T): an expert held here is chosen within
    ``sz["tie"]`` (:data:`TIE`) of the first one left out, or left out
    within it of the last one chosen. All in float32 whatever the mode: a
    choice is a step, not a rounding."""
    k = sz["top_k"]
    z = jax.nn.sigmoid(jnp.matmul(h, p["router_w"], precision=HI))
    scores = z if mode == "no_bias" else z + p["select_bias"]
    top, experts = jax.lax.top_k(scores, k + 1)
    first, count = sz["held"]
    last_in, first_out = top[..., k - 1:k], top[..., k:]
    held = scores[..., first:first + count]
    near = (jnp.where(held >= last_in, held - first_out, last_in - held)
            < sz["tie"]).any(-1)
    experts = experts[..., :k]
    chosen = jnp.take_along_axis(z, experts, axis=-1)
    return (experts, sz["route_scale"] * chosen
            / (chosen.sum(-1, keepdims=True) + ROUTE_EPS), near)


def routed_ffn(h, p, sz: dict, mode: str, share: tuple | None = None):
    """What the experts ``share = (first, count)`` (default: all of them)
    add: every one of them over every token, weighted by the router's
    weight where the token chose it and by nought elsewhere. With it,
    where the choice was a near-tie (:func:`route`)."""
    first, count = share or sz["held"]
    experts, weights, near = route(h, p, sz, mode)
    out = jnp.zeros_like(h)
    for e in range(first, first + count):
        w = jnp.where(experts == e, weights, 0.0).sum(-1)
        out = out + w[..., None] * mimo.swiglu(
            h, p["e_gate_w"][e], p["e_up_w"][e], p["e_down_w"][e], mode)
    return out, near


def block(x, p, sz: dict, kind: str, ffn: str, mode: str):
    """A layer whose operator is of ``kind`` and whose FFN of ``ffn`` over
    the stream, and the positions (B, T) whose routing in it was a
    near-tie (none in a dense layer)."""
    h = mimo.rms_norm(x, p["ln1_g"], sz["eps"])
    x = x + (short_conv(h, p, sz, mode) if kind == "conv"
             else attention(h, p, sz, mode))
    h = mimo.rms_norm(x, p["ln2_g"], sz["eps"])
    if ffn == "routed":
        out, near = routed_ffn(h, p, sz, mode)
        return x + out, near
    return (x + mimo.swiglu(h, p["gate_w"], p["up_w"], p["down_w"], mode),
            jnp.zeros(x.shape[:2], bool))


head = mimo.head


def forward(params: dict, ids, sz: dict, mode: str = "f32"):
    """Logits (B, T, V) in float32 for token ids (B, T), from whole
    parameters (the CPU tests' sizes)."""
    x = params["globals"]["wte"][ids]
    for kind, ffn, p in zip(sz["kinds"], sz["ffns"], params["layers"]):
        x, _ = block(x, p, sz, kind, ffn, mode)
    return head(x, params["globals"], sz, mode)


# -- serving: the gap of served tokens below the reference's best --------------


def _frozen(sz: dict) -> tuple:
    return tuple(sorted(sz.items()))


# jitted here, not inside served_gaps_fn, and keyed by the sizes: the
# control and every planted fault then share the float32 pass's programs
@functools.partial(jax.jit, static_argnames=("sizes",))
def _globals(key, sizes):
    return init_globals(key, dict(sizes))


@functools.partial(jax.jit, static_argnames=("kind", "ffn", "m", "sizes"))
def _layer(x, key, i, kind, ffn, m, sizes):
    sz = dict(sizes)
    return block(x, init_layer(key, sz, i, kind, ffn), sz, kind, ffn, m)


@functools.partial(jax.jit, static_argnames=("m", "sizes"))
def _logits(x, g, m, sizes):
    return head(x, g, dict(sizes), m)[0]


_gaps = jax.jit(mimo._gaps)


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    """What ``check.served_gaps`` calls for each ``(seq, first, n)``. The
    parameters are made layer by layer from ``key`` as each layer runs,
    and a layer's program is compiled once for each pair of kinds and mode
    (the layer's number is traced)."""
    sizes = _frozen(sz)
    glob = _globals(key, sizes)

    def run(seq, m):
        x = glob["wte"][seq]
        unsure = jnp.zeros(seq.shape[1], bool)
        for i, (kind, ffn) in enumerate(zip(sz["kinds"], sz["ffns"])):
            x, near = _layer(x, key, i, kind, ffn, m, sizes)
            unsure = unsure | near[0]
        return _logits(x, glob, m, sizes), unsure

    def fn(seq, first, n):
        ref, unsure = run(seq, "f32")
        low = None if mode == "f32" else run(seq, mode)[0]
        return _gaps(ref, low, seq, first, unsure)

    return fn
