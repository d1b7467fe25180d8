"""The plain reference of the ``mimo_v2_flash`` family: MiMo-V2-Flash's
layer equations in straightforward ``jax.numpy``, float32, every product
at ``precision=HIGHEST``. No kernels, no cache, no batching; nothing of
the program is imported (the ``gpt2`` reference's linear layer and its
rounding modes are, as ``benchmark/README.md`` allows).

The equations, from the published ``config.json``
(huggingface.co/XiaomiMiMo/MiMo-V2-Flash) and the catalog's
``described_as``; ``x`` is the residual stream:

- Layer kinds from ``hybrid_layer_pattern`` (0 full, 1 window) and
  ``moe_layer_freq`` (0 dense FFN, 1 routed).
- Attention of kind k: ``h = RMSNorm(x)`` (a gain, no bias); ``q = h Wq``
  as ``H`` heads of ``head_dim``; ``k = h Wk`` as ``Hk`` heads of
  ``head_dim``; ``v = h Wv`` as ``Hk`` heads of ``v_head_dim``; ``Hk`` is
  ``num_key_value_heads`` (full) or ``swa_num_key_value_heads`` (window);
  no biases. Rotary positions on the first ``int(head_dim *
  partial_rotary_factor)`` dimensions (rounded down to even) of every q
  and k head, half-rotation convention, base ``rope_theta`` (full) or
  ``swa_rope_theta`` (window). ``v`` is multiplied by
  ``attention_value_scale`` before the weighted sum. Scores ``q.k /
  sqrt(head_dim)``, kept where ``j <= i`` and, in window layers, ``i - j
  < sliding_window``. Full layers: plain softmax. Window layers
  (``add_swa_attention_sink_bias``): one learned ``b_h`` a query head
  joins the DENOMINATOR only. ``x += o Wo``.
- Dense FFN: ``x += (silu(h Wg) * (h Wu)) Wd`` on ``h = RMSNorm(x)``.
- Routed FFN: ``z = sigmoid(h Wr)`` over ``E`` experts in float32; the
  chosen set is the ``num_experts_per_tok`` largest of ``z + c`` (``c``
  the selection bias of ``noaux_tc``; ``n_group`` 1, so no group limit);
  weights ``z_e / (sum of the chosen z + 1e-20)`` (``norm_topk_prob``;
  ``routed_scaling_factor`` null = 1); ``x += sum_e w_e SwiGLU_e(h)``; no
  shared expert.
- A final RMSNorm and an untied head without bias.

Departures and assumptions, each under ``assumed`` in the configuration's
file: the half-rotation convention; where the value scale sits; the three
multi-token-prediction layers of ``described_as`` have no key in
``config`` and are left out; weights are random from a seed.

THE CHIP'S SHARE. ``sz["held"] = (first, count)`` says which experts this
holder has: the router keeps its ``E`` outputs and its top ``k``, the
layer adds only what the held experts give (``n_routed_experts`` in a cut
configuration is ``count``, ``published.n_routed_experts`` the router's
width). ``share`` lets a test give every holder's part.

NEAR-TIES. Where the reference's own choice of experts hangs on two
scores closer than ``TIE``, the position is left out of the comparison
(``TIE`` says why, ``route`` how).

``mode`` is the arithmetic of the linear layers (``f32`` is the reference;
``bf16``, ``int8``, ``fp8`` round both operands, the control) or one of
the four PLANTED FAULTS, each the reference with one piece of the
mathematics left out: ``no_sink`` (the sink dropped from the
denominator), ``full_window`` (window layers see the whole context),
``v_unscaled`` (the value scale left out), ``no_bias`` (the selection
bias left out of the choice). ``benchmark/limits.py --modes`` reads each
as it reads the control.

Parameters are made layer by layer from the key (13.7 GB of float32 never
stand at once), as matrices that bfloat16 holds exactly, so storing them
in bfloat16 loses nothing. The sinks are drawn to hold a quarter of a
full window's softmax and the selection bias is set at a size at which leaving either out
fails the cell's limit (``SELECT_BIAS`` says why the bias is a fixed
pattern and no draw).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.references import gpt2 as plain

HI = plain.HI
ROUNDINGS = plain.MODES
FAULTS = ("no_sink", "full_window", "v_unscaled", "no_bias")
MODES = ROUNDINGS + FAULTS

#: how many query heads attention handles at a time: (heads, T, T) scores
#: of 4,096 positions are 1 GiB at 16 heads
HEAD_BLOCK = 16

#: leaf -> (shape from sizes, init kind); ``hk``/``kvk``/``kvv`` are
#: resolved per attention kind
ATTN_LEAVES = {
    "ln1_g": (("d",), "gain"),
    "q_w": (("d", "qd"), "weight"), "k_w": (("d", "kvk"), "weight"),
    "v_w": (("d", "kvv"), "weight"), "o_w": (("od", "d"), "weight"),
    "ln2_g": (("d",), "gain"),
}
SINK_LEAF = {"sink": (("heads",), "sink")}
DENSE_LEAVES = {
    "gate_w": (("d", "f"), "weight"), "up_w": (("d", "f"), "weight"),
    "down_w": (("f", "d"), "weight"),
}
ROUTED_LEAVES = {
    "router_w": (("d", "experts"), "weight"),
    "select_bias": (("experts",), "select_bias"),
    "e_gate_w": (("held_n", "d", "ef"), "weight"),
    "e_up_w": (("held_n", "d", "ef"), "weight"),
    "e_down_w": (("held_n", "ef", "d"), "weight"),
}
GLOBAL_LEAVES = {
    "wte": (("v", "d"), "weight"), "lnf_g": (("d",), "gain"),
    "head_w": (("d", "v"), "weight"),
}
#: the standard deviation every matrix is drawn with, the experts' among
#: them, unless the configuration states an ``initializer_range`` (the CPU
#: tests' narrow models do: at a width of 32 a product of 0.02s is nought
#: to every comparison). Drawn wider, an expert's part of the stream grows
#: with the cube of the width, and a token whose eighth expert program and
#: reference choose differently (bfloat16 products against float32: a few
#: in a hundred tokens) then reads as wrong as a planted fault
WEIGHT_STD = 0.02
#: the selection bias is a FIXED pattern, the same for every seed and
#: layer: +SELECT_BIAS for an expert of even index, -SELECT_BIAS for an
#: odd one. Against sigmoid scores whose top eight lie within a few
#: hundredths of each other it changes one or two of a token's eight
#: experts (leaving it out of the choice fails the cell's limit), and it
#: makes the even experts about 2.5 times as popular as the odd ones. A
#: RANDOM draw of that size would do the same to another set of experts
#: under every seed, the number of held experts that a decode step hits
#: would follow, and with it the step's bytes: the cell's throughput
#: would spread with the seed by more than its bound (PERF.md section 6)
SELECT_BIAS = 0.02
#: two selection scores closer than this are a TIE. Choosing the eight
#: largest is a step: the program's products are bfloat16, its stream
#: differs from this float32 one by some 0.5%, a sigmoid score by some
#: 0.0005, and where the eighth and the ninth score lie closer than that
#: the two choose differently; a token then differs by a held expert's
#: whole part, as much as a planted fault moves it, so one such token in
#: the thousands a run compares would set the widest gap and no limit
#: could tell a sound run from a faulty one. A position whose OWN routing
#: in the reference is such a near-tie in any layer (and touches a held
#: expert: any other changes nothing here) is therefore left out of the
#: comparison, for the served token and the control's alike: about one
#: position in ten (PERF.md section 2 has the share and the readings
#: with and without). Either choice there is the reference's to rounding
TIE = 0.003


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under the short names used here."""
    published = cfg.get("published", {})
    heads, dk, dv = (int(cfg["num_attention_heads"]), int(cfg["head_dim"]),
                     int(cfg["v_head_dim"]))
    held_n = int(cfg["n_routed_experts"])
    experts = int(published.get("n_routed_experts", held_n))
    first = int(cfg.get("deployment_share", {}).get("first_expert", 0))
    rotary = int(dk * float(cfg["partial_rotary_factor"])) // 2 * 2
    kinds = tuple("swa" if k else "full" for k in cfg["hybrid_layer_pattern"])
    ffns = tuple("routed" if k else "dense" for k in cfg["moe_layer_freq"])
    layers = int(cfg["num_hidden_layers"])
    assert len(kinds) == len(ffns) == layers, "patterns and depth disagree"
    return {
        "d": int(cfg["hidden_size"]), "v": int(cfg["vocab_size"]),
        "layers": layers, "kinds": kinds, "ffns": ffns,
        "heads": heads, "dk": dk, "dv": dv, "qd": heads * dk,
        "od": heads * dv,
        "hk": {"full": int(cfg["num_key_value_heads"]),
               "swa": int(cfg["swa_num_key_value_heads"])},
        "theta": {"full": float(cfg["rope_theta"]),
                  "swa": float(cfg["swa_rope_theta"])},
        "sink": {"full": bool(cfg["add_full_attention_sink_bias"]),
                 "swa": bool(cfg["add_swa_attention_sink_bias"])},
        "rotary": rotary, "window": int(cfg["sliding_window"]),
        "value_scale": float(cfg["attention_value_scale"]),
        "f": int(cfg["intermediate_size"]),
        "ef": int(cfg["moe_intermediate_size"]),
        "experts": experts, "held": (first, held_n), "held_n": held_n,
        "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["layernorm_epsilon"]),
        "init_std": float(cfg.get("initializer_range", WEIGHT_STD)),
    }


def layer_leaves(sz: dict, i: int) -> dict:
    """The leaves of layer ``i`` with their shapes and init kinds."""
    kind = sz["kinds"][i]
    dims = dict(sz, kvk=sz["hk"][kind] * sz["dk"],
                kvv=sz["hk"][kind] * sz["dv"])
    leaves = dict(ATTN_LEAVES)
    if sz["sink"][kind]:
        leaves.update(SINK_LEAF)
    leaves.update(ROUTED_LEAVES if sz["ffns"][i] == "routed"
                  else DENSE_LEAVES)
    return {name: (tuple(dims[k] for k in shape), init)
            for name, (shape, init) in leaves.items()}


def _exact(x):
    """``x`` as bfloat16 holds it, in float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def make_leaf(key, shape: tuple, init: str, sz: dict):
    n = jax.random.normal(key, shape, jnp.float32)
    if init == "gain":
        return _exact(1.0 + 0.1 * n)
    if init == "sink":
        # a window's scores of deviation 1.6 sum to some 3.7 a key under
        # the exponential (470 for 128 keys): a sink of ln(window), 5
        # for 128, holds a quarter of a full window's softmax. (One of 1
        # held half a hundredth, and leaving it out read as nothing on
        # the chip: PERF.md section 6)
        return _exact(round(math.log(sz["window"])) + n)
    if init == "select_bias":
        return _exact(SELECT_BIAS * jnp.where(
            jnp.arange(shape[0]) % 2 == 0, 1.0, -1.0))
    return _exact(sz["init_std"] * n)


def _names() -> list:
    return sorted({**ATTN_LEAVES, **SINK_LEAF, **DENSE_LEAVES,
                   **ROUTED_LEAVES, **GLOBAL_LEAVES})


def init_layer(key, sz: dict, i: int) -> dict:
    """Layer ``i``'s leaves from the run's key. Traceable."""
    key = jax.random.fold_in(key, 1000 + i)
    names = _names()
    return {name: make_leaf(jax.random.fold_in(key, names.index(name)),
                            shape, init, sz)
            for name, (shape, init) in layer_leaves(sz, i).items()}


def init_globals(key, sz: dict) -> dict:
    names = _names()
    return {name: make_leaf(jax.random.fold_in(key, names.index(name)),
                            tuple(sz[k] for k in shape), init, sz)
            for name, (shape, init) in GLOBAL_LEAVES.items()}


def init_params(key, sz: dict) -> dict:
    """Every parameter from the run's key: ``{"globals": {...}, "layers":
    [{...}, ...]}``. Traceable; under one ``jax.jit`` with the adapter
    (which casts to the stored width) no float32 copy of the whole model
    is ever made."""
    return {"globals": init_globals(key, sz),
            "layers": [init_layer(key, sz, i) for i in range(sz["layers"])]}


# -- the equations -------------------------------------------------------------


def linear(x, w, b, mode: str):
    """The ``gpt2`` reference's linear layer; a planted fault leaves the
    arithmetic as the reference's own."""
    return plain.linear(x, w, b, mode if mode in ROUNDINGS else "f32")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float, rotary: int):
    """Half-rotation over the first ``rotary`` dimensions of every head of
    ``x`` (B, T, H, D), positions 0 .. T-1; the rest pass."""
    t, half = x.shape[1], rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest), -1)


def attention(h, p, sz: dict, kind: str, mode: str):
    b, t, _ = h.shape
    heads, hk, dk, dv = sz["heads"], sz["hk"][kind], sz["dk"], sz["dv"]
    group = heads // hk
    zero = jnp.zeros((), jnp.float32)
    q = linear(h, p["q_w"], zero, mode).reshape(b, t, heads, dk)
    k = linear(h, p["k_w"], zero, mode).reshape(b, t, hk, dk)
    v = linear(h, p["v_w"], zero, mode).reshape(b, t, hk, dv)
    if mode != "v_unscaled":
        v = v * sz["value_scale"]
    q = rope(q, sz["theta"][kind], sz["rotary"])
    k = rope(k, sz["theta"][kind], sz["rotary"])
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    keep = j <= i
    if kind == "swa" and mode != "full_window":
        keep = keep & (i - j < sz["window"])
    sink = p.get("sink") if mode != "no_sink" else None
    outs = []
    # in blocks of query heads, so that (heads, T, T) scores fit
    for lo in range(0, heads, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, heads)
        kv = jnp.arange(lo, hi) // group
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, lo:hi], k[:, :, kv],
                       precision=HI) / jnp.sqrt(jnp.float32(dk))
        s = jnp.where(keep[None, None], s, -jnp.inf)
        m = s.max(axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[lo:hi][None, :, None, None])
        e = jnp.exp(s - m)
        denom = e.sum(axis=-1, keepdims=True)
        if sink is not None:
            denom = denom + jnp.exp(sink[lo:hi][None, :, None, None] - m)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", e / denom, v[:, :, kv],
                               precision=HI))
    o = jnp.concatenate(outs, axis=2).reshape(b, t, heads * dv)
    return linear(o, p["o_w"], zero, mode)


def swiglu(h, gate_w, up_w, down_w, mode: str):
    zero = jnp.zeros((), jnp.float32)
    mid = jax.nn.silu(linear(h, gate_w, zero, mode)) * linear(
        h, up_w, zero, mode)
    return linear(mid, down_w, zero, mode)


def route(h, p, sz: dict, mode: str):
    """The chosen experts (B, T, k), their weights, and where the choice
    is a NEAR-TIE (B, T): the last expert chosen and the first left out
    lie within ``TIE`` of each other and one of them is held here. All in
    float32 whatever the mode: a choice is a step, not a rounding."""
    k = sz["top_k"]
    z = jax.nn.sigmoid(jnp.matmul(h, p["router_w"], precision=HI))
    choice = z if mode == "no_bias" else z + p["select_bias"]
    top, experts = jax.lax.top_k(choice, k + 1)
    first, count = sz["held"]
    edge = experts[..., k - 1:]
    near = (top[..., k - 1] - top[..., k] < TIE) & (
        (edge >= first) & (edge < first + count)).any(-1)
    experts = experts[..., :k]
    chosen = jnp.take_along_axis(z, experts, axis=-1)
    return experts, chosen / (chosen.sum(-1, keepdims=True) + 1e-20), near


def routed_ffn(h, p, sz: dict, mode: str, share: tuple | None = None):
    """What the experts ``share = (first, count)`` (default: the held
    ones) add: every one of them over every token, weighted by the
    router's weight where the token chose it and by nought elsewhere.
    With it, where the choice was a near-tie (:func:`route`)."""
    first, count = share or sz["held"]
    experts, weights, near = route(h, p, sz, mode)
    out = jnp.zeros_like(h)
    for e in range(count):
        w = jnp.where(experts == first + e, weights, 0.0).sum(-1)
        out = out + w[..., None] * swiglu(
            h, p["e_gate_w"][e], p["e_up_w"][e], p["e_down_w"][e], mode)
    return out, near


def block(x, p, sz: dict, i: int, mode: str):
    """Layer ``i`` over the stream, and the positions (B, T) whose
    routing in it was a near-tie (none in a dense layer)."""
    kind = sz["kinds"][i]
    x = x + attention(rms_norm(x, p["ln1_g"], sz["eps"]), p, sz, kind, mode)
    h = rms_norm(x, p["ln2_g"], sz["eps"])
    if sz["ffns"][i] == "routed":
        out, near = routed_ffn(h, p, sz, mode)
        return x + out, near
    return (x + swiglu(h, p["gate_w"], p["up_w"], p["down_w"], mode),
            jnp.zeros(x.shape[:2], bool))


def head(x, g: dict, sz: dict, mode: str):
    x = rms_norm(x, g["lnf_g"], sz["eps"])
    return linear(x, g["head_w"], jnp.zeros((), jnp.float32), mode)


def forward(params: dict, ids, sz: dict, mode: str = "f32"):
    """Logits (B, T, V) in float32 for token ids (B, T), from whole
    parameters (the CPU tests' sizes)."""
    x = params["globals"]["wte"][ids]
    for i, p in enumerate(params["layers"]):
        x, _ = block(x, p, sz, i, mode)
    return head(x, params["globals"], sz, mode)


# -- serving: the gap of served tokens below the reference's best --------------


def _gaps(ref, low, seq, first: int, unsure):
    last = seq.shape[1] - 1
    pos = jnp.clip(first - 1 + jnp.arange(seq.shape[1]), 0, last)
    at = ref[pos]
    toks = seq[0][jnp.clip(pos + 1, 0, last)]
    best = at.max(-1)
    sure = ~unsure[pos]

    def below(picked):
        gap = best - jnp.take_along_axis(at, picked[:, None], axis=1)[:, 0]
        return jnp.where(sure, gap, 0.0)

    served = below(toks)
    if low is None:
        return served, served
    return served, below(jnp.argmax(low[pos], axis=-1))


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    """What ``check.served_gaps`` calls for each ``(seq, first, n)``. The
    parameters are made layer by layer from ``key`` as each layer runs,
    and a layer's program is compiled once for each of its kinds."""
    glob = jax.jit(lambda k: init_globals(k, sz))(key)

    @functools.partial(jax.jit, static_argnames=("i", "m"))
    def layer(x, k, i, m):
        return block(x, init_layer(k, sz, i), sz, i, m)

    embed = jax.jit(lambda g, seq: g["wte"][seq])
    logits = jax.jit(lambda x, g, m: head(x, g, sz, m)[0],
                     static_argnames=("m",))
    gaps = jax.jit(_gaps)

    def run(seq, m):
        x = embed(glob, seq)
        unsure = jnp.zeros(seq.shape[1], bool)
        for i in range(sz["layers"]):
            x, near = layer(x, key, i, m)
            unsure = unsure | near[0]
        return logits(x, glob, m), unsure

    def fn(seq, first, n):
        ref, unsure = run(seq, "f32")
        low = None if mode == "f32" else run(seq, mode)[0]
        return gaps(ref, low, seq, first, unsure)

    return fn
