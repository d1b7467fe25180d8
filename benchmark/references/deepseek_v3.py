"""The plain reference of the ``deepseek_v3`` family (the ``model_type`` of
kakaocorp/kanana-2-30b-a3b-instruct-2601): its layer equations in
straightforward ``jax.numpy``, float32, every product at
``precision=HIGHEST``. No kernels, no cache, the attention NOT absorbed;
nothing of the program is imported (the ``gpt2`` reference's linear layer
and rounding modes are, and the ``mimo_v2_flash`` reference's arithmetic
where it is the same: the norm, SwiGLU, the head, how a leaf is drawn and
how served tokens are held against logits).

The equations, from the published ``config.json`` and the catalog's
``described_as``; ``x`` is the float32 residual stream, ``h = RMSNorm(x)``
(a gain, no bias), ``H`` the number of heads:

- Attention, every layer (``q_lora_rank`` null: the query has no low
  rank). ``q = h W_q`` as ``H`` heads ``[q_nope (qk_nope_head_dim) ;
  q_rope (qk_rope_head_dim)]``. ``[c_raw (kv_lora_rank) ; k_rope] = h
  W_kva``; ``c = RMSNorm(c_raw)``; ``k_rope`` is ONE head that all query
  heads share. ``[k_nope_h ; v_h (v_head_dim)] = c W_kvb`` for each head.
  Rotary positions on ``q_rope`` and ``k_rope`` alone, base
  ``rope_theta``, no scaling, pairs ``(2i, 2i+1)`` rotated by ``pos *
  base^(-2i / qk_rope_head_dim)`` (``rope_interleave``). ``k_h =
  [k_nope_h ; k_rope]``, scores ``q_h . k_h / sqrt(qk_head_dim)``, causal,
  plain softmax, ``o_h = sum p v_h``, ``x += concat(o_h) W_o``. No biases.
- Dense FFN (the first ``first_k_dense_replace`` layers): ``x += (silu(h
  W_g) * (h W_u)) W_d``.
- Routed FFN (the others): ``z = sigmoid(h W_r)`` over ``E`` experts in
  float32; the chosen set is the ``num_experts_per_tok`` largest of ``z +
  c_bias`` (``noaux_tc``, one group: no group limit); weights
  ``routed_scaling_factor * z_e / (sum of the chosen z + 1e-20)``; the
  bias enters no weight. ``x += sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)``,
  the shared one ``n_shared_experts * moe_intermediate_size`` wide, always
  on, unweighted.
- A final RMSNorm and an untied head without bias.

The program's decode step computes the same attention ABSORBED (``W_kvb``'s
key half folded into the query, its value half applied after the sum over
the cached ``[c ; k_rope]`` rows); this reference does not, so the
comparison tests that identity.

THE CHIP'S SHARE and how parameters are made: as the ``mimo_v2_flash``
reference's docstring sets out (``sz["held"]``, ``share``; layer by layer
from the key, every number one that bfloat16 holds exactly). NEAR-TIES are
left out as there, by a rule that sees every held expert (:data:`TIE`).

``mode`` is the arithmetic of the linear layers (``f32``; ``bf16``,
``int8``, ``fp8`` round both operands: the control) or one of the five
PLANTED FAULTS, each the reference with one piece left out:
``no_rope_key`` (the shared rotary key dropped from the scores),
``no_latent_norm`` (``c = c_raw``), ``scale_128`` (scores over
``sqrt(qk_nope_head_dim)``), ``no_shared`` (the shared expert left out),
``unscaled_route`` (``routed_scaling_factor`` read as 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references import gpt2 as plain
from benchmark.references import mimo_v2_flash as mimo

HI = plain.HI
ROUNDINGS = plain.MODES
FAULTS = ("no_rope_key", "no_latent_norm", "scale_128", "no_shared",
          "unscaled_route")
MODES = ROUNDINGS + FAULTS

#: how many query positions attention handles at a time: (H, rows, T)
#: scores of 8,192 keys are 1 GiB at 32 heads and 1,024 rows
QUERY_BLOCK = 1024

ATTN_LEAVES = {
    "ln1_g": (("d",), "gain"),
    "q_w": (("d", "qd"), "weight"), "kva_w": (("d", "ad"), "weight"),
    "kvn_g": (("rank",), "gain"), "kvb_w": (("rank", "bd"), "weight"),
    "o_w": (("od", "d"), "weight"),
    "ln2_g": (("d",), "gain"),
}
DENSE_LEAVES = mimo.DENSE_LEAVES
ROUTED_LEAVES = dict(mimo.ROUTED_LEAVES, **{
    # drawn narrower by the routing scale (make_leaf says why)
    "e_down_w": (("held_n", "ef", "d"), "routed_out"),
    "s_gate_w": (("d", "sf"), "weight"), "s_up_w": (("d", "sf"), "weight"),
    "s_down_w": (("sf", "d"), "weight"),
})
GLOBAL_LEAVES = mimo.GLOBAL_LEAVES
WEIGHT_STD = mimo.WEIGHT_STD
#: a selection score closer than this to the far side of the choice is a
#: TIE: a position where, in any layer of the reference's OWN routing, an
#: expert HELD here is chosen but lies within ``TIE`` of the first expert
#: left out, or is left out but lies within ``TIE`` of the last one chosen,
#: is left out of the comparison (the ``mimo_v2_flash`` reference's ``TIE``
#: says why: either choice there is the reference's to rounding, and the
#: token differs by a held expert's WHOLE part). That reference asks only
#: whether the last chosen and the first left out lie so close and one of
#: THEM is held: a held expert one place further from the edge, with both
#: its neighbours within the noise, flips as well, and that rule is blind
#: to it at any width. This cell, by that rule, read one served token in
#: some 10,000 at 0.2 to 0.55 (first weights), at 0.012 and 0.024 alike.
#: ``tools/latent_witness.py`` asked the program for such a token again
#: WITHOUT its pool (my chip run, PR 33): expanded and absorbed it puts
#: the reference's best first and the served token 0.45 below, as the
#: reference does (0.445), so the token was moved on the serving path: at
#: that one position (no later token of the request moved, as a row
#: written or read wrong would have made them), by one expert's part. A
#: held expert off the pair is what fits: every layer's pair there chooses
#: as the reference does, five pairs lie within 0.0035 with neither expert
#: held, the width of 0.048 that lost the token did so by a sixth layer's
#: pair at 0.0346, and a noise model on the CPU gives the pair's rule 3
#: such flips in 10,000 kept positions at 0.012 and this rule none at
#: 0.006. That position's held scores were not kept, so it is not SHOWN
#: (PERF.md section 7). The width follows the noise of a score between
#: neighbours, program (bfloat16 products) against reference, read on the
#: chip at the cell's size with the committed weights
#: (``tools/route_tie_readings.py``, two seeds of 12,030 and 11,648 served
#: tokens, by the pair's rule): widest gap 0.153 and 0.166 at 0.003 (one
#: token over 0.1 each), 0.064 and 0.083 at 0.006 and at 0.012 alike. The
#: flips end between 0.003 and 0.006, and 0.006 it is: 71% of positions
#: are left out (CPU, real widths, 384 positions; by the pair's rule at
#: 0.012, as this cell was first read: 81%)
TIE = 0.006


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under the short names used here."""
    published = cfg.get("published", {})
    heads = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    rank = int(cfg["kv_lora_rank"])
    assert cfg.get("q_lora_rank") is None, "the query has no low rank here"
    assert int(cfg["n_group"]) == 1 and int(cfg["moe_layer_freq"]) == 1
    held_n = int(cfg["n_routed_experts"])
    layers = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    ef = int(cfg["moe_intermediate_size"])
    return {
        "d": int(cfg["hidden_size"]), "v": int(cfg["vocab_size"]),
        "layers": layers,
        "ffns": tuple("dense" if i < dense else "routed"
                      for i in range(layers)),
        "heads": heads, "dn": dn, "dr": dr, "dv": dv, "rank": rank,
        "qd": heads * (dn + dr), "ad": rank + dr, "bd": heads * (dn + dv),
        "od": heads * dv, "theta": float(cfg["rope_theta"]),
        "interleave": bool(cfg["rope_interleave"]),
        "f": int(cfg["intermediate_size"]), "ef": ef,
        "sf": int(cfg["n_shared_experts"] or 0) * ef,
        "experts": int(published.get("n_routed_experts", held_n)),
        "held": (int(cfg.get("deployment_share", {}).get("first_expert", 0)),
                 held_n),
        "held_n": held_n, "top_k": int(cfg["num_experts_per_tok"]),
        "route_scale": float(cfg["routed_scaling_factor"] or 1.0),
        "eps": float(cfg["rms_norm_eps"]),
        "init_std": float(cfg.get("initializer_range", WEIGHT_STD)),
        "tie": TIE,
    }


def layer_leaves(sz: dict, kind: str) -> dict:
    """The leaves of a layer whose FFN is of ``kind``, with their shapes
    and init kinds."""
    leaves = dict(ATTN_LEAVES)
    if kind == "dense":
        leaves.update(DENSE_LEAVES)
    else:
        leaves.update(ROUTED_LEAVES)
        if not sz["sf"]:
            leaves = {k: v for k, v in leaves.items()
                      if not k.startswith("s_")}
    return {name: (tuple(sz[k] for k in shape), init)
            for name, (shape, init) in leaves.items()}


def _names() -> list:
    return sorted({**ATTN_LEAVES, **DENSE_LEAVES, **ROUTED_LEAVES,
                   **GLOBAL_LEAVES})


def make_leaf(key, shape: tuple, init: str, sz: dict):
    """The ``mimo_v2_flash`` reference's draw, but for the routed experts'
    output matrices: N(0, ``init_std / routed_scaling_factor``). A trained
    model has learned its experts under the factor; random experts at the
    common deviation, their sum then multiplied by 2.448, make ONE expert's
    part an eighth of a token's stream, and the one served token in some
    10,000 where program and reference choose another held expert (no
    near-tie of the boundary pair at any width: :data:`TIE`) then read
    0.45 and 0.55 on the chip
    where the float8 control reads 0.86: no limit on the widest gap had
    room on both sides (PR 29's lesson again; PERF.md section 6, PR 33)."""
    if init == "routed_out":
        n = jax.random.normal(key, shape, jnp.float32)
        return mimo._exact(sz["init_std"] / sz["route_scale"] * n)
    return mimo.make_leaf(key, shape, init, sz)


def _make(key, leaves: dict, sz: dict) -> dict:
    names = _names()
    return {name: make_leaf(jax.random.fold_in(key, names.index(name)),
                            shape, init, sz)
            for name, (shape, init) in leaves.items()}


def init_layer(key, sz: dict, i, kind: str | None = None) -> dict:
    """Layer ``i``'s leaves from the run's key. Traceable, in ``i`` too
    where the layer's ``kind`` is given."""
    return _make(jax.random.fold_in(key, 1000 + i),
                 layer_leaves(sz, kind or sz["ffns"][i]), sz)


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


def rope(x, theta: float, interleave: bool):
    """Rotary positions 0 .. T-1 over every dimension of every head of
    ``x`` (B, T, H, D): pairs ``(2i, 2i+1)`` where ``interleave``, else
    ``(i, i + D/2)``, each rotated by ``pos * theta^(-2i / D)``."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if not interleave:
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack((x1 * cos - x2 * sin, x1 * sin + x2 * cos),
                     axis=-1).reshape(x.shape)


def attention(h, p, sz: dict, mode: str):
    b, t, _ = h.shape
    heads, dn, dr, dv, rank = (sz["heads"], sz["dn"], sz["dr"], sz["dv"],
                               sz["rank"])
    q = linear(h, p["q_w"], mode).reshape(b, t, heads, dn + dr)
    kva = linear(h, p["kva_w"], mode)
    c = kva[..., :rank]
    if mode != "no_latent_norm":
        c = mimo.rms_norm(c, p["kvn_g"], sz["eps"])
    kv = linear(c, p["kvb_w"], mode).reshape(b, t, heads, dn + dv)
    q_rope = rope(q[..., dn:], sz["theta"], sz["interleave"])
    k_rope = rope(kva[..., None, rank:], sz["theta"], sz["interleave"])
    if mode == "no_rope_key":
        k_rope = jnp.zeros_like(k_rope)
    q = jnp.concatenate((q[..., :dn], q_rope), axis=-1)
    k = jnp.concatenate(
        (kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, heads, dr))), axis=-1)
    v = kv[..., dn:]
    width = dn if mode == "scale_128" else dn + dr
    outs = []
    # in blocks of query positions, each against the keys it can see
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                       precision=HI) / jnp.sqrt(jnp.float32(width))
        keep = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(keep[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                               v[:, :hi], precision=HI))
    o = jnp.concatenate(outs, axis=1).reshape(b, t, heads * dv)
    return linear(o, p["o_w"], mode)


def route(h, p, sz: dict, mode: str):
    """The chosen experts (B, T, k), their weights, and where the choice
    is a NEAR-TIE (B, T): an expert held here is chosen within
    ``sz["tie"]`` (:data:`TIE`) of the first one left out, or left out
    within it of the last one chosen. All in float32 whatever the mode: a
    choice is a step, not a rounding."""
    k = sz["top_k"]
    z = jax.nn.sigmoid(jnp.matmul(h, p["router_w"], precision=HI))
    scores = z + p["select_bias"]
    top, experts = jax.lax.top_k(scores, k + 1)
    first, count = sz["held"]
    last_in, first_out = top[..., k - 1:k], top[..., k:]
    held = scores[..., first:first + count]
    # how far a held expert lies from the other side of the choice
    near = (jnp.where(held >= last_in, held - first_out, last_in - held)
            < sz["tie"]).any(-1)
    experts = experts[..., :k]
    chosen = jnp.take_along_axis(z, experts, axis=-1)
    scale = 1.0 if mode == "unscaled_route" else sz["route_scale"]
    return (experts,
            scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20), near)


def routed_ffn(h, p, sz: dict, mode: str, share: tuple | None = None):
    """What the experts ``share = (first, count)`` (default: the held
    ones) add: every one of them over every token, weighted by the
    router's weight where the token chose it and by nought elsewhere; the
    shared expert is no part of it. With it, where the choice was a
    near-tie (:func:`route`)."""
    first, count = share or sz["held"]
    experts, weights, near = route(h, p, sz, mode)
    out = jnp.zeros_like(h)
    for e in range(count):
        w = jnp.where(experts == first + e, weights, 0.0).sum(-1)
        out = out + w[..., None] * mimo.swiglu(
            h, p["e_gate_w"][e], p["e_up_w"][e], p["e_down_w"][e], mode)
    return out, near


def shared_ffn(h, p, sz: dict, mode: str):
    """The always-on shared expert, unweighted."""
    if not sz["sf"] or mode == "no_shared":
        return jnp.zeros_like(h)
    return mimo.swiglu(h, p["s_gate_w"], p["s_up_w"], p["s_down_w"], mode)


def block(x, p, sz: dict, kind: str, mode: str):
    """A layer whose FFN is of ``kind`` over the stream, and the
    positions (B, T) whose routing in it was a near-tie (none in a dense
    layer)."""
    x = x + attention(mimo.rms_norm(x, p["ln1_g"], sz["eps"]), p, sz, mode)
    h = mimo.rms_norm(x, p["ln2_g"], sz["eps"])
    if kind == "routed":
        out, near = routed_ffn(h, p, sz, mode)
        return x + out + shared_ffn(h, p, sz, mode), near
    return (x + mimo.swiglu(h, p["gate_w"], p["up_w"], p["down_w"], mode),
            jnp.zeros(x.shape[:2], bool))


head = mimo.head


def forward(params: dict, ids, sz: dict, mode: str = "f32"):
    """Logits (B, T, V) in float32 for token ids (B, T), from whole
    parameters (the CPU tests' sizes)."""
    x = params["globals"]["wte"][ids]
    for kind, p in zip(sz["ffns"], params["layers"]):
        x, _ = block(x, p, sz, kind, mode)
    return head(x, params["globals"], sz, mode)


# -- serving: the gap of served tokens below the reference's best --------------


def _frozen(sz: dict) -> tuple:
    return tuple(sorted(sz.items()))


# jitted here, not inside served_gaps_fn, and keyed by the sizes: the
# control and every planted fault then share the float32 pass's programs
@functools.partial(jax.jit, static_argnames=("sizes",))
def _globals(key, sizes):
    return init_globals(key, dict(sizes))


@functools.partial(jax.jit, static_argnames=("kind", "m", "sizes"))
def _layer(x, key, i, kind, m, sizes):
    sz = dict(sizes)
    return block(x, init_layer(key, sz, i, kind), sz, kind, m)


@functools.partial(jax.jit, static_argnames=("m", "sizes"))
def _logits(x, g, m, sizes):
    return head(x, g, dict(sizes), m)[0]


_gaps = jax.jit(mimo._gaps)


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    """What ``check.served_gaps`` calls for each ``(seq, first, n)``. The
    parameters are made layer by layer from ``key`` as each layer runs,
    and a layer's program is compiled once for each FFN kind and mode (the
    layer's number is traced)."""
    sizes = _frozen(sz)
    glob = _globals(key, sizes)

    def run(seq, m):
        x = glob["wte"][seq]
        unsure = jnp.zeros(seq.shape[1], bool)
        for i, kind in enumerate(sz["ffns"]):
            x, near = _layer(x, key, i, kind, m, sizes)
            unsure = unsure | near[0]
        return _logits(x, glob, m, sizes), unsure

    def fn(seq, first, n):
        ref, unsure = run(seq, "f32")
        low = None if mode == "f32" else run(seq, mode)[0]
        return _gaps(ref, low, seq, first, unsure)

    return fn
