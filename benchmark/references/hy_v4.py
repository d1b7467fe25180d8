"""The plain reference of the ``hy_v4`` family (the ``model_type`` of
tencent/Hy4-preview): its layer equations in straightforward
``jax.numpy``, float32, every product at ``precision=HIGHEST``. No kernels,
no cache, no batching of slots; nothing of the program is imported (the
``gpt2`` reference's linear layer and rounding modes are, and the
``mimo_v2_flash`` and ``deepseek_v3`` references' arithmetic where it is
the same: the RMSNorm, the router and its near-tie rule, the head, how a
leaf is drawn and how served tokens are held against logits).

The equations, from the published ``config.json``, the catalog's
``described_as`` and the configuration's ``assumed``. ``rms(x) = x /
sqrt(mean(x^2) + eps) * g``; rotary positions are the half-rotation of
the first ``qk_rope_head_dim`` numbers of a head, base ``rope_theta``.

- THE RESIDUAL is ``n = hc_mult`` streams ``X`` (T, n, C), each the token's
  embedding at the start, summed before the final norm. Around each
  sublayer ``F`` (attention, then the FFN), manifold-constrained
  hyper-connections (mHC, arXiv:2512.24880): ``x = RMSNorm(vec X)`` (no
  gain), ``[p ; q ; r] = x phi``, ``H_pre = sigmoid(a_0 p + b_pre)``,
  ``H_post = hc_magnitude * sigmoid(a_1 q + b_post)``, ``H_res =
  SinkhornKnopp(exp(a_2 mat(r) + b_res))`` (20 alternations of row and
  column normalisation, ``hc_eps`` in each division), and ``X' = H_res X
  + H_post^T F(H_pre X)``.
- ATTENTION (gated MLA with DeepSeek sparse attention), on ``h =
  rms(H_pre X)``: the query's low rank ``c_q = rms(h W_qa)``, ``q = c_q
  W_qb`` as ``H`` heads ``[q_nope ; q_rope]``; ``[c_raw ; k_rope] = h
  W_kva``, ``c = rms(c_raw)``, one ``k_rope`` for all heads; ``[k_nope_h ;
  v_h] = c W_kvb``. The LIGHTNING INDEXER (a ``full`` layer's own):
  ``q^I = c_q W_iq`` (``index_n_heads`` of ``index_head_dim``), ``k^I =
  LayerNorm(h W_ik)`` (one a position, gain and bias, eps 1e-6), both
  rotated on their first ``qk_rope_head_dim`` numbers, ``w = h W_iw /
  sqrt(index_n_heads * index_head_dim)``; ``I_{t,s} = sum_j w_{t,j}
  ReLU(q^I_{t,j} . k^I_s)`` for ``s <= t``; the query at ``t`` reads the
  ``min(index_topk, t + 1)`` positions of highest ``I`` alone. A
  ``shared`` layer reads the choice of the nearest ``full`` layer above
  it. Scores ``q_h . k_h / sqrt(qk_nope + qk_rope)`` over the chosen
  positions, a learned per-head SINK in the softmax's denominator (``p_j
  = e^{s_j} / (sum_j e^{s_j} + e^{sink_h})``), ``o_h = sum p v_h``; the
  GATE ``o_h * sigmoid(h W_g)_h`` element by element; ``concat(o) W_o``.
- FFN on ``rms(H_pre X)``: the first layer's dense SwiGLU, the others'
  routed one (sigmoid scores, the ``num_experts_per_tok`` largest of score
  plus a selection bias, weights ``routed_scaling_factor * z / sum z``)
  beside one always-on shared expert; every SwiGLU CLAMPED: ``silu(min(g,
  swiglu_limit)) * clip(u, -swiglu_limit, swiglu_limit)``.
- A final RMSNorm over the streams' sum, an untied float32 head.

Each query's chosen rows are GATHERED, never a dense ``T x T`` masked, and
the attention over them is taken ABSORBED (``W_kvb``'s key half folded
into the query, its value half applied after the sum; DeepSeek's own
sparse kernels take it so): the identity is exact, and expanding 2,048
keys of 64 heads for every query would not fit a chip. Positions are
worked in chunks (:data:`CHUNK`) and queries in blocks, so that 32k
positions fit; the check's passes stop at the chunk of the last served
token, since no position before it reads a later one.

THE CHIP'S SHARE and how parameters are made: as the ``deepseek_v3``
reference's (the held experts ``sz["held"]``; layer by layer from the
key; every number one that bfloat16 holds exactly). Router NEAR-TIES are
left out by that reference's rule (:data:`TIE`).

``mode`` is the arithmetic of the linear layers (``f32``; ``bf16``,
``int8``, ``fp8`` round both operands: the control) or one of the five
PLANTED FAULTS, each the reference with one piece changed:
``dense_read`` (no choice: every earlier row is read), ``first_index``
(the shared layers read layer 0's choice, not the nearest ``full``
layer's), ``plain_residual`` (one stream, ``X' = X + F(X)``),
``no_gate``, ``no_sink``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import deepseek_v3 as dsv3
from benchmark.references import gpt2 as plain
from benchmark.references import mimo_v2_flash as mimo

HI = plain.HI
ROUNDINGS = plain.MODES
FAULTS = ("dense_read", "first_index", "plain_residual", "no_gate",
          "no_sink")
MODES = ROUNDINGS + FAULTS
TIE = dsv3.TIE
#: positions worked at once outside attention's read (a chunk's dense
#: FFN of 18,432 is 300 MB at 4,096), queries whose index scores over
#: every key lie in memory at once (64 x 32 heads x 32,768 keys is 268
#: MB), and queries whose chosen rows are gathered at once
CHUNK = 4096
INDEX_BLOCK = 64
READ_BLOCK = 32
#: the Sinkhorn-Knopp alternations of the residual's mixing map
SINKHORN_ITERS = 20
#: the index key's LayerNorm epsilon (DeepSeek-V3.2's indexer)
INDEX_NORM_EPS = 1e-6
#: a residual-mixing map's bias on its diagonal: the streams start kept
HC_KEEP = 4.0

ATTN_LEAVES = {
    "ln1_g": (("d",), "gain"),
    "qa_w": (("d", "qr"), "weight"), "qan_g": (("qr",), "gain"),
    "qb_w": (("qr", "qd"), "weight"),
    "kva_w": (("d", "ad"), "weight"), "kvn_g": (("rank",), "gain"),
    "kvb_w": (("rank", "bd"), "weight"),
    "ag_w": (("d", "od"), "weight"), "o_w": (("od", "d"), "weight"),
    "sink": (("heads",), "sink"),
    "ln2_g": (("d",), "gain"),
    "hc1_phi": (("nd", "hw"), "weight"), "hc1_b": (("hw",), "hc_bias"),
    "hc1_a": (("three",), "hc_alpha"),
    "hc2_phi": (("nd", "hw"), "weight"), "hc2_b": (("hw",), "hc_bias"),
    "hc2_a": (("three",), "hc_alpha"),
}
INDEX_LEAVES = {
    "iq_w": (("qr", "iqd"), "weight"), "ik_w": (("d", "di"), "weight"),
    "ikn_g": (("di",), "gain"), "ikn_b": (("di",), "bias"),
    "iw_w": (("d", "hi"), "weight"),
}
DENSE_LEAVES = mimo.DENSE_LEAVES
ROUTED_LEAVES = dsv3.ROUTED_LEAVES
GLOBAL_LEAVES = mimo.GLOBAL_LEAVES


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under the short names used here."""
    published = cfg.get("published", {})
    layers = int(cfg["num_hidden_layers"])
    heads = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    rank, qr = int(cfg["kv_lora_rank"]), int(cfg["q_lora_rank"])
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    n, d = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    held_n = int(cfg["n_routed_experts"])
    ef = int(cfg["moe_intermediate_size"])
    assert int(cfg["n_group"]) == 1 and cfg["use_mla"] and cfg["use_dsa"]
    return {
        "d": d, "v": int(cfg["vocab_size"]), "layers": layers,
        "ffns": tuple("dense" if k == "dense" else "routed"
                      for k in cfg["mlp_layer_types"][:layers]),
        "own": tuple(k == "full" for k in cfg["indexer_types"][:layers]),
        "heads": heads, "dn": dn, "dr": dr, "dv": dv, "rank": rank,
        "qr": qr, "qd": heads * (dn + dr), "ad": rank + dr,
        "bd": heads * (dn + dv), "od": heads * dv,
        "hi": hi, "di": di, "iqd": hi * di, "topk": int(cfg["index_topk"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "hc": n, "nd": n * d, "hw": 2 * n + n * n, "three": 3,
        "hc_magnitude": float(cfg["hc_magnitude"]),
        "hc_eps": float(cfg["hc_eps"]),
        "f": int(cfg["intermediate_size"]), "ef": ef,
        "sf": int(cfg["n_shared_experts"] or 0) * ef,
        "experts": int(published.get("n_routed_experts", held_n)),
        "held": (int(cfg.get("deployment_share", {}).get("first_expert", 0)),
                 held_n),
        "held_n": held_n, "top_k": int(cfg["num_experts_per_tok"]),
        "route_scale": float(cfg["routed_scaling_factor"] or 1.0),
        "limit": float(cfg["swiglu_limit"] or 0.0),
        "eps": float(cfg["rms_norm_eps"]),
        "init_std": float(cfg.get("initializer_range", mimo.WEIGHT_STD)),
        "tie": TIE,
    }


def layer_leaves(sz: dict, i: int, own: bool | None = None,
                 kind: str | None = None) -> dict:
    """Layer ``i``'s leaves with their shapes and init kinds: the index
    leaves where it runs its own indexer, the dense or routed FFN's."""
    own = sz["own"][i] if own is None else own
    kind = kind or sz["ffns"][i]
    leaves = dict(ATTN_LEAVES)
    if own:
        leaves.update(INDEX_LEAVES)
    leaves.update(DENSE_LEAVES if kind == "dense" else ROUTED_LEAVES)
    if kind != "dense" and not sz["sf"]:
        leaves = {k: v for k, v in leaves.items() if not k.startswith("s_")}
    return {name: (tuple(sz[k] for k in shape), init)
            for name, (shape, init) in leaves.items()}


def _names() -> list:
    return sorted({**ATTN_LEAVES, **INDEX_LEAVES, **DENSE_LEAVES,
                   **ROUTED_LEAVES, **GLOBAL_LEAVES})


def make_leaf(key, shape: tuple, init: str, sz: dict):
    """The ``deepseek_v3`` reference's draw, and the kinds this family
    adds. A head's ``sink`` is ``ln(index_topk) + N(0, 1)``: its query
    reads ``index_topk`` rows at the prompts' lengths, and a sink of the
    log of their number holds a share of the softmax that a fault without
    it moves. A mixing map's bias keeps the streams at the start (``b_res``
    ``HC_KEEP`` on the diagonal) and moves every map a little (N(0,
    0.1)); its three ``alpha`` are ``0.1 + N(0, 0.02)``, so that the maps
    read the streams (``x phi`` of deviation ``0.02 sqrt(n C)``)."""
    n = jax.random.normal(key, shape, jnp.float32)
    if init == "sink":
        return mimo._exact(round(math.log(sz["topk"])) + n)
    if init == "bias":
        return mimo._exact(0.1 * n)
    if init == "hc_alpha":
        return mimo._exact(0.1 + 0.02 * n)
    if init == "hc_bias":
        k = sz["hc"]
        keep = jnp.concatenate((jnp.zeros(2 * k),
                                HC_KEEP * jnp.eye(k).reshape(-1)))
        return mimo._exact(keep + 0.1 * n)
    return dsv3.make_leaf(key, shape, init, sz)


def init_layer(key, sz: dict, i, own: bool | None = None,
               kind: str | None = None) -> dict:
    """Layer ``i``'s leaves from the run's key. Traceable, in ``i`` too
    where ``own`` and ``kind`` are given."""
    key = jax.random.fold_in(key, 1000 + i)
    names = _names()
    return {name: make_leaf(jax.random.fold_in(key, names.index(name)),
                            shape, init, sz)
            for name, (shape, init) in layer_leaves(sz, i, own, kind).items()}


def init_globals(key, sz: dict) -> dict:
    names = _names()
    return {name: make_leaf(jax.random.fold_in(key, names.index(name)),
                            tuple(sz[k] for k in shape), init, sz)
            for name, (shape, init) in GLOBAL_LEAVES.items()}


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


rms_norm = mimo.rms_norm


def rope(x, positions, theta: float, rotary: int):
    """Half-rotation of the first ``rotary`` numbers of every head of
    ``x`` (T, H, D) at ``positions`` (T,); the rest pass."""
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest), -1)


def hyper_maps(x, phi, b, a, sz: dict, mode: str):
    """``H_pre`` (T, n), ``H_post`` (T, n) and ``H_res`` (T, n, n) of the
    streams ``x`` (T, n, C)."""
    k = sz["hc"]
    flat = x.reshape(x.shape[0], -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                                + sz["eps"])
    z = linear(flat, phi, mode)
    pre = jax.nn.sigmoid(a[0] * z[:, :k] + b[:k])
    post = sz["hc_magnitude"] * jax.nn.sigmoid(
        a[1] * z[:, k:2 * k] + b[k:2 * k])
    res = jnp.exp(a[2] * z[:, 2 * k:] + b[2 * k:]).reshape(-1, k, k)
    for _ in range(SINKHORN_ITERS):
        res = res / (res.sum(-1, keepdims=True) + sz["hc_eps"])
        res = res / (res.sum(-2, keepdims=True) + sz["hc_eps"])
    return pre, post, res


def swiglu(h, gate_w, up_w, down_w, sz: dict, mode: str):
    g, u = linear(h, gate_w, mode), linear(h, up_w, mode)
    if sz["limit"]:
        g = jnp.minimum(g, sz["limit"])
        u = jnp.clip(u, -sz["limit"], sz["limit"])
    return linear(jax.nn.silu(g) * u, down_w, mode)


def experts_linear(xs, w, sizes_, mode: str):
    """Rows of ``xs`` sorted by expert through their expert's matrix
    (``jax.lax.ragged_dot``), rounded as :func:`linear` rounds."""
    if mode in ROUNDINGS and mode != "f32":
        xs = plain._round_operand(xs, -1, mode)
        w = plain._round_operand(w, 1, mode)
    return jax.lax.ragged_dot(xs, w, sizes_, precision=HI)


def routed_ffn(h, p, sz: dict, mode: str, share: tuple | None = None):
    """What the experts ``share = (first, count)`` of the layer's held
    ones (default: all of them) add to the tokens ``h`` (T, C): each
    (token, expert) pair that fell on one of them through its clamped
    SwiGLU, weighted; and where the choice was a near-tie
    (``deepseek_v3.route``)."""
    first, count = share or sz["held"]
    base = sz["held"][0]
    experts, weights, near = dsv3.route(h, p, sz, mode)
    local = experts.reshape(-1) - first
    mine = (local >= 0) & (local < count)
    group = jnp.where(mine, local, count)
    order = jnp.argsort(group, stable=True)
    counts = (group[None, :] == jnp.arange(count)[:, None]).sum(
        axis=1, dtype=jnp.int32)
    xs = h[order // sz["top_k"]]

    def mine_of(name):
        return p[name][first - base:first - base + count]

    g = experts_linear(xs, mine_of("e_gate_w"), counts, mode)
    u = experts_linear(xs, mine_of("e_up_w"), counts, mode)
    if sz["limit"]:
        g = jnp.minimum(g, sz["limit"])
        u = jnp.clip(u, -sz["limit"], sz["limit"])
    ys = experts_linear(jax.nn.silu(g) * u, mine_of("e_down_w"), counts, mode)
    w = jnp.where(mine, weights.reshape(-1), 0.0)[order]
    ys = jnp.where(mine[order][:, None], ys * w[:, None], 0.0)
    return jnp.zeros_like(h).at[order // sz["top_k"]].add(ys), near


def shared_ffn(h, p, sz: dict, mode: str):
    if not sz["sf"]:
        return jnp.zeros_like(h)
    return swiglu(h, p["s_gate_w"], p["s_up_w"], p["s_down_w"], sz, mode)


def _chunks(n: int) -> int:
    return min(CHUNK, n)


def _map(f, xs, block: int):
    """``f`` over blocks of ``block`` rows of every array in ``xs``,
    concatenated: ``jax.lax.map`` over the blocks."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n} rows do not split into blocks of {block}"
    split = jax.tree_util.tree_map(
        lambda a: a.reshape(n // block, block, *a.shape[1:]), xs)
    out = jax.lax.map(f, split)
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n, *a.shape[2:]), out)


def _upto(f, end):
    """``f`` over a block of ``(rows, positions, ...)`` whose first
    position lies before ``end`` (a traced count), zeros of ``f``'s shapes
    past it: rows no compared position reads (causality). ``end`` None
    runs every block."""
    if end is None:
        return f

    def block(args):
        shapes = jax.eval_shape(f, args)
        return jax.lax.cond(
            args[1][0] < end, f,
            lambda _: jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), shapes), args)

    return block


def _mixed(x, name: str, p, sz: dict, mode: str):
    """The sublayer's input ``H_pre X`` (T, C) and the maps, or the stream
    itself under ``plain_residual``."""
    if mode == "plain_residual":
        return x, None
    pre, post, res = hyper_maps(x, p[f"{name}_phi"], p[f"{name}_b"],
                                p[f"{name}_a"], sz, mode)
    return jnp.einsum("tn,tnc->tc", pre, x, precision=HI), (post, res)


def _leave(x, maps, out):
    if maps is None:
        return x + out
    post, res = maps
    return (jnp.einsum("tij,tjc->tic", res, x, precision=HI)
            + post[..., None] * out[:, None, :])


def _projections(x, p, sz: dict, mode: str, positions):
    """What attention needs of the positions ``x`` (T, ...) stands at: the
    normed input ``h``, the query's latent ``c_q``, the cached row ``[c ;
    k_rope]`` and, where the layer has its own indexer, the index key."""
    inp, maps = _mixed(x, "hc1", p, sz, mode)
    h = rms_norm(inp, p["ln1_g"], sz["eps"])
    c_q = rms_norm(linear(h, p["qa_w"], mode), p["qan_g"], sz["eps"])
    kva = linear(h, p["kva_w"], mode)
    c = rms_norm(kva[:, :sz["rank"]], p["kvn_g"], sz["eps"])
    k_rope = rope(kva[:, None, sz["rank"]:], positions, sz["theta"],
                  sz["dr"])[:, 0]
    row = jnp.concatenate((c, k_rope), -1)
    key = None
    if "ik_w" in p:
        key = plain.layer_norm(linear(h, p["ik_w"], mode), p["ikn_g"],
                               p["ikn_b"], INDEX_NORM_EPS)
        key = rope(key[:, None], positions, sz["theta"], sz["dr"])[:, 0]
    return h, c_q, row, key, maps


def _index_select(h, c_q, keys, p, sz: dict, mode: str, positions):
    """The ``min(topk, T_keys)`` positions of highest index score for the
    queries at ``positions``, in descending order of score."""
    b = h.shape[0]
    qi = linear(c_q, p["iq_w"], mode).reshape(b, sz["hi"], sz["di"])
    qi = rope(qi, positions, sz["theta"], sz["dr"])
    w = linear(h, p["iw_w"], mode) / math.sqrt(sz["hi"] * sz["di"])
    k = min(sz["topk"], keys.shape[0])

    def block(args):
        qb, wb, at = args
        s = jnp.einsum("qhd,kd->qhk", qb, keys, precision=HI)
        s = (jnp.maximum(s, 0.0) * wb[..., None]).sum(1)
        s = jnp.where(jnp.arange(keys.shape[0])[None] <= at[:, None], s,
                      -jnp.inf)
        return jax.lax.top_k(s, k)[1]

    return _map(block, (qi, w, positions), INDEX_BLOCK)


def _read(q, rows, sel, positions, sink, sz: dict, mode: str):
    """Absorbed queries ``q`` (T, H, rank + dr) over the rows ``sel``
    chose (every earlier row under ``dense_read``): ``o_lat`` (T, H,
    rank)."""
    scale = 1.0 / math.sqrt(sz["dn"] + sz["dr"])
    rank = sz["rank"]

    def soft(s, valid, values, spec):
        s = jnp.where(valid, s, -jnp.inf)
        m = s.max(-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[None, :, None])
        e = jnp.exp(s - m)
        denom = e.sum(-1, keepdims=True)
        if sink is not None:
            denom = denom + jnp.exp(sink[None, :, None] - m)
        return jnp.einsum(spec, e / denom, values, precision=HI)

    if mode == "dense_read":
        def dense(args):
            qb, at = args
            s = jnp.einsum("qhc,kc->qhk", qb, rows, precision=HI) * scale
            valid = (jnp.arange(rows.shape[0])[None] <= at[:, None])[:, None]
            return soft(s, valid, rows[:, :rank], "qhk,kc->qhc")

        return _map(dense, (q, positions), READ_BLOCK)

    def sparse(args):
        qb, selb, at = args
        picked = rows[selb]                               # (q, k, rank + dr)
        s = jnp.einsum("qhc,qkc->qhk", qb, picked, precision=HI) * scale
        count = jnp.minimum(at + 1, selb.shape[-1])
        valid = (jnp.arange(selb.shape[-1])[None] < count[:, None])[:, None]
        return soft(s, valid, picked[..., :rank], "qhk,qkc->qhc")

    return _map(sparse, (q, sel, positions), READ_BLOCK)


def layer(x, p, sz: dict, own: bool, kind: str, mode: str, sel=None,
          end=None):
    """One layer over the streams ``x`` (T, n, C) (or (T, C) under
    ``plain_residual``): the streams after it, the choice it read, and
    the positions (T,) whose routing was a near-tie. A layer with its own
    indexer chooses, unless ``sel`` hands it a choice (the CPU tests hand
    it the program's, to hold the rest of the layer to it); a shared one
    reads ``sel``. ``end`` (a traced count, or None for all): the blocks
    of positions from ``end`` on are left as zeros, which no position
    before ``end`` reads."""
    t = x.shape[0]
    heads, dn, dr, dv, rank = (sz["heads"], sz["dn"], sz["dr"], sz["dv"],
                               sz["rank"])
    chunk = _chunks(t)
    positions = jnp.arange(t)
    # every position's cached row and index key
    rows, keys = _map(_upto(
        lambda args: _projections(args[0], p, sz, mode, args[1])[2:4],
        end), (x, positions), chunk)
    w_kvb = p["kvb_w"].reshape(rank, heads, dn + dv)
    sink = None if mode == "no_sink" else p["sink"]

    def attend(args):
        xc, at, selc = args
        h, c_q, _, _, maps = _projections(xc, p, sz, mode, at)
        if own and not selc.shape[-1]:
            selc = _index_select(h, c_q, keys, p, sz, mode, at)
        q = linear(c_q, p["qb_w"], mode).reshape(-1, heads, dn + dr)
        q_lat = jnp.einsum("thn,chn->thc", q[..., :dn], w_kvb[..., :dn],
                           precision=HI)
        q_rope = rope(q[..., dn:], at, sz["theta"], dr)
        o_lat = _read(jnp.concatenate((q_lat, q_rope), -1), rows, selc, at,
                      sink, sz, mode)
        o = jnp.einsum("thc,chv->thv", o_lat, w_kvb[..., dn:], precision=HI)
        if mode != "no_gate":
            o = o * jax.nn.sigmoid(linear(h, p["ag_w"], mode)).reshape(
                o.shape)
        xc = _leave(xc, maps, linear(o.reshape(o.shape[0], -1), p["o_w"],
                                     mode))
        inp, maps = _mixed(xc, "hc2", p, sz, mode)
        hf = rms_norm(inp, p["ln2_g"], sz["eps"])
        if kind == "dense":
            out = swiglu(hf, p["gate_w"], p["up_w"], p["down_w"], sz, mode)
            near = jnp.zeros(xc.shape[0], bool)
        else:
            out, near = routed_ffn(hf, p, sz, mode)
            out = out + shared_ffn(hf, p, sz, mode)
        return _leave(xc, maps, out), selc, near

    if sel is None:
        sel = jnp.zeros((t, 0), jnp.int32)
    return _map(_upto(attend, end), (x, positions, sel), chunk)



def streams(ids, g: dict, sz: dict, mode: str):
    """The residual at the start: the embedding of ``ids`` (T,) in each
    stream, (T, n, C); one stream under ``plain_residual``."""
    x = g["wte"][ids]
    if mode == "plain_residual":
        return x
    return jnp.broadcast_to(x[:, None], (x.shape[0], sz["hc"], x.shape[1]))


def head(x, g: dict, sz: dict, mode: str):
    """Logits (T, V) of the streams' sum."""
    if mode != "plain_residual":
        x = x.sum(1)
    return linear(rms_norm(x, g["lnf_g"], sz["eps"]), g["head_w"], mode)


def _choices(sz: dict, mode: str):
    """For each layer, which choice it reads: ``None`` (its own), or the
    layer whose own choice it reuses: the nearest ``full`` layer above it,
    or the first under ``first_index``."""
    out, last = [], None
    for i, own in enumerate(sz["own"]):
        out.append(None if own else
                   (sz["own"].index(True) if mode == "first_index" else last))
        if own:
            last = i
    return out


def forward(params: dict, ids, sz: dict, mode: str = "f32", given=None):
    """Logits (1, T, V) in float32 for token ids (1, T), from whole
    parameters (the CPU tests' sizes), and every layer's choice. ``given``
    (a choice (T, k) for every layer, or None) makes each layer with its
    own indexer read the one given instead of its own."""
    x = streams(ids[0], params["globals"], sz, mode)
    chosen = []
    for i, (p, use) in enumerate(zip(params["layers"], _choices(sz, mode))):
        sel = chosen[use] if use is not None else (
            None if given is None else given[i])
        x, sel, _ = layer(x, p, sz, sz["own"][i], sz["ffns"][i], mode, sel)
        chosen.append(sel)
    return head(x, params["globals"], sz, mode)[None], chosen


# -- serving: the gap of served tokens below the reference's best --------------


def _frozen(sz: dict) -> tuple:
    return tuple(sorted(sz.items()))


# jitted here, not inside served_gaps_fn, and keyed by the sizes: the
# control and every planted fault then share the float32 pass's programs
@functools.partial(jax.jit, static_argnames=("m", "sizes"))
def _start(key, seq, m, sizes):
    sz = dict(sizes)
    g = init_globals(key, sz)
    return g, streams(seq[0], g, sz, m)


# the streams are donated: at 32k positions they are 3.2 GB in float32
@functools.partial(jax.jit, static_argnames=("own", "kind", "m", "sizes"),
                   donate_argnums=(0,))
def _layer(x, key, i, sel, end, own, kind, m, sizes):
    sz = dict(sizes)
    return layer(x, init_layer(key, sz, i, own, kind), sz, own, kind, m,
                 sel, end)


#: the served tokens a request can have at most: the rows of the last
#: layer's streams that the head reads for a request (the longest answer
#: of the cell's mix is 2,048 tokens)
SERVED_ROWS = 4096
#: the float32 pass's rows of the last requests compared: the control and
#: each planted fault (``benchmark/limits.py``) then run their own pass
#: alone over a request the float32 pass has read
_F32 = {}
_F32_KEEP = 8


def _at(first, rows: int, length: int):
    """The positions whose logits predict the served tokens ``first ..``:
    ``first - 1 ..``, ``rows`` of them, clipped to the sequence."""
    return jnp.clip(first - 1 + jnp.arange(rows), 0, length - 1)


# the head reads the rows that predict the served tokens alone: logits of
# every one of 32k positions are 2 GB
@functools.partial(jax.jit, static_argnames=("rows", "m", "sizes"))
def _logits(x, g, first, rows, m, sizes):
    return head(x[_at(first, rows, x.shape[0])], g, dict(sizes), m)


@functools.partial(jax.jit, static_argnames=("rows",))
def _served(unsure, seq, first, rows):
    """The near-tie flags at those positions, and the served tokens."""
    at = _at(first, rows, seq.shape[1])
    return unsure[at], seq[0][jnp.clip(at + 1, 0, seq.shape[1] - 1)]


@jax.jit
def _below(at, unsure, picked):
    """How far the token ``picked`` of each row lies below its best; nought
    at a near-tie."""
    gap = at.max(-1) - jnp.take_along_axis(at, picked[:, None], axis=1)[:, 0]
    return jnp.where(unsure, 0.0, gap)


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    """What ``check.served_gaps`` calls for each ``(seq, first, n)``. The
    parameters are made layer by layer from ``key`` as each layer runs,
    and a layer's program is compiled once for each kind of layer and
    mode (the layer's number is traced). The float32 pass's rows of a
    request are kept (:data:`_F32`), so a control or a fault asked of the
    same request runs its own pass alone."""
    sizes_ = _frozen(sz)

    def run(seq, first, n, rows, m):
        """The logits (rows, V) at the positions that predict the served
        tokens, and where a router's choice was a near-tie, (T,). A
        program whose arithmetic a fault leaves as the float32 pass's is
        the float32 pass's own: ``first_index`` changes no layer, only
        which choice a layer is handed, and only a rounding or
        ``plain_residual`` changes the streams' start or the head."""
        ends = m if m == "plain_residual" or m in ROUNDINGS else "f32"
        body = "f32" if m == "first_index" else m
        g, x = _start(key, seq, "plain_residual" if m == "plain_residual"
                      else "f32", sizes_)
        unsure = jnp.zeros(seq.shape[1], bool)
        chosen = []
        empty = jnp.zeros((seq.shape[1], 0), jnp.int32)
        # the positions past the last served token's are read by none
        # that is compared: their blocks are skipped
        end = jnp.int32(first + n)
        for i, use in enumerate(_choices(sz, m)):
            x, sel, near = _layer(
                x, key, i, empty if use is None else chosen[use], end,
                sz["own"][i], sz["ffns"][i], body, sizes_)
            chosen.append(sel if sz["own"][i] else None)
            unsure = unsure | near
        return _logits(x, g, first, rows, ends, sizes_), unsure

    def fn(seq, first, n):
        rows = min(SERVED_ROWS, seq.shape[1])
        assert n <= rows, f"{n} served tokens, more than {rows}"
        tag = (np.asarray(jax.random.key_data(key)).tobytes(),
               hash(np.asarray(seq).tobytes()), int(first), int(n))
        if tag not in _F32:
            at, unsure = run(seq, first, n, rows, "f32")
            while len(_F32) >= _F32_KEEP:
                _F32.pop(next(iter(_F32)))
            _F32[tag] = (at, *_served(unsure, seq, first, rows))
        at, unsure, toks = _F32[tag]
        served = _below(at, unsure, toks)
        if mode == "f32":
            return served, served
        low = run(seq, first, n, rows, mode)[0]
        return served, _below(at, unsure, jnp.argmax(low, axis=-1))

    return fn
