"""The plain reference of the ``sdar_moe`` family (the ``model_type`` of
JetLM/SDAR-30B-A3B-Chat): its layer equations in straightforward
``jax.numpy``, float32, every product at ``precision=HIGHEST``. No kernels,
no cache, no batching of slots; nothing of the program is imported (the
``gpt2`` reference's linear layer and rounding modes are, and the
``mimo_v2_flash`` reference's arithmetic where it is the same: the norm,
SwiGLU, the half-rotation, the head and how a leaf is drawn).

The equations, from the published ``config.json``, the catalog's
``described_as`` and the configuration's ``assumed``; ``x`` is the float32
residual stream, ``rms(x) = x / sqrt(mean(x^2) + eps) * g``. Every layer:
``h = x + Attn(rms(x))``, then ``h + MoE(rms(h))``.

- Attn: ``q = x W_q`` as ``H`` heads of ``head_dim``, ``k = x W_k``, ``v =
  x W_v`` as ``num_key_value_heads`` heads; no biases; an RMSNorm over
  each query and each key head (one gain a kind), then half-rotation
  rotary positions on all of a head, base ``rope_theta``; query head
  ``i`` reads KV head ``i // (H / hk)``; scores over ``sqrt(head_dim)``;
  BLOCK-causal: position ``i`` sees ``j`` iff ``j // L <= i // L``;
  ``concat(o) W_o``.
- MoE: ``r = softmax(h W_r)`` over ``num_experts`` in float32, ``E`` the
  ``num_experts_per_tok`` largest, ``w_e = r_e / sum_E r``
  (``norm_topk_prob``), ``sum_E w_e SwiGLU_e(h)``, experts
  ``moe_intermediate_size`` wide, no shared expert.
- A final RMSNorm and an untied head without bias.

GENERATION BY DIFFUSION OVER BLOCKS of ``L`` on a grid from position 0:
a block starts with its positions past the prompt MASKED (they read
``mask_id``); each of ``S`` denoising steps runs the block's ``L`` rows
against the clean prefix and the block's own rows, both ways, and commits
the masked positions of highest confidence (the softmax probability of
the greedy token, ``mask_id`` excluded), ``n // S`` a step of the ``n``
masked at the start, one more while ``s < n % S``; then the clean block
closes. :func:`replay` REPLAYS that over a served request: ONE clean
block-causal forward over the prompt and the served tokens gives every
layer's K/V of the clean prefix. That is exact: under the block-causal
mask no row of a finished block depends on any later position, so the
clean forward's rows of a block are the rows its close wrote, given the
prefix's. Each step then runs the ``L`` rows of every generated block,
in every state of its commitments that the static schedule allows,
against those K/V and the block's own rows, the committed values being
the SERVED tokens; a token's gap (its reference logit below the
reference's best, ``mask_id`` excluded) is read at the step that commits
it. Which positions a step commits is held to the REFERENCE'S OWN
confidences: an order is read only if at each step no position it
commits lies more than ``ORDER_TIE`` below one it leaves masked (the
reference's own order always is; the width is what the program's
rounding can flip). Of the allowed orders, block by block, the one under
which the block's widest gap is least is read. Every served token is
compared but those of a trailing block the budget cut: its unserved
tokens were inputs of its later steps (a line on standard error says how
many were compared).

``mode`` is the arithmetic of the linear layers (``f32``; ``bf16``,
``int8``, ``fp8`` round both operands: the control) or one of the four
PLANTED FAULTS: ``causal_block`` (causal inside a block: a row sees only
the rows of its block at or before it), ``dirty_close`` (a generated
block's K/V from its last denoising pass: the positions the last step
committed still masked), ``no_topk_norm`` (the chosen experts weighted
by ``r_e`` unnormalised), ``no_qk_norm`` (the heads' norms left out). A
mode's token at each row is its greedy token on the same inputs, and its
gaps are read as the served tokens' are: block by block under the
allowed order whose widest gap is least.

Parameters are made layer by layer from the key, every number one that
bfloat16 holds exactly (the ``mimo_v2_flash`` reference says why and
how); all experts are held here (``sz["held"] = (0, num_experts)``).
"""

from __future__ import annotations

import functools
import itertools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import gpt2 as plain
from benchmark.references import mimo_v2_flash as mimo

HI = plain.HI
ROUNDINGS = plain.MODES
FAULTS = ("causal_block", "dirty_close", "no_topk_norm", "no_qk_norm")
MODES = ROUNDINGS + FAULTS

#: how many query heads attention handles at a time: (heads, T, T) scores
#: of 4,096 positions are 1 GiB at 16 heads
HEAD_BLOCK = mimo.HEAD_BLOCK
#: a replay's block rows run in passes of this many, and its clean forward
#: over a multiple of it: a few programs serve every request
ROW_BUCKET = 1024

ATTN_LEAVES = {
    "ln1_g": (("d",), "gain"),
    "q_w": (("d", "qd"), "weight"), "k_w": (("d", "kvd"), "weight"),
    "v_w": (("d", "kvd"), "weight"),
    "qn_g": (("dk",), "gain"), "kn_g": (("dk",), "gain"),
    "o_w": (("qd", "d"), "weight"),
    "ln2_g": (("d",), "gain"),
}
ROUTED_LEAVES = {name: leaf for name, leaf in mimo.ROUTED_LEAVES.items()
                 if name != "select_bias"}
GLOBAL_LEAVES = mimo.GLOBAL_LEAVES
WEIGHT_STD = mimo.WEIGHT_STD
#: a step's commitment is read under an order of the block's positions
#: that the reference's own confidences (logs of probabilities) allow: no
#: position committed lies more than this below one left masked
ORDER_TIE = 0.03


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under the short names used here."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hk, dk = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    layers = int(cfg["num_hidden_layers"])
    model = cfg["program"]["model"]
    assert cfg.get("norm_topk_prob") and not cfg.get("attention_bias")
    assert not cfg.get("mlp_only_layers") and int(
        cfg.get("decoder_sparse_step", 1)) == 1, "every layer routed"
    experts = int(cfg["num_experts"])
    return {
        "d": d, "v": int(cfg["vocab_size"]), "layers": layers,
        "kinds": ("full",) * layers, "ffns": ("routed",) * layers,
        "heads": heads, "hk": hk, "dk": dk, "qd": heads * dk,
        "kvd": hk * dk, "theta": float(cfg["rope_theta"]),
        "ef": int(cfg["moe_intermediate_size"]),
        "experts": experts, "held": (0, experts), "held_n": experts,
        "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]),
        "init_std": float(cfg.get("initializer_range", WEIGHT_STD)),
        "block_len": int(model["block"]),
        "denoise_steps": int(model["denoise_steps"]),
        "mask_id": int(model["mask_id"]),
        "order_tie": ORDER_TIE,
    }


def layer_leaves(sz: dict) -> dict:
    return {name: (tuple(sz[k] for k in shape), init)
            for name, (shape, init) in {**ATTN_LEAVES,
                                        **ROUTED_LEAVES}.items()}


def _names() -> list:
    return sorted({**ATTN_LEAVES, **ROUTED_LEAVES, **GLOBAL_LEAVES})


def _make(key, leaves: dict, sz: dict) -> dict:
    names = _names()
    return {name: mimo.make_leaf(jax.random.fold_in(key, names.index(name)),
                                 shape, init, sz)
            for name, (shape, init) in leaves.items()}


def init_layer(key, sz: dict, i) -> dict:
    """Layer ``i``'s leaves from the run's key. Traceable, in ``i`` too."""
    return _make(jax.random.fold_in(key, 1000 + i), layer_leaves(sz), sz)


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


def rope_at(x, theta: float, positions):
    """Half-rotation over every dimension of each head of ``x`` (B, T, H,
    D) at ``positions`` (T,)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), -1)


def qkv(h, p, sz: dict, positions, mode: str):
    """Queries, keys and values of ``h`` (1, T, d) at ``positions``: the
    heads' norms, then the rotation."""
    b, t, _ = h.shape
    q = linear(h, p["q_w"], mode).reshape(b, t, sz["heads"], sz["dk"])
    k = linear(h, p["k_w"], mode).reshape(b, t, sz["hk"], sz["dk"])
    v = linear(h, p["v_w"], mode).reshape(b, t, sz["hk"], sz["dk"])
    if mode != "no_qk_norm":
        q = mimo.rms_norm(q, p["qn_g"], sz["eps"])
        k = mimo.rms_norm(k, p["kn_g"], sz["eps"])
    return (rope_at(q, sz["theta"], positions),
            rope_at(k, sz["theta"], positions), v)


def attend(q, keys, values, keep, p, sz: dict, mode: str):
    """``q`` (1, T, H, D) over ``keys``/``values`` (1, K, hk, D) where
    ``keep`` (T, K) allows, then the output projection."""
    heads, group = sz["heads"], sz["heads"] // sz["hk"]
    outs = []
    # in blocks of query heads, so that (heads, T, K) scores fit
    for lo in range(0, heads, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, heads)
        kv = jnp.arange(lo, hi) // group
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, lo:hi], keys[:, :, kv],
                       precision=HI) / jnp.sqrt(jnp.float32(sz["dk"]))
        s = jnp.where(keep[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                               values[:, :, kv], precision=HI))
    o = jnp.concatenate(outs, axis=2).reshape(q.shape[0], q.shape[1], -1)
    return linear(o, p["o_w"], mode)


def sees(qpos, kpos, sz: dict, mode: str):
    """Which keys each query sees: block-causal, or causal under the
    ``causal_block`` fault."""
    if mode == "causal_block":
        return kpos[None, :] <= qpos[:, None]
    length = sz["block_len"]
    return kpos[None, :] // length <= qpos[:, None] // length


def route(h, p, sz: dict, mode: str):
    """The chosen experts (1, T, k) and their weights. In float32
    whatever the mode: a choice is a step, not a rounding."""
    logits = jnp.matmul(h, p["router_w"], precision=HI)
    _, experts = jax.lax.top_k(logits, sz["top_k"])
    chosen = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), experts,
                                 axis=-1)
    if mode != "no_topk_norm":
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return experts, chosen


def experts_linear(xs, w, sizes, mode: str):
    """Each row of ``xs`` (P, K), rows sorted by expert and ``sizes[e]``
    of them expert ``e``'s, through its expert's matrix of ``w`` (E, K,
    N): the ``gpt2`` reference's linear layer over a stack of matrices
    (``jax.lax.ragged_dot``, rows past the last group nought), its
    operands rounded as that layer rounds them in a control's mode."""
    if mode in ROUNDINGS and mode != "f32":
        xs = plain._round_operand(xs, -1, mode)
        w = plain._round_operand(w, 1, mode)
    return jax.lax.ragged_dot(xs, w, sizes, precision=HI)


def moe(h, p, sz: dict, mode: str, share: tuple | None = None):
    """What the experts ``share = (first, count)`` (default: all) add:
    each (token, expert) pair that fell on one of them through that
    expert's SwiGLU, weighted by the router's weight (the pairs sorted by
    expert, :func:`experts_linear`)."""
    first, count = share or sz["held"]
    experts, weights = route(h, p, sz, mode)
    flat = h.reshape(-1, h.shape[-1])
    local = experts.reshape(-1) - first
    mine = (local >= 0) & (local < count)
    group = jnp.where(mine, local, count)
    order = jnp.argsort(group, stable=True)
    sizes = (group[None, :] == jnp.arange(count)[:, None]).sum(
        axis=1, dtype=jnp.int32)
    xs = flat[order // sz["top_k"]]

    def mine_of(name):
        return p[name][first:first + count]

    mid = jax.nn.silu(experts_linear(xs, mine_of("e_gate_w"), sizes, mode)
                      ) * experts_linear(xs, mine_of("e_up_w"), sizes, mode)
    ys = experts_linear(mid, mine_of("e_down_w"), sizes, mode)
    w = jnp.where(mine, weights.reshape(-1), 0.0)[order]
    ys = jnp.where(mine[order][:, None], ys * w[:, None], 0.0)
    out = jnp.zeros_like(flat).at[order // sz["top_k"]].add(ys)
    return out.reshape(h.shape)


def ffn_half(x, p, sz: dict, mode: str):
    return x + moe(mimo.rms_norm(x, p["ln2_g"], sz["eps"]), p, sz, mode)


def block(x, p, sz: dict, mode: str):
    """One layer over the whole stream ``x`` (1, T, d), block-causal:
    the stream after it, its keys and values."""
    t = x.shape[1]
    positions = jnp.arange(t)
    q, k, v = qkv(mimo.rms_norm(x, p["ln1_g"], sz["eps"]), p, sz, positions,
                  mode)
    x = x + attend(q, k, v, sees(positions, positions, sz, mode), p, sz,
                   mode)
    return ffn_half(x, p, sz, mode), k, v


head = mimo.head


def forward(params: dict, ids, sz: dict, mode: str = "f32"):
    """Logits (1, T, V) in float32 for token ids (1, T) under the
    block-causal mask, from whole parameters (the CPU tests' sizes)."""
    x = params["globals"]["wte"][ids]
    for p in params["layers"]:
        x, *_ = block(x, p, sz, mode)
    return head(x, params["globals"], sz, mode)


def block_rows(x, keys, values, rows, group, p, sz: dict, mode: str):
    """One layer over a denoising step's block rows ``x`` (1, R, d) at
    positions ``rows`` (R,): each row sees the clean rows ``keys`` /
    ``values`` (1, T, hk, D) before its block's start, and the rows of its
    own ``group`` (its block) both ways
    (causal under the ``causal_block`` fault). A pad row (position -1,
    a group of its own) sees its group's rows."""
    length = sz["block_len"]
    q, k, v = qkv(mimo.rms_norm(x, p["ln1_g"], sz["eps"]), p, sz,
                  jnp.maximum(rows, 0), mode)
    start = jnp.where(rows < 0, 0, rows // length * length)
    prefix = jnp.arange(keys.shape[1])[None, :] < start[:, None]
    own = group[:, None] == group[None, :]
    if mode == "causal_block":
        own = own & (rows[None, :] <= rows[:, None])
    x = x + attend(q, jnp.concatenate((keys, k), axis=1),
                   jnp.concatenate((values, v), axis=1),
                   jnp.concatenate((prefix, own), axis=1), p, sz, mode)
    return ffn_half(x, p, sz, mode)


# -- serving: a replay of the denoising over each served request ---------------


def _frozen(sz: dict) -> tuple:
    return tuple(sorted(sz.items()))


def _f32(p: dict) -> dict:
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


# jitted here and keyed by the sizes: the control and every planted fault
# share the float32 pass's programs where their shapes agree. A layer's
# parameters are made once a run and kept in bfloat16, which holds every
# one of them exactly (init_layer draws them so)
@functools.partial(jax.jit, static_argnames=("sizes",))
def _globals(key, sizes):
    return init_globals(key, dict(sizes))


@functools.partial(jax.jit, static_argnames=("sizes",))
def _layer_params(key, i, sizes):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                  init_layer(key, dict(sizes), i))


@functools.partial(jax.jit, static_argnames=("m", "sizes"))
def _clean_layer(x, p, m, sizes):
    return block(x, _f32(p), dict(sizes), m)


@functools.partial(jax.jit, static_argnames=("m", "sizes"))
def _rows_layer(x, keys, values, rows, group, p, m, sizes):
    return block_rows(x, keys, values, rows, group, _f32(p), dict(sizes), m)


@functools.partial(jax.jit, static_argnames=("m", "sizes"))
def _logits(x, g, m, sizes):
    """Logits (R, V) with the mask id out of every choice."""
    sz = dict(sizes)
    logits = head(x, g, sz, m)[0]
    return jnp.where(jnp.arange(logits.shape[-1]) == sz["mask_id"],
                     -jnp.inf, logits)


@jax.jit
def _numbers(logits, served):
    """Per row: the best logit, the served token's, and the log of the
    softmax's sum (the row's confidence is the best less it)."""
    return (logits.max(-1),
            jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0],
            jax.nn.logsumexp(logits, axis=-1))


@jax.jit
def _at_argmax(ref, low):
    """The float32 logit of the token ``low`` puts first."""
    return jnp.take_along_axis(ref, jnp.argmax(low, -1)[:, None], 1)[:, 0]


def _clean(glob, layers, seq, sizes, m):
    """Every layer's K/V of the clean forward over ``seq`` (1, T)."""
    x = glob["wte"][seq]
    kv = []
    for p in layers:
        x, k, v = _clean_layer(x, p, m, sizes)
        kv.append((k, v))
    return kv


def _rows(glob, layers, kv, ids, rows, group, sizes, m):
    """The last hidden states of block rows."""
    x = glob["wte"][ids][None]
    for p, (k, v) in zip(layers, kv):
        x = _rows_layer(x, k, v, rows, group, p, m, sizes)
    return x


def _passes(glob, layers, kvs: dict, ids, rows, served, sizes) -> tuple:
    """Block rows, ``ids`` at positions ``rows`` (each block's ``L`` rows
    in one state a group), in passes of ``ROW_BUCKET`` rows. Over the
    float32 K/V ``kvs["f32"]``: per row, the gap of the ``served`` token
    below the best logit and the row's confidence (the best logit less
    the log of the softmax's sum); over each other mode's K/V: the gap of
    the token that mode puts first."""
    sz = dict(sizes)
    n = len(ids)
    total = -(-n // ROW_BUCKET) * ROW_BUCKET

    def pad(a, fill):
        return np.pad(a, (0, total - n), constant_values=fill)

    ids, rows, served = pad(ids, sz["mask_id"]), pad(rows, -1), pad(served, 0)
    group = np.arange(total) // sz["block_len"]
    gaps = {m: np.zeros(total, np.float32) for m in kvs}
    conf = np.zeros(total, np.float32)
    for lo in range(0, total, ROW_BUCKET):
        cut = slice(lo, lo + ROW_BUCKET)
        args = (jnp.asarray(ids[cut]), jnp.asarray(rows[cut]),
                jnp.asarray(group[cut]), sizes)
        ref = _logits(_rows(glob, layers, kvs["f32"], *args, "f32"), glob,
                      "f32", sizes)
        best, at, lse = _numbers(ref, jnp.asarray(served[cut]))
        gaps["f32"][cut], conf[cut] = best - at, best - lse
        for m, kv in kvs.items():
            if m != "f32":
                low = _logits(_rows(glob, layers, kv, *args, m), glob, m,
                              sizes)
                gaps[m][cut] = best - _at_argmax(ref, low)
    return {m: g[:n] for m, g in gaps.items()}, conf[:n]


def _contexts(masked: list, counts: list) -> list:
    """Every state a block's masked positions can be in at each step:
    ``[step][k]`` the positions committed before it, by any order the
    static schedule allows (``counts[s]`` committed at step ``s``)."""
    out, done = [], 0
    for c in counts:
        out.append([frozenset(x) for x in itertools.combinations(masked,
                                                                 done)])
        done += c
    return out


def _chains(masked: list, counts: list, before=frozenset(), step=0):
    """Every order of commitment: a tuple, step by step, of the positions
    committed before it."""
    if step == len(counts):
        yield ()
        return
    rest = [p for p in masked if p not in before]
    for chosen in itertools.combinations(rest, counts[step]):
        for tail in _chains(masked, counts, before | set(chosen), step + 1):
            yield (before,) + tail


def _orders(masked: list, counts: list, row_of) -> list:
    """Every order of commitment of a block's ``masked`` positions, as
    rows of the replay: ``at`` (the row that reads each position at the
    step that commits it), ``step`` (that step) and ``checks`` (per step,
    the rows it commits and the rows it leaves masked, both read in that
    step's state). ``row_of(step, state, p)`` is position ``p``'s row."""
    out = []
    for chain in _chains(masked, counts):
        after = list(chain[1:]) + [frozenset(masked)]
        step = [next(s for s, done in enumerate(after) if p in done)
                for p in masked]
        checks = []
        for s, state in enumerate(chain):
            taken = [row_of(s, state, p) for p in masked
                     if p in after[s] and p not in state]
            left = [row_of(s, state, p) for p in masked if p not in after[s]]
            if taken and left:
                checks.append((np.asarray(taken), np.asarray(left)))
        out.append({"at": np.asarray([row_of(s, chain[s], p)
                                      for p, s in zip(masked, step)]),
                    "step": np.asarray(step), "checks": checks})
    return out


def _pick(conf, orders: list, value, tie: float) -> dict:
    """Of a block's ``orders``, those the reference's confidences
    ``conf`` (per row) allow (at each step no position committed lies
    more than ``tie`` below one left masked: the reference's own order
    always is), the one under which the widest ``value`` (per row) is
    least."""
    return min((o for o in orders
                if all(conf[taken].min() >= conf[left].max() - tie
                       for taken, left in o["checks"])),
               key=lambda o: value[o["at"]].max())


def replay(sz: dict, key, seq, first: int, n: int, modes=(), glob=None,
           layers=None) -> dict:
    """The denoising of one served request replayed (the module's
    docstring): per served token (n,), its ``gap`` below the reference's
    best at the step that commits it, ``low[mode]`` for each of ``modes``
    (the gap of the token ``mode`` puts first), ``cut`` (its block the
    budget cut) and its ``block``. ``seq`` (1, T) holds the prompt and
    the served tokens from ``first`` on.

    The rows of every generated block in every state of its commitments
    that the static schedule allows (7 at 4 masked positions over 2
    steps) run in passes of ``ROW_BUCKET``; block by block, the served
    gaps are read under the allowed order whose widest gap is least, and
    each mode's under the allowed order whose widest gap of that mode is
    least."""
    sizes = _frozen(sz)
    glob = _globals(key, sizes) if glob is None else glob
    layers = layers or [_layer_params(key, i, sizes)
                        for i in range(sz["layers"])]
    length, steps, mask = sz["block_len"], sz["denoise_steps"], sz["mask_id"]
    # a cut block's positions past the budget read the padding
    seq_h = np.pad(np.asarray(seq)[0], (0, length))
    t = min(seq_h.shape[0] - length,
            -(-(first + n) // ROW_BUCKET) * ROW_BUCKET)
    seq_d = jnp.asarray(seq_h[None, :t])
    b0 = first // length * length
    n_blocks = -(-(first + n - b0) // length)
    gen = {b: [p for p in range(length)
               if first <= b0 + b * length + p < first + n]
           for b in range(n_blocks)}
    counts = {b: [len(m) // steps + (s < len(m) % steps)
                  for s in range(steps)] for b, m in gen.items()}
    # the rows of every state of every block at every step:
    # (step, block, state) -> the row of the block's first position
    first_row, ids, rows = {}, [], []
    for s in range(steps):
        for b in range(n_blocks):
            for state in _contexts(gen[b], counts[b])[s]:
                first_row[s, b, state] = len(ids)
                start = b0 + b * length
                ids += [mask if p in gen[b] and p not in state
                        else int(seq_h[start + p]) for p in range(length)]
                rows += range(start, start + length)
    ids, rows = np.asarray(ids, np.int32), np.asarray(rows, np.int32)
    served = seq_h[rows]
    orders = [_orders(gen[b], counts[b],
                      lambda s, state, p, b=b: first_row[s, b, state] + p)
              for b in range(n_blocks)]
    clean = {"f32": _clean(glob, layers, seq_d, sizes, "f32")}
    gaps, conf = _passes(glob, layers, clean, ids, rows, served, sizes)
    picked = [_pick(conf, o, gaps["f32"], sz["order_tie"]) for o in orders]
    kvs = dict(clean)
    for m in modes:
        dirty = seq_d
        if m == "dirty_close":
            # what the served order's last step committed still masked in
            # the K/V
            last = [b0 + b * length + p for b, o in enumerate(picked)
                    for p, s in zip(gen[b], o["step"]) if s == steps - 1]
            dirty = seq_d.at[0, jnp.asarray(last, jnp.int32)].set(mask)
        kvs[m] = _clean(glob, layers, dirty, sizes, m)
    if modes:
        gaps, _ = _passes(glob, layers, kvs, ids, rows, served, sizes)
    blocks = (np.arange(first, first + n) - b0) // length
    cut = np.zeros(n_blocks, bool)
    cut[-1] = bool((first + n) % length)
    return {"gap": gaps["f32"][np.concatenate([o["at"] for o in picked])],
            "low": {m: gaps[m][np.concatenate(
                [_pick(conf, o, gaps[m], sz["order_tie"])["at"]
                 for o in orders])] for m in modes},
            "cut": cut[blocks], "block": blocks}


def served_gaps_fn(sz: dict, key, mode: str = "f32"):
    """What ``check.served_gaps`` calls for each ``(seq, first, n)``:
    the served gaps and the mode's (n,) of :func:`replay`, nought in a
    trailing block the budget cut, and a line on standard error with how
    many tokens were compared."""
    sizes = _frozen(sz)
    glob = _globals(key, sizes)
    layers = [_layer_params(key, i, sizes) for i in range(sz["layers"])]
    modes = () if mode == "f32" else (mode,)

    def fn(seq, first, n):
        r = replay(sz, key, seq, first, n, modes, glob, layers)
        keep = ~r["cut"]
        low = r["low"].get(mode, np.zeros(n, np.float32))
        print(f"served_gaps sdar_moe: compared {int(keep.sum())} of {n} "
              f"served tokens (blocks the budget cut: "
              f"{int(r['cut'].sum())} tokens)", file=sys.stderr)
        return np.where(keep, r["gap"], 0.0), np.where(keep, low, 0.0)

    return fn
