"""Expert parallelism: sparse mixture-of-experts dispatch over a mesh axis.

No reference counterpart (SURVEY.md §2.5 — data parallelism is the
reference's only strategy); expert parallelism is part of the first-class
distributed design the TPU build adds.

Design (the standard TPU MoE recipe — Switch/GShard style, expressed with
GSPMD rather than hand-written all-to-alls):

- expert FFN params are *stacked* on a leading dim of size ``n_experts``
  and sharded over the ``expert`` mesh axis (rule set
  :data:`EXPERT_RULES`) — each device group holds ``n_experts / E`` experts;
- routing is top-k softmax gating with capacity-bounded dispatch: tokens
  are scattered into a ``(n_experts, capacity, d)`` buffer via one-hot
  matmuls (MXU-friendly — no dynamic shapes, no sorts inside jit),
  experts run as one batched ``einsum`` over the stacked dim, and results
  gather back weighted by the gate probabilities;
- with the dispatch tensor sharded ``(expert, None, None)`` and token
  activations sharded on ``data``, GSPMD compiles the scatter/gather into
  the all-to-alls over ICI — the collectives are derived, not written;
- tokens overflowing an expert's capacity are dropped (standard Switch
  behavior); the residual connection keeps dropped tokens lossless in the
  block output.

Everything is fixed-shape and differentiable; the auxiliary load-balancing
loss (Switch §2.2 form: ``n_experts * Σ_e f_e · p_e``) is returned alongside
the output for the trainer to add.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import ParamError
from mmlspark_tpu.parallel.mesh import EXPERT_AXIS

#: param-sharding rules placing the stacked expert dim on the ``expert``
#: mesh axis (leading dim of every leaf under an ``experts`` module).
EXPERT_RULES: list[tuple[str, tuple]] = [
    (r"/experts/", (EXPERT_AXIS,)),
]


def router_probs(x, gate_w):
    """Softmax router over experts. x: (B, T, D); gate_w: (D, E)."""
    # float32 routing regardless of compute dtype: gate decisions are
    # precision-sensitive
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def moe_dispatch(probs, capacity: int, mask=None):
    """Build dispatch/combine tensors from router probabilities.

    probs: (N, E) per-token expert probabilities (tokens already flattened);
    mask: optional (N,) 0/1 real-token mask — padding tokens route nowhere,
    consume no expert capacity, and are excluded from the balance loss
    (the primary loss masks them too, trainer.masked_loss).
    Returns ``(dispatch, combine, aux_loss)`` where dispatch is a boolean
    (N, E, C) scatter mask, combine is its gate-weighted float version, and
    aux_loss is the Switch load-balancing loss.
    """
    n, e = probs.shape
    expert = jnp.argmax(probs, axis=-1)  # top-1 routing
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # (N, E)
    if mask is not None:
        onehot = onehot * mask.astype(jnp.float32)[:, None]
    # position of each token within its expert's queue (exclusive cumsum)
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot  # (N, E)
    kept = (pos < capacity) * onehot  # overflow tokens dropped
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)  # (N, E, C)
    dispatch = kept[..., None] * slot  # (N, E, C)
    gate = (probs * kept).sum(-1)  # chosen-expert prob, 0 when dropped
    combine = dispatch * gate[:, None, None]
    # Switch load-balance loss over real tokens: routed fraction vs mean
    # router prob
    n_real = jnp.maximum(onehot.sum(), 1.0)
    frac = onehot.sum(0) / n_real
    if mask is not None:
        w = mask.astype(jnp.float32)[:, None]
        mean_prob = (probs * w).sum(0) / jnp.maximum(w.sum(), 1.0)
    else:
        mean_prob = probs.mean(0)
    aux = e * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w_in, b_in, w_out, b_out, *,
            capacity_factor: float = 1.25, mask=None,
            group_size: int = 1024):
    """Top-1 switch FFN. x: (B, T, D); w_in: (E, D, F); w_out: (E, F, D);
    mask: optional (B,) real-row mask (padding rows route nowhere).

    Tokens route in fixed-size groups (the GShard/Switch recipe): capacity
    is bounded per group, so the (G, S, E, C) dispatch/combine tensors stay
    LINEAR in the token count instead of quadratic — the all-token variant
    would be O(N²) memory and overflow HBM at production batch×seq.

    Returns (out, aux_loss). Compute dtype follows ``x``; routing and the
    dispatch einsums run float32.
    """
    b, t, d = x.shape
    e = w_in.shape[0]
    n = b * t
    flat = x.reshape(n, d)
    tok_mask = (
        jnp.repeat(mask.astype(jnp.float32), t)
        if mask is not None
        else jnp.ones(n, jnp.float32)
    )
    # pad the token dim up to a multiple of the group size: masked padding
    # tokens route nowhere and consume no capacity, so group size stays at
    # the target for ANY batch x seq shape (a divisor-of-n scheme
    # degenerates to 1-token groups when n is prime, making the capacity
    # bound vacuous)
    s = min(group_size, n)
    pad = (-n) % s
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        tok_mask = jnp.pad(tok_mask, (0, pad))
    g = (n + pad) // s
    capacity = max(int(capacity_factor * s / e), 1)
    probs = router_probs(flat, gate_w).reshape(g, s, e)
    gmask = tok_mask.reshape(g, s)
    dispatch, combine, aux = jax.vmap(
        lambda p, m: moe_dispatch(p, capacity, m)
    )(probs, gmask)
    aux = aux.mean()
    grouped = flat.reshape(g, s, d)
    # scatter: (G, S, E, C) × (G, S, D) -> (G, E, C, D); sharded over
    # `expert`, GSPMD turns this into the dispatch all-to-all
    buf = jnp.einsum("gsec,gsd->gecd", dispatch,
                     grouped.astype(jnp.float32)).astype(x.dtype)
    h = jnp.einsum("gecd,edf->gecf", buf, w_in.astype(x.dtype))
    h = jax.nn.gelu(h + b_in[None, :, None, :].astype(x.dtype))
    y = jnp.einsum("gecf,efd->gecd", h, w_out.astype(x.dtype))
    y = y + b_out[None, :, None, :].astype(x.dtype)
    # gather back, gate-weighted; drop the padding tokens
    out = jnp.einsum("gsec,gecd->gsd", combine, y.astype(jnp.float32))
    out = out.reshape((n + pad), d)[:n]
    return out.reshape(b, t, d).astype(x.dtype), aux


def moe_ffn_dropless(x, gate_w, w_in, b_in, w_out, b_out):
    """Dropless top-1 routing for DECODE steps (models/generate.py).

    Capacity-bounded dispatch exists to keep training-scale token counts
    fixed-shape and balanced; at decode there are only B tokens (one per
    sequence) and dropping any of them would corrupt the stream outright.
    Each token instead gathers its argmax expert's weights directly —
    (B, D, F) per-token weight reads, trivially affordable at decode
    batch sizes — and the output is gate-prob scaled exactly like the
    capacity path scales kept tokens, so wherever the capacity path
    drops nothing the two are numerically equivalent (tested in
    tests/test_moe.py). No aux loss: routing balance is a training
    concern."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    probs = router_probs(flat, gate_w)  # (N, E) float32
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    h = jnp.einsum("nd,ndf->nf", flat.astype(x.dtype),
                   w_in[expert].astype(x.dtype))
    h = jax.nn.gelu(h + b_in[expert].astype(x.dtype))
    y = jnp.einsum("nf,nfd->nd", h, w_out[expert].astype(x.dtype))
    y = y + b_out[expert].astype(x.dtype)
    out = y.astype(jnp.float32) * gate[:, None]
    return out.reshape(b, t, d).astype(x.dtype)


#: the routers' score kinds: ``sigmoid`` with a selection bias (the
#: DeepSeek-V3 line), ``softmax`` over all experts (the Qwen3-MoE line)
SIGMOID, SOFTMAX = "sigmoid", "softmax"


def router_topk(x, router_w, select_bias, top_k: int, scale: float = 1.0,
                score: str = SIGMOID):
    """Sigmoid scores, the choice by score plus a selection bias, the
    weights by score alone (the "noaux_tc" router of the DeepSeek-V3
    line, one group). ``x`` is (N, D); ``router_w`` (D, E);
    ``select_bias`` (E,). Returns ``(experts (N, top_k) int32, weights
    (N, top_k) float32)``: the ``top_k`` largest of ``z + bias``, and
    ``scale * z_e / (sum of the chosen z + 1e-20)`` (``scale`` is the
    line's ``routed_scaling_factor``, applied after the normalisation).
    The bias moves the choice and never a weight. All of it in float32,
    the product at full precision: a choice is a step, not a rounding.

    ``score="softmax"``: ``r = softmax(x W)`` over all ``E`` experts, the
    ``top_k`` largest (taken on the logits, whose order softmax keeps
    without rounding them together), weights ``r_e`` over the sum of the
    chosen ``r`` (``norm_topk_prob``), times ``scale``; no selection
    bias (``select_bias`` may be None)."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == SOFTMAX:
        _, experts = jax.lax.top_k(logits, top_k)
        chosen = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                     experts, axis=-1)
        weights = chosen / chosen.sum(axis=-1, keepdims=True)
        if scale != 1.0:
            weights = weights * jnp.float32(scale)
        return experts.astype(jnp.int32), weights
    if score != SIGMOID:
        raise ParamError(
            f"router scores are '{SIGMOID}' or '{SOFTMAX}', got {score!r}")
    z = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(z + select_bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(z, experts, axis=-1)
    weights = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * jnp.float32(scale)
    return experts.astype(jnp.int32), weights


#: an expert's row tile is the smallest power of two, 16 (a bfloat16
#: sublane tile) to 512, that holds this many times the MEAN of what an
#: expert receives, ``tokens * top_k / E``. On a v5e, us for a layer's
#: three products under an even routing (``tools/hybrid_chip_check.py
#: time``, my chip run, PR 36; the mean rows an expert, then the tile in
#: brackets):
#:
#:     step                          mean   half     CHOSEN      twice   4 times
#:     lfm2-8b-a1b decode, 128         16   1,344     966 (32)     986    1,027
#:     lfm2-8b-a1b prefill, 512        64   1,415   1,027 (128)  1,159    2,089
#:     kanana-2-30b-a3b decode, 64      3       -     196 (16)     200      210
#:     kanana-2-30b-a3b prefill, 2,048 96     198     291 (256)    464        -
#:     mimo-v2-flash decode, 64         2       -     818 (16)     826      841
#:     mimo-v2-flash prefill, 2,048    64   1,703   1,194 (128)  1,385    2,387
#:
#: A tile AT the mean sends half the experts to a second tile, whose
#: weights are read again; a wider one multiplies pad rows, which costs
#: little at decode (the weights' bytes bound it) and much at a prefill.
#: (kanana's prefill at half: 2.7 times the mean rounds to 1.3 there,
#: and an even draw of 2,048 tokens still fits it: not a rule to lean on.)
_TILE_OVER_MEAN = 2


def held_tiles(tokens: int, held: int, top_k: int,
               experts: int) -> tuple[int, int]:
    """The row tile of :func:`moe_ffn_held`'s grouped products for
    ``tokens`` tokens that each choose ``top_k`` of ``experts`` experts,
    and the most tiles the ``held`` experts' pairs can fill once every
    expert's rows are padded to whole tiles.

    The tile follows what ONE expert is expected to receive, not the
    step's tokens: a product's time is its weights read once a tile and
    the tile's rows multiplied, pad rows too, so a tile far over an
    expert's share multiplies (and gathers, and gates) mostly padding.
    ``_TILE_OVER_MEAN`` times the mean leaves an evenly routed expert in
    one tile. SKEW costs weights: an expert with more rows than a tile
    takes a second tile and its weight blocks are read again (where the
    block is the whole matrix they are found in place), so a hot expert
    at ``r`` times the tile costs ``ceil(r)`` reads of its matrices, as
    much as that many experts hit: with every token's first choice ONE
    expert, +9% at ``lfm2-8b-a1b``'s decode step (35 tiles for 32) and
    +93% at ``mimo-v2-flash``'s prefill of 2,048 (31 for 16; my chip
    run, PR 36). It costs no correctness: the layer is dropless at
    every routing.

    ``most`` is by two bounds: a token chooses an expert at most once,
    so an expert has at most ``tokens`` rows, and all of them together
    at most ``tokens * min(top_k, held)``, which leaves at most one
    partly filled tile an expert."""
    mean = tokens * top_k / experts
    tm = 16
    while tm < 512 and tm < _TILE_OVER_MEAN * mean:
        tm *= 2
    most = min(held * -(-tokens // tm),
               held + tokens * min(top_k, held) // tm)
    return tm, most


def moe_ffn_held(x, router_w, select_bias, w_gate, w_up, w_down, *,
                 top_k: int, first: int, valid=None, scale: float = 1.0,
                 interpret: bool | None = None, score: str = SIGMOID,
                 limit: float = 0.0):
    """The part of a routed SwiGLU layer that THIS holder's experts give.

    The router scores all ``E`` experts (``router_w`` (D, E)) and every
    token takes its ``top_k`` (:func:`router_topk`); this holder has the
    experts ``first .. first + held - 1`` (``w_gate``/``w_up`` (held, D,
    F), ``w_down`` (held, F, D)), computes ``w_e * SwiGLU_e(x)`` for the
    (token, expert) pairs that fell on them and adds nothing for the
    rest: summed over the holders of all ``E`` experts that is the whole
    layer (expert parallelism's contract; on one chip there is no
    exchange to run). ``x`` is (B, T, D); ``valid`` ((B, T) bool) marks
    the real tokens: a pad or a dead row routes nowhere. ``scale``
    multiplies every routing weight and ``score`` is the router's kind
    (:func:`router_topk`). ``limit`` > 0 clamps every expert's SwiGLU:
    ``silu(min(g, limit)) * clip(u, -limit, limit)``.

    Dropless at every length and fixed in shape: the held pairs are
    ranked inside their expert, every expert's rows are padded to whole
    row tiles, and three grouped products
    (:func:`mmlspark_tpu.ops.grouped_matmul.grouped_matmul`) run over
    the tiles that are live. No (tokens, D, F) weight copy is made, and
    an expert that no token chose is not read.

    Returns ``(out (B, T, D) in x's dtype, {"pairs", "hit", "rows"})``:
    the pairs that fell on held experts, the held experts hit, and the
    rows the products multiplied (live tiles x the row tile of
    :func:`held_tiles`): pairs over rows is how full the tiles were."""
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul

    b, t, d = x.shape
    held = w_gate.shape[0]
    n = b * t
    flat = x.reshape(n, d)
    experts, weights = router_topk(flat, router_w, select_bias, top_k,
                                   scale, score)
    local = experts - first
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid.reshape(n)[:, None]
    pairs = n * top_k
    group = jnp.where(mine, local, held).reshape(pairs)
    member = group[:, None] == jnp.arange(held)[None, :]       # (P, held)
    sizes = member.sum(axis=0).astype(jnp.int32)
    tm, most = held_tiles(n, held, top_k, router_w.shape[1])
    padded = -(-sizes // tm) * tm
    pad_end = jnp.cumsum(padded)
    # a pair's row: its expert's first row plus its rank inside the expert
    rank = jnp.take_along_axis(
        jnp.cumsum(member, axis=0, dtype=jnp.int32) - 1,
        jnp.minimum(group, held - 1)[:, None], axis=1,
    )[:, 0]
    mine_flat = mine.reshape(pairs)
    row = jnp.where(
        mine_flat, (pad_end - padded)[jnp.minimum(group, held - 1)] + rank, 0
    )
    rows = most * tm
    token_of_row = jnp.zeros((rows,), jnp.int32).at[
        jnp.where(mine_flat, row, rows)
    ].set(jnp.arange(pairs, dtype=jnp.int32) // top_k, mode="drop")
    tile_group = jnp.searchsorted(
        pad_end, jnp.arange(most, dtype=jnp.int32) * tm, side="right"
    )
    live_tiles = pad_end[-1] // tm
    gmm = partial(grouped_matmul, tile_group=tile_group,
                  live_tiles=live_tiles, tm=tm, interpret=interpret)
    xs = flat[token_of_row].astype(w_gate.dtype)
    gate = gmm(xs, w_gate, name="moe_gate")
    up = gmm(xs, w_up, name="moe_up")
    if limit:
        gate = jnp.minimum(gate.astype(jnp.float32), limit)
        up = jnp.clip(up.astype(jnp.float32), -limit, limit)
    mid = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(w_down.dtype)
    y = gmm(mid, w_down, name="moe_down")
    # back to the tokens: each pair reads its own row; what fell
    # elsewhere reads nothing (a where, not a product with nought: a
    # dead tile's rows were never written and may hold anything)
    mine_w = jnp.where(mine, weights, 0.0)
    picked = jnp.where(mine_flat[:, None], y[row].astype(jnp.float32), 0.0)
    out = (picked.reshape(n, top_k, d) * mine_w[:, :, None]).sum(axis=1)
    counters = {"pairs": mine.sum().astype(jnp.int32),
                "hit": (sizes > 0).sum().astype(jnp.int32),
                "rows": (live_tiles * tm).astype(jnp.int32)}
    return out.reshape(b, t, d).astype(x.dtype), counters


def validate_experts(n_experts: int, mesh=None) -> None:
    if n_experts < 2:
        raise ParamError(f"need >= 2 experts, got {n_experts}")
    if (
        mesh is not None
        and EXPERT_AXIS in mesh.shape
        and n_experts % mesh.shape[EXPERT_AXIS]
    ):
        raise ParamError(
            f"n_experts {n_experts} not divisible by mesh axis "
            f"'{EXPERT_AXIS}' ({mesh.shape[EXPERT_AXIS]})"
        )
