"""The comparisons that decide ``correct``: what the timed path produced,
held against the plain reference of the cell's family (``ref`` below: the
module that :func:`benchmark.family.resolve` found by the name in the
configuration's file). No family's equations are known here.

Serving: a seeded sample of the requests the window finished, the longest
among them. The reference runs once over each prompt with its served
tokens, and the number compared is the widest gap by which a served
token's reference logit lies below the reference's best at its position.

Training: the trainer's first three steps. Compared are each step's loss,
the first gradient's norm as the optimizer got it, and the norm of each
leaf's change over the three steps, by the worst leaf.

The CONTROL is the same reference with its linear layers in the nearest
precision below the configuration's (``mode``): its numbers have to fall
outside the limits. ``python -m benchmark.limits`` reads both on the chip.

Nothing here imports the program; the parameters are made anew from the
seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.family import seed_key

#: a leaf whose first reference gradient is under this share of the median
#: leaf's is nought to rounding (the key bias under softmax) and moves
#: under Adam by round-off alone: left out of the change comparison
DEAD_GRADIENT_SHARE = 1e-3


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the table printed with it: every number beside its
    limit. A number that is missing or not finite fails."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is not None and not np.isfinite(value):
            value = None
        fine = value is not None and value <= limit
        ok = ok and bool(fine)
        table[name] = {"value": value, "limit": limit}
    return ok, table


# -- serving -----------------------------------------------------------------


def sample_requests(finished: list, n: int, seed: int) -> list:
    """``n`` of the finished requests, drawn from the seed, the longest
    (prompt + served tokens) always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picked = [order[0]] + [rest[i] for i in
                           rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [finished[i] for i in picked]


def served_gaps(ref, sz: dict, seed: int, samples: list, length: int,
                mode: str = "f32") -> dict:
    """``samples`` is a list of (prompt ids, served token ids). Returns
    ``served_gap``: the widest gap of a served token below the reference's
    best; with a ``mode`` other than ``f32`` also ``control_gap``: the
    widest gap of the token that ``mode`` puts first, at the same
    positions. Every sequence is padded to ``length`` (causality hides
    the padding), so the reference can compile one program for all
    samples; how it holds its parameters is its own affair."""
    fn = ref.served_gaps_fn(sz, seed_key(seed), mode)
    worst_served, worst_control, tokens = 0.0, 0.0, 0
    for prompt, served in samples:
        n = len(served)
        if n == 0:
            continue
        seq = np.zeros((1, length), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + n] = served
        s, c = fn(jnp.asarray(seq), len(prompt), n)
        s, c = np.asarray(s)[:n], np.asarray(c)[:n]
        worst_served = max(worst_served, float(s.max()))
        worst_control = max(worst_control, float(c.max()))
        tokens += n
    out = {"served_gap": worst_served if tokens else None,
           "tokens_compared": tokens, "requests_compared": len(samples)}
    if mode != "f32":
        out["control_gap"] = worst_control if tokens else None
    return out


# -- training ----------------------------------------------------------------


def adam_step(params, m, v, grads, step: int, lr: float,
              b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """One Adam update (Kingma & Ba 2015, with bias correction); ``step``
    counts from 1."""
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(
        lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree_util.tree_map(
        lambda p, a, s: p - lr * (a / c1) / (jnp.sqrt(s / c2) + eps),
        params, m, v)
    return params, m, v


def leaf_norms(ref, tree: dict) -> dict:
    """The norm of every leaf of ``tree``, at the grain the reference cuts
    its leaves to (``ref.split_leaves``)."""
    return {k: float(np.linalg.norm(v))
            for k, v in ref.split_leaves(tree).items()}


def reference_steps(ref, sz: dict, seed: int, batches: list, lr: float,
                    mode: str = "f32", rows_per_block: int = 4,
                    fault: str | None = None) -> dict:
    """Follow ``batches`` (a list of (x, y) int arrays, one per step) from
    the seed's parameters with plain Adam. Gradients are summed over
    blocks of rows so that a step fits beside the optimizer's state.

    ``fault`` plants one of the faults a training cell can have, for the
    readings the limits are set from: ``half_batch`` (the second half of
    every batch left out, the mean taken over the rest), ``no_exchange``
    (only the first quarter of the batch: what one of four chips sees
    when the gradient exchange is left out)."""
    params = jax.jit(lambda k: ref.init_params(k, sz))(seed_key(seed))
    start = jax.device_get(params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss_sum, sz=sz, mode=mode)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    update = jax.jit(functools.partial(adam_step, lr=lr),
                     static_argnames=("step",), donate_argnums=(0, 1, 2))
    scale = jax.jit(lambda t, s: jax.tree_util.tree_map(lambda a: a / s, t))
    # blocks of rows go round the local chips (dispatch does not wait), the
    # sums come back to the first: the same arithmetic on one chip or four
    chips = jax.local_devices()
    losses, first_grad = [], None
    for step, (x, y) in enumerate(batches, start=1):
        if fault == "half_batch":
            x, y = x[:len(x) // 2], y[:len(y) // 2]
        elif fault == "no_exchange":
            x, y = x[:len(x) // 4], y[:len(y) // 4]
        copies = [params] + [jax.device_put(params, d) for d in chips[1:]]
        sums, block_losses = [None] * len(chips), []
        for i, lo in enumerate(range(0, len(x), rows_per_block)):
            j = i % len(chips)
            loss, g = grad_fn(
                copies[j], jax.device_put(x[lo:lo + rows_per_block], chips[j]),
                jax.device_put(y[lo:lo + rows_per_block], chips[j]))
            block_losses.append(loss)
            sums[j] = g if sums[j] is None else add(sums[j], g)
        del copies
        total = sum(float(loss) for loss in block_losses)
        grads = None
        for g in sums:
            if g is not None:
                g = jax.device_put(g, chips[0])
                grads = g if grads is None else add(grads, g)
        del sums
        tokens = float(x.size)
        grads = scale(grads, tokens)
        losses.append(total / tokens)
        if step == 1:
            first_grad = jax.device_get(grads)
        params, m, v = update(params, m, v, grads, step=step)
    end = jax.device_get(params)
    return {"losses": losses, "first_grad": first_grad,
            "start": start, "end": end}


def train_gaps(ref, ref_run: dict, losses: list, first_grad_norm: float,
               end_params: dict) -> dict:
    """The program's three numbers against the reference's run.

    ``loss_gap``: the widest relative gap of a step's loss.
    ``grad_gap``: the relative gap of the first gradient's global norm,
    as the trainer reports it before the optimizer's update.
    ``delta_gap``: by the worst leaf, the gap between the norm of the
    program's change over the steps and the reference's, against the
    reference's norm of that leaf's change or of the median leaf's,
    whichever is larger. Leaves with a dead gradient are left out."""
    ref_losses = ref_run["losses"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    g = leaf_norms(ref, ref_run["first_grad"])
    g_global = float(np.sqrt(sum(n * n for n in g.values())))
    grad_gap = abs(first_grad_norm - g_global) / g_global
    start = ref.split_leaves(ref_run["start"])
    ref_end = ref.split_leaves(ref_run["end"])
    got_end = ref.split_leaves(end_params)
    g_median = float(np.median(list(g.values())))
    counted = [k for k in g if g[k] >= DEAD_GRADIENT_SHARE * g_median]
    ref_delta = {k: float(np.linalg.norm(ref_end[k] - start[k]))
                 for k in counted}
    d_median = float(np.median(list(ref_delta.values())))
    worst, worst_leaf = 0.0, None
    for k in counted:
        got = float(np.linalg.norm(got_end[k] - start[k]))
        gap = abs(got - ref_delta[k]) / max(ref_delta[k], d_median)
        if gap > worst:
            worst, worst_leaf = gap, k
    return {"loss_gap": float(loss_gap), "grad_gap": float(grad_gap),
            "delta_gap": worst, "delta_worst_leaf": worst_leaf,
            "leaves_counted": len(counted),
            "leaves_left_out": len(g) - len(counted),
            "losses": [float(a) for a in losses],
            "ref_losses": [float(b) for b in ref_losses]}
