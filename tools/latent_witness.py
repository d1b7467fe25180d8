"""On the chip, at the ``deepseek_v3`` cell's own size: WHOSE SIDE a second
witness takes at the served token that lies furthest below the reference's
best. One window of the cell through the harness's own run; the reference
over the sampled requests finds the worst token; then the PROGRAM, with the
same bfloat16 weights, is asked for that token again by two paths that
touch neither the serving pool nor its kernels:

- ``expanded``: the whole sequence up to that token in ONE forward without
  a cache (``LatentAttention``'s prefill arithmetic: per-head keys and
  values through the flash forward kernel; no latent row is read back);
- ``absorbed``: the sequence up to the last multiple of 1,024 as a prefill
  into ``generate()``'s linear latent rows, the rest as one chunk of
  ABSORBED attention over them (``W_kvb`` folded into the query, a dense
  read; not the pool, not ``flash_decode_grouped``, not
  ``latent_row_write``).

If both put the served token first, the latent read, the row write and the
pool write are not what moved it: bfloat16 arithmetic alone does. If they
put the reference's best first, the serving path is at fault. Then, layer
by layer at that position, the router's scores by the reference and by the
program's expanded forward (its own normed stream, captured): where the two
choose different experts, by how much the scores differ and how wide the
reference's own margin was. ``PERF.md`` section 2 has the readings.

    chiprun --timeout 1800 -- python tools/latent_witness.py \\
        --workload kanana-2-30b-a3b.report-backlog --seed 2700000011 \\
        --routed-out-unscaled

``--routed-out-unscaled`` draws the routed experts' output matrices at the
common deviation, as the cell's FIRST weights were (the reference's
``make_leaf`` divides it by ``routed_scaling_factor`` since). One JSON line
a reading, to stdout and ``chiprun_out/latent_witness.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmark import family, run, serving  # noqa: E402
from tools import long_ticks  # noqa: E402
from tools.route_tie_readings import emit, window  # noqa: E402

OUT = ROOT / "chiprun_out" / "latent_witness.jsonl"


def unscale_routed_out(ref) -> None:
    """The reference's leaves as they were first drawn: a routed expert's
    output matrix like any other weight."""
    drawn = ref.make_leaf
    ref.make_leaf = lambda key, shape, init, sz: drawn(
        key, shape, "weight" if init == "routed_out" else init, sz)


def reference_pass(ref, sz: dict, key, ids, pos: int):
    """The reference's logits at ``pos`` and, for every layer, the
    selection scores ``z + bias`` there (None in a dense layer) and
    whether its own routing was a near-tie."""
    import jax
    import jax.numpy as jnp

    from benchmark.references import mimo_v2_flash as mimo

    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(x, i, at, kind):
        p = ref.init_layer(key, sz, i, kind)
        out, near = ref.block(x, p, sz, kind, "f32")
        if kind != "routed":
            return out, near[0, at], jnp.zeros((sz["experts"],), jnp.float32)
        # block()'s own arithmetic again up to the router (one program:
        # the compiler keeps one copy of the attention)
        mid = x + ref.attention(
            mimo.rms_norm(x, p["ln1_g"], sz["eps"]), p, sz, "f32")
        h = mimo.rms_norm(mid, p["ln2_g"], sz["eps"])[0, at]
        z = jax.nn.sigmoid(jnp.matmul(h, p["router_w"], precision=ref.HI))
        return out, near[0, at], z + p["select_bias"]

    glob = jax.jit(lambda k: ref.init_globals(k, sz))(key)
    x = glob["wte"][ids]
    scores, near = [], []
    for i, kind in enumerate(sz["ffns"]):
        x, n, s = layer(x, i, pos, kind)
        near.append(bool(n))
        scores.append(np.asarray(s) if kind == "routed" else None)
    logits = jax.jit(lambda x, g: ref.head(x, g, sz, "f32")[0])(
        x[:, pos:pos + 1], glob)
    return np.asarray(logits[0]), scores, near


def expanded(graph, variables, ids, at):
    """One forward without a cache: the logits of row 0 at every position
    and every routed block's selection scores at ``at``."""
    import jax
    import jax.numpy as jnp

    x, scores = ids, {}
    for name, mod in graph.blocks:
        v = variables[name]
        if not getattr(mod, "routed", False):
            x = mod.apply(v, x)
            continue
        x, state = mod.apply(
            v, x, capture_intermediates=lambda m, _: m.name == "ln2")
        h = state["intermediates"]["ln2"]["__call__"][0][0, at]
        moe = v["params"]["moe"]
        z = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), moe["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        scores[name] = z + moe["select_bias"].astype(jnp.float32)
    return x[0].astype(jnp.float32), scores


def absorbed(graph, edge: int, upto: int, variables, ids):
    """Tokens ``0 .. edge - 1`` as a prefill into linear latent rows, the
    rest up to ``upto`` as ONE absorbed chunk over them: the chunk's
    logits."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.generate import _cached_apply, init_cache

    cache = init_cache(graph, variables, 1, ids.shape[1])
    _, cache = _cached_apply(graph, variables, ids[:, :edge], cache, 0)
    logits, _ = _cached_apply(graph, variables, ids[:, edge:upto], cache,
                              edge)
    return logits[0].astype(jnp.float32)


def program_expanded(graph, variables, ids, pos: int):
    """The program's logits at every position from one forward without a
    cache, and every routed block's selection scores at ``pos`` from the
    block's own normed stream."""
    import jax

    logits, scores = jax.jit(functools.partial(expanded, graph))(
        variables, ids, pos)
    return np.asarray(logits), {k: np.asarray(v) for k, v in scores.items()}


def program_absorbed(graph, variables, ids, upto: int, edge: int):
    """The program's logits at positions ``edge .. upto - 1`` from
    :func:`absorbed`."""
    import jax

    return np.asarray(jax.jit(functools.partial(absorbed, graph, edge, upto))(
        variables, ids))


def side(name: str, logits, ref_logits, served: int, **more) -> dict:
    """What a witness says at the token in question."""
    first, best = int(np.argmax(logits)), int(np.argmax(ref_logits))
    return dict(
        witness=name, puts_first=first, served=served, reference_best=best,
        takes=("the served token's side" if first == served else
               "the reference's side" if first == best else "neither side"),
        gap_of_its_first_below_the_references_best=float(
            ref_logits[best] - ref_logits[first]),
        its_own_logit_served_minus_reference_best=float(
            logits[served] - logits[best]), **more)


def routing_rows(sz: dict, ref_scores: list, got_scores: dict) -> list[dict]:
    first, count = sz["held"]
    k = sz["top_k"]
    rows = []
    for i, ref in enumerate(ref_scores):
        if ref is None:
            continue
        got = got_scores[f"block{i}"]
        order = np.argsort(-ref)
        theirs, ours = set(order[:k].tolist()), set(
            np.argsort(-got)[:k].tolist())
        swapped = sorted(theirs ^ ours)
        # how far the nearest HELD expert lies from the other side of the
        # reference's choice (the near-tie rule's own quantity)
        held = ref[first:first + count]
        far_side = np.where(held >= ref[order[k - 1]], held - ref[order[k]],
                            ref[order[k - 1]] - held)
        rows.append({
            "layer": i, "same_choice": theirs == ours,
            "reference_margin_last_chosen_to_first_left_out": float(
                ref[order[k - 1]] - ref[order[k]]),
            "reference_nearest_held_expert_to_the_far_side": float(
                far_side.min()),
            "its_score_gap": float((got - ref)[first + int(far_side.argmin())]),
            "score_gap_median": float(np.median(np.abs(got - ref))),
            "score_gap_max": float(np.abs(got - ref).max()),
            "swapped": swapped,
            "swapped_score_gaps": [float(got[e] - ref[e]) for e in swapped],
            "a_swapped_expert_is_held": any(
                first <= e < first + count for e in swapped)})
    return rows


def main(argv=None) -> int:
    import jax.numpy as jnp

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--routed-out-unscaled", action="store_true")
    args = ap.parse_args(argv)
    manifest = run.load_json(str(ROOT), "BENCHMARK.json")
    files = run.cell_files(manifest, args.workload, str(ROOT))
    run.chips_or_exit(1)
    run.compile_cache()
    fam = family.resolve(files["config"], files["mix"]["kind"],
                         files["control_mode"])
    ref, sz = fam.reference, fam.sz
    if args.routed_out_unscaled:
        unscale_routed_out(ref)
    state, samples, length = window(fam, files["mix"], args.seed,
                                    args.seconds)
    emit(OUT, workload=args.workload, seed=args.seed,
         routed_out_unscaled=args.routed_out_unscaled, run=state["numbers"],
         long_ticks=long_ticks.report(state, long_ticks.OVER_MS))

    # the worst served token of the sample
    key = family.seed_key(args.seed)
    fn = ref.served_gaps_fn(sz, key, "f32")
    worst = (-1.0, None, None)
    for j, (prompt, served) in enumerate(samples):
        seq = np.zeros((1, length), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + len(served)] = served
        gaps = np.asarray(fn(seq, len(prompt), len(served))[0])[:len(served)]
        if gaps.max() > worst[0]:
            worst = (float(gaps.max()), j, int(gaps.argmax()))
    gap, j, p = worst
    prompt, served = samples[j]
    upto = len(prompt) + p              # tokens fed; the next one is asked
    pos, token = upto - 1, int(served[p])
    step = min(1024, max(8, length // 4))
    padded = -(-upto // step) * step
    ids = np.zeros((1, padded), np.int32)
    ids[0, :len(prompt)] = prompt
    ids[0, len(prompt):upto] = served[:p]
    ids = jnp.asarray(ids)
    emit(OUT, worst_served_gap=gap, request=j, prompt_len=len(prompt),
         served_index=p, position=pos, token=token, padded=padded)

    ref_logits, ref_scores, near = reference_pass(ref, sz, key, ids, pos)
    best = int(np.argmax(ref_logits))
    emit(OUT, witness="reference", reference_best=best,
         gap_of_the_served_token=float(ref_logits[best] - ref_logits[token]),
         near_tie_in_layer=[i for i, n in enumerate(near) if n])

    variables = serving.build_weights(fam, args.seed)
    graph = serving.build_graph(fam)
    lo = len(prompt) - 1                # the first position that was served
    asked = np.asarray(ids[0, lo + 1:upto])
    logits, got_scores = program_expanded(graph, variables, ids, pos)
    emit(OUT, **side("program, expanded, no cache", logits[pos], ref_logits,
                token,
                agrees_with_what_was_served_before=float(
                    (logits[lo:pos].argmax(-1) == asked).mean())
                if pos > lo else None))
    for row in routing_rows(sz, ref_scores, got_scores):
        emit(OUT, **row)
    del logits

    edge = (pos // step) * step         # the chunk holds ``pos``
    if not edge:
        # a call from position 0 is a prefill, which expands
        emit(OUT, witness="program, absorbed over linear rows, no pool",
             takes="not read: the token lies inside the first chunk")
        return 0
    chunk = program_absorbed(graph, variables, ids, upto, edge)
    since = max(lo, edge)
    emit(OUT, **side("program, absorbed over linear rows, no pool", chunk[-1],
                ref_logits, token, chunk=upto - edge,
                agrees_with_what_was_served_before=float(
                    (chunk[since - edge:-1].argmax(-1)
                     == np.asarray(ids[0, since + 1:upto])).mean())
                if pos > since else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
