"""On the chip: each kernel that ``hybrid_lm`` brought, compiled, against
its plain-XLA oracle at the benchmark cell's shapes, then the model's own
forward and its served tokens against the family's reference at the
published widths. One JSON line a check; exits 1 if any is off.

    chiprun -- python tools/hybrid_chip_check.py [kernels] [model] [time]

(``grouped`` is the grouped product's share of ``kernels`` alone.)

``time`` (asked for by name; about 4 minutes) times an expert layer's
three grouped products ALONE at the three routed cells' shapes, a decode
micro-step and one prefill bucket each, under an even routing drawn once:
us a call of ``moe_gate``, ``moe_up`` and ``moe_down`` and of what XLA
fuses around them, at the row tile and the weight block the rules choose
(``parallel/expert.held_tiles``, ``ops/grouped_matmul._blocks``), at
other tiles and blocks, and, where the parent commit's tree lies in
``.parent_tree/`` (``git archive``), through the PARENT'S kernel at the
parent's tile. A line a variant, to stdout and
``chiprun_out/grouped_matmul_timing.jsonl``: what ``_TILE_OVER_MEAN`` and
``_GMM_VMEM`` rest on; run it again before changing either or the kernel.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

FAILED = []


def report(name: str, gap: float, limit: float, **more) -> None:
    ok = bool(np.isfinite(gap) and gap <= limit)
    if not ok:
        FAILED.append(name)
    print(json.dumps({"check": name, "gap": float(gap), "limit": limit,
                      "ok": ok, **more}), flush=True)


def gap(a, b) -> float:
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def kernels() -> None:
    from mmlspark_tpu.ops.attention import dense_attention
    from mmlspark_tpu.ops.flash_attention import (
        cache_row_write,
        flash_attention,
        flash_decode_grouped,
    )

    key = jax.random.PRNGKey(0)
    h, dk, dv = 64, 192, 128
    for kind, hk, rows, window in (("full", 4, 4096, None),
                                   ("swa", 8, 128, 128)):
        b = 8
        kq, kk, kv, ks = jax.random.split(jax.random.fold_in(key, hk), 4)
        q = jax.random.normal(kq, (b, 1, h, dk), jnp.bfloat16)
        k = jax.random.normal(kk, (b, hk, rows, dk), jnp.bfloat16)
        v = jax.random.normal(kv, (b, hk, rows, dv), jnp.bfloat16)
        sink = jax.random.normal(ks, (h,), jnp.float32) if window else None
        lengths = jnp.asarray([1, 7, 128, 129, 1000, 2047, 4095, 4096])
        lengths = jnp.minimum(lengths, rows)
        got = jax.jit(lambda q, k, v, n, s: flash_decode_grouped(
            q, k, v, n, sink=s, name=f"attn_{kind}_decode"))(
                q, k, v, lengths, sink)
        # the oracle: linear rows, each row's own length
        want = []
        for i, n in enumerate(np.asarray(lengths)):
            want.append(dense_attention(
                q[i:i + 1], jnp.moveaxis(k[i:i + 1, :, :n], 1, 2),
                jnp.moveaxis(v[i:i + 1, :, :n], 1, 2), sink=sink)[0])
        report(f"flash_decode_grouped.{kind}", gap(got, jnp.stack(want)),
               3e-2)
        # the row write
        kn = jax.random.normal(kq, (b, hk, dk), jnp.bfloat16)
        vn = jax.random.normal(kk, (b, hk, dv), jnp.bfloat16)
        at = jnp.asarray([0, 5, 17, 127, 64, 33, 100, 15]) % rows
        k2, v2 = jax.jit(cache_row_write, donate_argnums=(0, 1))(
            jnp.copy(k), jnp.copy(v), kn, vn, at)
        rows_i = jnp.arange(b)
        wk = k.at[rows_i, :, at].set(kn)
        wv = v.at[rows_i, :, at].set(vn)
        report(f"cache_row_write.{kind}", max(gap(k2, wk), gap(v2, wv)), 0.0)
        # the forward kernel
        # None: the block the module chooses, as the model's prefill runs
        for t, block in ((512, None), (1024, None), (2048, None), (384, 128)):
            q = jax.random.normal(kq, (1, t, h, dk), jnp.bfloat16)
            k = jax.random.normal(kk, (1, t, hk, dk), jnp.bfloat16)
            v = jax.random.normal(kv, (1, t, hk, dv), jnp.bfloat16)
            got = jax.jit(lambda q, k, v, s: flash_attention(
                q, k, v, causal=True, window=window, sink=s,
                block=block))(q, k, v, sink)
            want = dense_attention(q, k, v, causal=True, window=window,
                                   sink=sink)
            report(f"flash_forward.{kind}.{t}", gap(got, want), 3e-2)
    grouped()


def grouped() -> None:
    """The grouped product at the decode step's and a prefill's shapes."""
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(0)
    # reason-backlog's tiles: 64 tokens and a bucket of 2,048, 8 of 256
    for m, tm, live in ((48 * 16, 16, 11), (144 * 128, 128, 9)):
        tiles = m // tm
        x = jnp.asarray(rng.normal(size=(m, 4096)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(16, 4096, 2048)) * 0.02,
                        jnp.bfloat16)
        group = jnp.asarray(np.sort(rng.integers(0, 16, tiles)), jnp.int32)
        got = jax.jit(lambda x, w, g, n: grouped_matmul(
            x, w, g, n, tm=tm))(x, w, group, live)
        want = jnp.concatenate([
            jnp.dot(x[i * tm:(i + 1) * tm], w[int(group[i])],
                    preferred_element_type=jnp.float32)
            for i in range(live)])
        # a dead tile takes no grid step: its rows are never written
        report(f"grouped_matmul.{m}", gap(got[:live * tm], want), 2e-2)


def model(seed: int = 5) -> None:
    from benchmark import check, family, run
    from benchmark.serving import build_graph, build_weights

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    files = run.cell_files(manifest, "mimo-v2-flash.reason-backlog")
    fam = family.resolve(files["config"], "backlog", "int8")
    ref, sz = fam.reference, fam.sz
    variables = build_weights(fam, seed)
    graph = build_graph(fam)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sz["v"], (1, 640)).astype(np.int32)
    got = jax.jit(graph.apply)(variables, jnp.asarray(ids))[0]
    key = family.seed_key(seed)
    glob = jax.jit(lambda k: ref.init_globals(k, sz))(key)
    x = glob["wte"][jnp.asarray(ids)]
    for i in range(sz["layers"]):
        x, _ = jax.jit(lambda x, k, i=i: ref.block(
            x, ref.init_layer(k, sz, i), sz, i, "f32"))(x, key)
    want = jax.jit(lambda x, g: ref.head(x, g, sz, "f32"))(x, glob)[0]
    report("forward.logits", gap(got, want), 0.15,
           logit_std=float(jnp.std(want)))
    agree = float((jnp.argmax(got, -1) == jnp.argmax(want, -1)).mean())
    report("forward.argmax_disagree", 1.0 - agree, 0.02)
    # prefill then decode through the pool
    from mmlspark_tpu.serve.engine import ServeEngine

    engine = ServeEngine(graph, variables, slots=8, cache_len=1024)
    prompts = [ids[0, :n] for n in (100, 128, 300, 640)]
    rids = [engine.submit(p, max_new_tokens=160) for p in prompts]
    results = engine.run()
    del engine
    samples = [(p, np.asarray(results[r].tokens[len(p):], np.int32))
               for p, r in zip(prompts, rids)]
    numbers = check.served_gaps(ref, sz, seed, samples, 1024)
    report("served_gap", numbers["served_gap"], 0.06,
           tokens=numbers["tokens_compared"])


# -- the expert layer's three products, timed alone ----------------------------

#: (cell, slots, a prefill bucket, top_k, experts, held, d_model, expert_d_ff)
ROUTED_CELLS = (
    ("synth-backlog", 128, 512, 4, 32, 32, 2048, 1792),
    ("report-backlog", 64, 2048, 6, 128, 16, 2048, 768),
    ("reason-backlog", 64, 2048, 8, 256, 16, 4096, 2048),
)


def parent_tiles(tokens: int, held: int, top_k: int) -> tuple[int, int]:
    """The row tile before PR 36: by the step's tokens."""
    tm = min(512, -(-tokens // 16) * 16)
    return tm, min(held * -(-tokens // tm),
                   held + tokens * min(top_k, held) // tm)


def parent_kernel():
    """The parent commit's ``grouped_matmul`` out of ``.parent_tree/``, or
    None where no such tree lies there."""
    import importlib.util

    path = os.path.join(ROOT, ".parent_tree", "mmlspark_tpu", "ops",
                        "grouped_matmul.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_gmm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.grouped_matmul


def time_products(cells=()) -> None:
    from flash_block_timing import traced_ops
    from mmlspark_tpu.ops import grouped_matmul as gm
    from mmlspark_tpu.parallel.expert import held_tiles

    out = os.path.join(ROOT, "chiprun_out", "grouped_matmul_timing.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]
    parent = parent_kernel()
    interpret = jax.devices()[0].platform != "tpu"   # a rehearsal
    rng = np.random.default_rng(0)

    def emit(**row):
        line = json.dumps(row)
        print(line, flush=True)
        with open(out, "a") as f:
            f.write(line + "\n")

    def layer(kernel, tm, most, sizes, d, blocks):
        """The three products as ``moe_ffn_held`` calls them, over the
        tiles that ``sizes`` rows an expert fill; ``blocks`` is ``{"up":
        (tk, tn), "down": ...}``, or None for the kernel's own choice."""
        tiles = -(-sizes // tm)
        live = int(tiles.sum())
        assert live <= most, (live, most)
        group = np.repeat(np.arange(sizes.size), tiles)
        group = np.concatenate([group, np.full(most - live, sizes.size - 1)])

        def products(xs, w_gate, w_up, w_down, group, live):
            def one(x, w, name, kind):
                more = dict(zip(("tk", "tn"), blocks[kind])) if blocks else {}
                return kernel(x, w, group, live, tm=tm, name=name,
                              interpret=interpret, **more)

            gate = one(xs, w_gate, "moe_gate", "up")
            up = one(xs, w_up, "moe_up", "up")
            mid = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(xs.dtype)
            return one(mid, w_down, "moe_down", "down")

        xs = jnp.asarray(rng.normal(size=(most * tm, d)), jnp.bfloat16)
        return (jax.jit(products), xs, jnp.asarray(group, jnp.int32),
                jnp.asarray(live, jnp.int32))

    for cell, slots, bucket, top_k, experts, held, d, f in ROUTED_CELLS:
        if cells and cell not in cells:
            continue
        key = jax.random.PRNGKey(held + d)
        w_gate, w_up = (
            jax.random.normal(k, (held, d, f), jnp.bfloat16) * 0.02
            for k in jax.random.split(key))
        w_down = jax.random.normal(key, (held, f, d), jnp.bfloat16) * 0.02
        shapes = {"up": (d, f), "down": (f, d)}
        for phase, tokens in (("decode", slots), ("prefill", bucket)):
            # an even routing: each token's top_k distinct experts
            chosen = np.stack([rng.permutation(experts)[:top_k]
                               for _ in range(tokens)])
            even = np.bincount(chosen[chosen < held], minlength=held)
            # and a skewed one: every token's first choice is expert 0
            hot = np.where(chosen == 0, chosen[:, :1], chosen)
            hot[:, 0] = 0
            skew = np.bincount(hot[hot < held], minlength=held)

            def tiles_at(tm):
                return tm, min(held * -(-tokens // tm),
                               held + tokens * min(top_k, held) // tm)

            tile = held_tiles(tokens, held, top_k, experts)
            assert tile == tiles_at(tile[0])
            # the contraction whole and a block of 2 MiB at the most
            small = {kind: (k, next((t for t in gm._cuts(n)
                                     if k * t * 2 <= 2 << 20), None))
                     for kind, (k, n) in shapes.items()}
            ladder = {kind: (gm_ladder(k, 1024), gm_ladder(n, 512))
                      for kind, (k, n) in shapes.items()}
            variants = [("chosen", gm.grouped_matmul, tile, even, None)]
            if parent is not None:
                variants.append(("parent", parent,
                                 parent_tiles(tokens, held, top_k), even,
                                 ladder))
            variants.append(("skew", gm.grouped_matmul, tile, skew, None))
            if all(tn for _, tn in small.values()):
                variants.append(("blocks_2MiB", gm.grouped_matmul, tile,
                                 even, small))
            variants.append(("parent_blocks", gm.grouped_matmul, tile, even,
                             ladder))
            variants += [(f"tm_{tm}", gm.grouped_matmul, tiles_at(tm), even,
                          None)
                         for tm in (tile[0] // 2, tile[0] * 2, tile[0] * 4)
                         if 16 <= tm <= 512]
            for name, kernel, (tm, most), sizes, blocks in variants:
                fn, xs, group, live = layer(kernel, tm, most, sizes, d,
                                            blocks)
                row = dict(time=cell, phase=phase, variant=name,
                           tokens=tokens, tm=tm, most=most,
                           live_tiles=int(live), pairs=int(sizes.sum()),
                           rows=int(live) * tm, hit=int((sizes > 0).sum()),
                           blocks=blocks or {
                               kind: gm._blocks(tm, k, n, 2)
                               for kind, (k, n) in shapes.items()})
                try:
                    ops, call_us = traced_ops(
                        fn, (xs, w_gate, w_up, w_down, group, live))
                except Exception as e:  # noqa: BLE001 - VMEM refuses a block
                    emit(**row, error=str(e)[:200])
                    continue
                # a product's least: its hit experts' weights once and its
                # pairs' rows in and out, or its pairs' multiplications
                least = max((row["hit"] * d * f + row["pairs"] * (d + f)) * 2
                            / peak["hbm_bytes_per_s"],
                            2.0 * row["pairs"] * d * f
                            / peak["flops_per_s"]) * 1e6
                kernels_us = {k.split(".")[0]: v for k, v in ops.items()
                              if k.startswith("moe_")}
                three = sum(kernels_us.values())
                emit(**row, **kernels_us, three_us=round(three, 1),
                     call_us=call_us, least_us=round(least, 1),
                     roofline_pct=round(300 * least / max(three, 1e-9), 1))


def gm_ladder(n: int, most: int) -> int:
    """The block before PR 36: the largest of ``most, most/2, ... 128``
    that divides ``n``, else ``n`` whole."""
    t = most
    while t >= 128:
        if n % t == 0:
            return t
        t //= 2
    return n


if __name__ == "__main__":
    what = sys.argv[1:] or ["kernels", "model"]
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    if "kernels" in what:
        kernels()
    elif "grouped" in what:
        grouped()
    if "model" in what:
        model()
    if "time" in what:
        time_products([a for a in what if a.endswith("-backlog")])
    print(json.dumps({"ok": not FAILED, "failed": FAILED}))
    sys.exit(1 if FAILED else 0)
