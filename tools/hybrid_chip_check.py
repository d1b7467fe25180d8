"""On the chip: each kernel that ``hybrid_lm`` brought, compiled, against
its plain-XLA oracle at the benchmark cell's shapes, then the model's own
forward and its served tokens against the family's reference at the
published widths. One JSON line a check; exits 1 if any is off.

    chiprun -- python tools/hybrid_chip_check.py
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
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul

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
    # grouped products at the decode step's and a prefill's shapes
    rng = np.random.default_rng(0)
    for m, tm, live in ((1024, 64, 11), (40960, 512, 9)):
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
        report(f"grouped_matmul.{m}", gap(got[:live * tm], want), 2e-2,
               dead_is_zero=not bool(jnp.abs(got[live * tm:]).max()))


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


if __name__ == "__main__":
    what = sys.argv[1:] or ["kernels", "model"]
    if "kernels" in what:
        kernels()
    if "model" in what:
        model()
    print(json.dumps({"ok": not FAILED, "failed": FAILED}))
    sys.exit(1 if FAILED else 0)
