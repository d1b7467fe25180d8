"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three main paths once, through the entry points a user calls,
at the full width of models the repo supports, on ONE TPU in ONE process:

1. serve  — ``ServeEngine`` over GPT-2 small (12 x 768, vocab 50257,
   random weights from a seed): six ragged requests with a mid-run join,
   on the dense bf16 pool, again behind the pipelined host loop
   (``async_host``) and again on the paged int8 pool;
2. stage  — ``TPUModel.transform`` over ResNet-50 at 224 x 224;
3. train  — ``SPMDTrainer`` on the same GPT-2-small graph, 8 x 1024 tokens;
4. timing — one fused decode block timed to ``block_until_ready`` and to a
   host fetch, to settle which idiom the docs may recommend.

Every phase checks what came out by the repo's own means (every served
token an argmax of the same graph's scoring pass, agreement with the
dense-attention graph, compile counts inside the engine's pins, the Pallas
kernels present in the lowered programs) and raises on the first failed check: there is no handler that
records an error and carries on. Each phase prints one JSON line; rates on
those lines are smoke figures, not benchmark results. The LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

With no TPU the script exits 2 before any phase. ``--chips 4`` runs only
the multi-chip phase (mesh-sharded engine and data-parallel trainer against
their one-device twins) and needs four chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

#: int8 KV parity is a flip budget, not identity: one rounding flip near an
#: argmax tie cascades through a greedy stream (tests/test_quantized_serve.py)
FLIP_BUDGET = 0.25
#: how far below the reference's best logit a served token's logit may sit,
#: as a share of the largest logit. Logits leave the head in bf16 (8 bits of
#: mantissa), so at a 50k vocabulary the top two often tie to the last bit,
#: and on the TPU the engine's batch-8 decode and a batch-1 reference round
#: differently: bit-identity with ``generate()`` holds on the CPU, not here
TIE_TOL = 2e-2
#: the stage figure of the last pre-round chip record (BENCH_LOCAL_r4.json:
#: ResNet-20 at 32 x 32, batch 1024, 16384 rows, through a remote-execution
#: link), and its shape, so that ROADMAP S3 can tell whether the gap to the
#: model-only rate was that link. A landmark, not a baseline.
BENCH_LOCAL_R4_STAGE_IMAGES_PER_SEC = 704.5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. ``REAL`` is what the chip runs; the CPU
    tests run the same phases at a tiny size."""

    lm: dict
    slots: int
    cache_len: int
    decode_block: int
    prompt_lens: tuple
    late: int            # requests submitted after the first decode block
    new_tokens: int
    image: int
    images: int
    stage_batch: int
    landmark_rows: int   # ResNet-20 32 x 32 rows through the stage
    landmark_batch: int
    train_batch: int
    train_seq: int
    train_steps: int


REAL = Sizes(
    lm=dict(vocab_size=50257, d_model=768, heads=12, depth=12, d_ff=3072,
            max_len=1024),  # GPT-2 small
    slots=8, cache_len=1024, decode_block=32,
    prompt_lens=(700, 17, 130, 389, 64, 512), late=2, new_tokens=48,
    image=224, images=256, stage_batch=128,
    landmark_rows=16384, landmark_batch=1024,
    train_batch=8, train_seq=1024, train_steps=4,
)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(**line) -> None:
    print(json.dumps(line, default=str), flush=True)


class CompileLog:
    """Every backend compile (or persistent-cache retrieval) of the
    process, stamped on the clock the flight recorders use, plus the
    cache's own hit/miss events."""

    def __init__(self):
        import jax.monitoring

        self.compiles: list[tuple[float, float]] = []  # (monotonic t, secs)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic(), secs))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> int:
        return len(self.compiles)

    def since(self, mark: int) -> dict:
        took = self.compiles[mark:]
        return {"compiles": len(took),
                "compile_s": round(sum(s for _, s in took), 2)}

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 < t <= t1)


# -- shared builders ---------------------------------------------------------


def lm_graph(sz: Sizes, **overrides):
    from mmlspark_tpu.models import build_model

    return build_model("transformer_lm", **{**sz.lm, **overrides})


def build_lm(sz: Sizes, seed: int):
    import jax
    import jax.numpy as jnp

    graph = lm_graph(sz)
    variables = jax.jit(graph.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )
    return graph, variables


def make_prompts(sz: Sizes, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, sz.lm["vocab_size"], size=n).astype(np.int32)
            for n in sz.prompt_lens]


def drive(graph, variables, prompts, sz: Sizes, **engine_kwargs):
    """The serve phase's traffic: all but the last ``late`` requests up
    front, the rest after the first decode block so they join mid-run."""
    from mmlspark_tpu.serve.engine import ServeEngine

    engine = ServeEngine(
        graph, variables, slots=sz.slots, cache_len=sz.cache_len,
        decode_block=sz.decode_block, max_queue=len(prompts),
        **engine_kwargs,
    )
    early = len(prompts) - sz.late
    ids = [engine.submit(p, sz.new_tokens) for p in prompts[:early]]
    results = {}
    while engine.decode_compile_count == 0:
        results.update((r.id, r) for r in engine.step())
    ids += [engine.submit(p, sz.new_tokens) for p in prompts[early:]]
    results.update(engine.run())
    return engine, [results[i] for i in ids]


def check_clean_run(engine, results, kernels: bool, label: str) -> dict:
    """Zero failures, retries and degradations; compile counts inside the
    engine's own pins; the kernels in the lowered programs."""
    from mmlspark_tpu.models.generate import cache_geometry

    m = engine.metrics.to_dict()
    check(all(r.status == "completed" for r in results),
          f"{label}: statuses {[r.status for r in results]}")
    for key in ("failed", "expired", "stalled", "retries_total",
                "quarantined_total", "preemptions_total", "degraded_mode"):
        check(m[key] == 0, f"{label}: {key} = {m[key]}")
    degraded = [e for e in engine.recorder.events()
                if e["name"] == "degraded"]
    check(not degraded, f"{label}: degraded {len(degraded)} times")
    check(1 <= engine.decode_compile_count <= engine.num_decode_blocks,
          f"{label}: {engine.decode_compile_count} decode programs, pin "
          f"{engine.num_decode_blocks}")
    check(1 <= engine.prefill_compile_count <= engine.num_prefill_buckets,
          f"{label}: {engine.prefill_compile_count} prefill programs, pin "
          f"{engine.num_prefill_buckets}")
    calls = {f: p["kernel_calls"] for f, p in m["perf_families"].items()}
    if kernels:
        # tpu_custom_call in the lowered text, once per layer at least:
        # the kernel, not its interpreter and not a dense stand-in
        depth = len(cache_geometry(engine.graph, engine.variables))
        for family, n in calls.items():
            check(n and n >= depth,
                  f"{label}: {family} lowered with {n} kernel calls")
    return {
        "tokens_generated": m["tokens_generated"],
        "decode_programs": engine.decode_compile_count,
        "prefill_programs": engine.prefill_compile_count,
        "kernel_calls": calls,
        "ttft_ms_p50": m["ttft_ms_p50"],
        "per_token_ms_p50": m["per_token_ms_p50"],
    }


def generated(result) -> list:
    return [int(t) for t in result.tokens[result.prompt_len:]]


def reference_gaps(graph, variables, prompts, streams, sz: Sizes):
    """Teacher-forced check of served streams: ONE scoring pass of the same
    graph over each prompt + its emitted tokens (all requests in one
    ``(R, cache_len)`` batch; causality hides the padding), then for every
    emitted token how far its logit sits below the best one at that
    position, scaled by the largest logit. 0 = the reference's argmax."""
    import jax
    import jax.numpy as jnp

    n = sz.new_tokens
    seqs = np.zeros((len(prompts), sz.cache_len), np.int32)
    rows = np.zeros((len(prompts), n), np.int32)
    for i, (prompt, stream) in enumerate(zip(prompts, streams)):
        seqs[i, :len(prompt) + n] = np.concatenate([prompt, stream])
        rows[i] = np.arange(len(prompt) - 1, len(prompt) + n - 1)

    def gaps(v, seqs, rows, toks):
        logits = graph.apply(v, seqs)
        at = jnp.take_along_axis(logits, rows[:, :, None], axis=1)
        chosen = jnp.take_along_axis(at, toks[:, :, None], axis=2)[..., 0]
        return (at.max(-1) - chosen) / jnp.abs(at).max()

    return np.asarray(jax.jit(gaps)(
        variables, seqs, rows, np.asarray(streams, np.int32)))


def flip_rate(a: list, b: list) -> float:
    flips = total = 0
    for x, y in zip(a, b):
        n = min(len(x), len(y))
        flips += sum(p != q for p, q in zip(x[:n], y[:n])) + abs(len(x) - len(y))
        total += max(len(x), len(y))
    return flips / max(total, 1)


# -- phase 1: serve ----------------------------------------------------------


def serve_phase(sz: Sizes, seed: int, log: CompileLog, kernels: bool) -> dict:
    import jax

    from mmlspark_tpu.models import generate
    from mmlspark_tpu.models.generate import _cached_apply, init_cache

    mark = log.mark()
    graph, variables = build_lm(sz, seed)
    prompts = make_prompts(sz, seed)

    t0 = time.perf_counter()
    engine, results = drive(graph, variables, prompts, sz)
    wall = time.perf_counter() - t0
    out = check_clean_run(engine, results, kernels, "serve bf16")
    out["wall_s_with_compiles"] = round(wall, 2)
    streams = [generated(r) for r in results]
    check(all(len(s) == sz.new_tokens for s in streams),
          f"serve bf16: stream lengths {[len(s) for s in streams]}")

    # every served token against a scoring pass of the same graph: it must
    # be the reference's argmax, or tie with it within bf16 rounding
    gaps = reference_gaps(graph, variables, prompts, streams, sz)
    check(float(gaps.max()) <= TIE_TOL,
          f"serve bf16: {int((gaps > TIE_TOL).sum())} served tokens are not "
          f"the reference's argmax (worst scaled logit gap {gaps.max():.4f})")
    out["served_tokens_checked"] = int(gaps.size)
    out["worst_scaled_logit_gap"] = round(float(gaps.max()), 5)
    # and against single-request generate(): identical on the CPU
    # (tests/test_decode_block.py); on the chip, reported
    same = 0
    for prompt, stream in zip(prompts, streams):
        ref = jax.jit(
            lambda v, p: generate(graph, v, p, sz.new_tokens)
        )(variables, prompt[None])
        ref = [int(t) for t in np.asarray(ref)[0, len(prompt):]]
        same += sum(a == b for a, b in zip(stream, ref))
    out["tokens_identical_to_generate"] = f"{same}/{gaps.size}"
    check(same >= (1 - FLIP_BUDGET) * gaps.size,
          f"serve bf16: only {same}/{gaps.size} tokens match generate()")

    # the first request's prefill logits against the dense-attention graph
    dense_graph = lm_graph(sz, attn_impl="dense")

    def prefill_logits(g):
        def fn(v, p):
            return _cached_apply(g, v, p, init_cache(g, v, 1, p.shape[1]), 0)[0]
        return np.asarray(jax.jit(fn)(variables, prompts[0][None]))

    ours, dense = prefill_logits(graph), prefill_logits(dense_graph)
    check(ours.shape == (1, len(prompts[0]), sz.lm["vocab_size"]),
          f"prefill logits shape {ours.shape}")
    check(bool(np.isfinite(ours).all()), "prefill logits not finite")
    err = float(np.abs(ours - dense).max() / np.abs(dense).max())
    check(err <= 2e-2, f"prefill logits vs dense graph: scaled error {err}")
    out["prefill_vs_dense_scaled_err"] = round(err, 5)
    out["attn_impl"] = graph.extra["attn_impl"]
    out.update(log.since(mark))
    del engine

    # again behind the pipelined host loop: the late requests are admitted
    # while a block is in flight, whose ``live`` output the pool's write
    # must leave for the fetch
    mark = log.mark()
    t0 = time.perf_counter()
    a_engine, a_results = drive(graph, variables, prompts, sz,
                                async_host=True)
    a_out = check_clean_run(a_engine, a_results, kernels, "serve async_host")
    a_out["wall_s_with_compiles"] = round(time.perf_counter() - t0, 2)
    a_streams = [generated(r) for r in a_results]
    a_gaps = reference_gaps(graph, variables, prompts, a_streams, sz)
    check(float(a_gaps.max()) <= TIE_TOL,
          f"serve async_host: worst scaled logit gap {a_gaps.max():.4f}")
    a_out["worst_scaled_logit_gap"] = round(float(a_gaps.max()), 5)
    a_out["flip_rate_vs_sync"] = round(flip_rate(streams, a_streams), 4)
    check(a_out["flip_rate_vs_sync"] <= FLIP_BUDGET,
          f"serve async_host: flip rate {a_out['flip_rate_vs_sync']}")
    overlapped = a_engine.metrics.to_dict()["overlapped_dispatches_total"]
    check(overlapped > 0, "serve async_host: no block was dispatched behind "
                          "another")
    a_out["overlapped_dispatches"] = overlapped
    a_out["pool_write_dispatches"] = sorted({
        e["attrs"]["dispatches"] for e in a_engine.recorder.events()
        if e["name"] == "serve.pool_write"})
    a_out.update(log.since(mark))
    del a_engine

    # the same six requests through the paged int8 pool, default page size
    mark = log.mark()
    t0 = time.perf_counter()
    q_engine, q_results = drive(graph, variables, prompts, sz,
                                paged=True, kv_dtype="int8")
    q_out = check_clean_run(q_engine, q_results, kernels, "serve paged int8")
    q_out["wall_s_with_compiles"] = round(time.perf_counter() - t0, 2)
    rate = flip_rate(streams, [generated(r) for r in q_results])
    check(rate <= FLIP_BUDGET,
          f"serve paged int8: flip rate {rate} over budget {FLIP_BUDGET}")
    q_out["flip_rate_vs_bf16"] = round(rate, 4)
    q_out["page_size"] = q_engine.pool.page_size
    q_out["num_pages"] = q_engine.pool.num_pages
    q_out.update(log.since(mark))
    return {"bf16_dense_pool": out, "bf16_dense_pool_async_host": a_out,
            "int8_paged_pool": q_out}


# -- phase 2: stage ----------------------------------------------------------


def stage_phase(sz: Sizes, seed: int, log: CompileLog) -> dict:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.data.dataset import Dataset
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.stages.dnn_model import TPUModel
    from mmlspark_tpu.testing.compile_guard import jit_cache_size

    mark = log.mark()
    graph = build_model("resnet50", input_size=sz.image)
    shape = (sz.image, sz.image, 3)
    variables = jax.jit(graph.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1,) + shape, jnp.float32)
    )
    x = np.random.default_rng(seed).normal(
        size=(sz.images,) + shape).astype(np.float32)
    ds = Dataset({"image": x})
    stage = TPUModel.from_graph(
        graph, variables, "resnet50", input_col="image",
        output_col="scores", batch_size=sz.stage_batch,
        model_config={"input_size": sz.image},
    )
    first = stage.transform(ds)
    programs = jit_cache_size(stage._forward())
    before = log.mark()
    t0 = time.perf_counter()
    second = stage.transform(ds)
    dt = time.perf_counter() - t0
    check(jit_cache_size(stage._forward()) == programs
          and log.since(before)["compiles"] == 0,
          "stage: the second transform compiled")
    scores = np.asarray(second["scores"])
    check(scores.shape == (sz.images, 1000), f"stage scores {scores.shape}")
    check(bool(np.isfinite(scores).all()), "stage scores not finite")
    check(np.array_equal(scores, np.asarray(first["scores"])),
          "stage: two transforms of the same rows differ")
    ref = np.asarray(jax.jit(graph.apply)(variables, x[:8]), np.float32)
    err = float(np.abs(scores[:8] - ref).max() / np.abs(ref).max())
    check(err <= 2e-2, f"stage vs graph.apply: scaled error {err} (bf16)")
    # the pre-round record's own model and shape through the same stage
    small = build_model("resnet20_cifar10")
    small_vars = jax.jit(small.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 32, 32, 3), jnp.float32)
    )
    small_ds = Dataset({"image": np.random.default_rng(seed).normal(
        size=(sz.landmark_rows, 32, 32, 3)).astype(np.float32)})
    small_stage = TPUModel.from_graph(
        small, small_vars, "resnet20_cifar10", input_col="image",
        output_col="scores", batch_size=sz.landmark_batch,
    )
    small_stage.transform(small_ds)
    t0 = time.perf_counter()
    small_scores = np.asarray(small_stage.transform(small_ds)["scores"])
    small_dt = time.perf_counter() - t0
    check(bool(np.isfinite(small_scores).all()), "landmark scores not finite")
    return {
        "model": f"resnet50 {sz.image}x{sz.image}", "rows": sz.images,
        "batch_size": sz.stage_batch,
        "vs_graph_apply_scaled_err": round(err, 5),
        "smoke_images_per_sec_second_call": round(sz.images / dt, 1),
        "smoke_resnet20_32x32_images_per_sec_second_call":
            round(sz.landmark_rows / small_dt, 1),
        "landmark_BENCH_LOCAL_r4_resnet20_32x32_stage_images_per_sec":
            BENCH_LOCAL_R4_STAGE_IMAGES_PER_SEC,
        **log.since(mark),
    }


# -- phase 3: train ----------------------------------------------------------


def train_data(sz: Sizes, seed: int, steps: int):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, sz.lm["vocab_size"], size=(steps * sz.train_batch, sz.train_seq + 1)
    ).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def run_trainer(graph, variables, x, y, sz: Sizes, mesh_axes: dict):
    import jax

    from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig

    trainer = SPMDTrainer(graph, TrainConfig(
        epochs=1, batch_size=sz.train_batch, learning_rate=1e-4,
        log_every=1, shuffle=False, mesh_axes=mesh_axes,
    ))
    # host copies: the trainer donates its state, ours must survive it
    trainer.train(x, y, init_variables=jax.device_get(variables))
    return trainer


def train_phase(sz: Sizes, seed: int, log: CompileLog, kernels: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.train.trainer import SOFTMAX_XENT, masked_loss

    mark = log.mark()
    lm = {**sz.lm, "max_len": sz.train_seq}
    tsz = dataclasses.replace(sz, lm=lm)
    graph, variables = build_lm(tsz, seed)
    x, y = train_data(sz, seed, sz.train_steps)
    t0 = time.perf_counter()
    trainer = run_trainer(graph, variables, x, y, sz, {"data": 1})
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in trainer.history if "loss" in h]
    check(len(losses) == sz.train_steps, f"train: {len(losses)} logged steps")
    check(bool(np.isfinite(losses).all()), f"train: losses {losses}")

    # step 0 against the dense-attention graph on the same weights and batch
    dense_graph = lm_graph(tsz, attn_impl="dense")
    b = sz.train_batch
    dense0 = float(jax.jit(lambda v, bx, by: masked_loss(
        SOFTMAX_XENT, dense_graph.apply(v, bx), by, jnp.ones((b,))
    ))(variables, x[:b], y[:b]))
    rel = abs(losses[0] - dense0) / abs(dense0)
    check(rel <= 1e-2, f"train: step-0 loss {losses[0]} vs dense {dense0}")

    # no compile after step 1: steps 2.. reuse the program
    steps = {e["tick"]: e["t"] for e in trainer.recorder.events()
             if e["name"] == "step"}
    late = log.between(steps[1], steps[sz.train_steps - 1])
    check(late == 0, f"train: {late} compiles after step 1")

    cost = trainer.step_cost()
    if kernels:
        # forward + two backward kernels per layer (36 at depth 12)
        check(cost.kernel_calls == 3 * lm["depth"],
              f"train: step lowered with {cost.kernel_calls} kernel calls")
    # each logged step ends in a host fetch of its loss, so the gaps
    # between step events are whole steps (the first one compiles)
    stamps = [steps[i] for i in range(1, sz.train_steps)]
    step_ms = float(np.median(np.diff(stamps))) * 1e3
    tokens = sz.train_batch * sz.train_seq
    return {
        "losses": [round(v, 4) for v in losses],
        "dense_step0_loss": round(dense0, 4),
        "step0_rel_diff_vs_dense": round(rel, 6),
        "kernel_calls": cost.kernel_calls,
        "step_flops": cost.flops,
        "smoke_step_ms_median": round(step_ms, 2),
        "smoke_tokens_per_sec": round(tokens / (step_ms / 1e3), 1),
        "wall_s_with_compiles": round(wall, 2),
        **log.since(mark),
    }


# -- phase 4: timing idiom ---------------------------------------------------


def timing_phase(sz: Sizes, seed: int, log: CompileLog) -> dict:
    """One fused decode block (the engine's program: same builder, same
    donation) timed two ways. ``block_until_ready`` under-waiting — seen
    once through a remote-execution link — would show as a ready time well
    below the fetch time, with the difference reappearing in a fetch made
    right after it."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.generate import init_cache, make_decode_block

    mark = log.mark()
    graph, variables = build_lm(sz, seed)
    t = sz.decode_block
    block = jax.jit(make_decode_block(graph), static_argnums=(7,),
                    donate_argnums=(1, 2, 3))
    s = sz.slots
    state = [init_cache(graph, variables, s, sz.cache_len),
             jnp.full((s,), sz.cache_len // 4, jnp.int32),
             jnp.ones((s,), bool)]
    # K and V must be distinct arrays to be donated
    state[0] = {n: (k, jnp.copy(v)) for n, (k, v) in state[0].items()}
    tok = jnp.zeros((s,), jnp.int32)
    rem = jnp.full((s,), sz.cache_len, jnp.int32)
    eos = jnp.full((s,), -1, jnp.int32)

    def one(wait):
        t0 = time.perf_counter()
        toks, live, buffers, pos = block(variables, *state, tok, rem, eos, t)
        wait(toks)
        dt = time.perf_counter() - t0
        t1 = time.perf_counter()
        int(toks[0, -1])
        after = time.perf_counter() - t1
        state[:] = [buffers, pos, live]
        return dt * 1e3, after * 1e3

    reps = max(2, min(5, (sz.cache_len // 2) // (2 * t) - 1))
    one(jax.block_until_ready)  # compile + warm
    ready, fetch, after_ready = [], [], []
    for _ in range(reps):
        dt, after = one(jax.block_until_ready)
        ready.append(dt)
        after_ready.append(after)
        fetch.append(one(lambda toks: int(toks[0, -1]))[0])
    ready_ms, fetch_ms = float(np.median(ready)), float(np.median(fetch))
    return {
        "decode_block": t, "slots": s, "reps": reps,
        "ms_to_block_until_ready": round(ready_ms, 3),
        "ms_to_host_fetch_of_scalar": round(fetch_ms, 3),
        "ms_fetch_after_ready": round(float(np.median(after_ready)), 3),
        "block_until_ready_waits": bool(ready_ms >= 0.9 * fetch_ms),
        **log.since(mark),
    }


# -- the four-chip phase -----------------------------------------------------


def device_bytes(tree) -> dict:
    """Bytes each device holds of a pytree, from its addressable shards."""
    import jax

    held: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + \
                shard.data.nbytes
    return dict(sorted(held.items()))


def multichip_phase(sz: Sizes, seed: int, log: CompileLog, kernels: bool,
                    chips: int = 4) -> dict:
    """What exists only across chips, each against its one-device twin in
    this process: the mesh-sharded engine and the data-parallel trainer."""
    import jax

    from mmlspark_tpu.parallel.sharding import _path_str
    from mmlspark_tpu.serve.supervisor import ReplicaSet

    check(len(jax.devices()) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees "
          f"{len(jax.devices())}")
    mark = log.mark()
    graph, variables = build_lm(sz, seed)
    prompts = make_prompts(sz, seed)
    one_engine, one_results = drive(graph, variables, prompts, sz)
    check_clean_run(one_engine, one_results, kernels, "one-device engine")
    del one_engine
    mesh = f"data={chips // 2},model=2"
    engine, results = drive(graph, variables, prompts, sz, mesh=mesh)
    out = check_clean_run(engine, results, kernels, f"engine {mesh}")
    # identical on virtual CPU devices (tests/test_serve_sharded.py); on
    # the chips the model axis splits every matmul's reduction, so bf16
    # near-ties can fall the other way: the scoring pass judges each
    # stream, and the two engines must stay within the flip budget
    streams = [generated(r) for r in results]
    one_streams = [generated(r) for r in one_results]
    gaps = reference_gaps(graph, variables, prompts, streams, sz)
    check(float(gaps.max()) <= TIE_TOL,
          f"engine {mesh}: {int((gaps > TIE_TOL).sum())} served tokens are "
          f"not the reference's argmax (worst gap {gaps.max():.4f})")
    rate = flip_rate(one_streams, streams)
    check(rate <= FLIP_BUDGET,
          f"engine {mesh}: flip rate {rate} vs the one-device engine")
    out["worst_scaled_logit_gap"] = round(float(gaps.max()), 5)
    out["tokens_identical_to_one_device"] = (
        f"{round((1 - rate) * gaps.size)}/{gaps.size}")

    # the pool and the parameters really occupy all the chips
    for name, tree in (("params", engine.variables),
                       ("kv_pool", engine.pool.buffers)):
        held = device_bytes(tree)
        check(len(held) == chips and min(held.values()) > 0,
              f"{name} sit on devices {held}")
        out[f"{name}_bytes_per_device"] = held
    # vocab 50257 is odd, so the vocab-parallel rule degrades to
    # replication on model=2 (parallel/sharding.py build_param_shardings)
    out["replicated_param_paths"] = sorted(
        _path_str(path) for path, leaf in
        jax.tree_util.tree_flatten_with_path(engine.variables)[0]
        if leaf.sharding.is_fully_replicated and leaf.size > sz.lm["d_model"]
    )
    del engine

    # two data-parallel steps against the same two steps on one device
    lm = {**sz.lm, "max_len": sz.train_seq}
    tgraph, tvars = build_lm(dataclasses.replace(sz, lm=lm), seed)
    x, y = train_data(sz, seed, 2)
    curves = {}
    for n in (1, chips):
        trainer = run_trainer(tgraph, tvars, x, y, sz, {"data": n})
        curves[n] = [h["loss"] for h in trainer.history if "loss" in h]
        if kernels:
            check(trainer.step_cost().kernel_calls == 3 * lm["depth"],
                  f"trainer data={n}: kernels missing from the step")
    check(len(curves[chips]) == 2 and bool(np.isfinite(curves[chips]).all()),
          f"trainer data={chips}: losses {curves[chips]}")
    rel = max(abs(a - b) / abs(b) for a, b in zip(curves[chips], curves[1]))
    check(rel <= 1e-3, f"trainer data={chips} vs data=1: {curves}")
    out["train_losses"] = {f"data={n}": [round(v, 5) for v in c]
                           for n, c in curves.items()}
    out["train_rel_diff"] = round(rel, 7)

    # where a ReplicaSet puts its replicas (ROADMAP R7/D5)
    rs = ReplicaSet(graph, variables, replicas=chips, slots=sz.slots,
                    cache_len=sz.cache_len, decode_block=sz.decode_block)
    out["replica_param_devices"] = {
        i: sorted({d.id for leaf in
                   jax.tree_util.tree_leaves(rs.engine(i).variables)
                   for d in leaf.devices()})
        for i in range(chips)
    }
    out.update(log.since(mark))
    return out


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    first = jax.devices()[0]
    if first.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found platform {first.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    from mmlspark_tpu.core.env import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    emit(phase="start", device=device, compile_cache_dir=cache_dir,
         jax=jax.__version__)
    log = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        emit(phase="multichip",
             **multichip_phase(REAL, args.seed, log, kernels=True))
    else:
        emit(phase="serve", **serve_phase(REAL, args.seed, log, kernels=True))
        emit(phase="stage", **stage_phase(REAL, args.seed, log))
        emit(phase="train", **train_phase(REAL, args.seed, log, kernels=True))
        emit(phase="timing", **timing_phase(REAL, args.seed, log))
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    emit(phase="end", wall_s=round(time.perf_counter() - t0, 1),
         **log.since(0), cache_hits=log.cache_hits,
         cache_misses=log.cache_misses, compile_cache_dir=cache_dir,
         compile_cache_entries=entries)
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
