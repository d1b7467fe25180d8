"""``hybrid_lm``'s generation by diffusion over blocks (``block``,
``denoise_steps``, ``mask_id``) and its softmax router, against the plain
reference of the family they were written for
(``benchmark/references/sdar_moe.py``, the one copy): a block-causal
prefill, then denoising steps of a block's rows through the serving pool
and the clean close that rewrites them, on its own or riding the next
block's first step; the engine's admission with no first token, prompts
and budgets that end inside a block; the kernels' block-causal mask and
several query rows a slot, of one block or two; and what the engine
refuses for such a model.

The size is tiny and of the benchmark cut's shape: every layer full
attention over blocks of 4 with 2 denoising steps, 8 query and 2 KV heads
of 16 over a stream of 64, all 8 experts of 16 held, top 2 by softmax.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import sdar_moe as adapter
from benchmark.references import sdar_moe as ref
from mmlspark_tpu.core.exceptions import FriendlyError, ParamError
from mmlspark_tpu.models import build_model
from mmlspark_tpu.models.generate import (
    _cached_apply,
    generate,
    init_cache,
    make_denoise_block,
)
from mmlspark_tpu.ops.attention import dense_attention
from mmlspark_tpu.ops.flash_attention import (
    cache_rows_write,
    flash_attention,
    flash_decode_grouped,
)
from mmlspark_tpu.parallel.expert import moe_ffn_held, router_topk
from mmlspark_tpu.serve.cache_pool import SlotCachePool
from mmlspark_tpu.serve.engine import ServeEngine
from mmlspark_tpu.testing.compile_guard import jit_cache_size

VOCAB, CACHE, D, HEADS, HK, DK = 96, 64, 64, 8, 2, 16
L, STEPS, MASK = 4, 2, 95
MODEL = dict(
    vocab_size=VOCAB, d_model=D, heads=HEADS, head_dim=DK, kv_heads=HK,
    attention=("full",) * 2, ffn=("routed",) * 2, rope_base=1e6,
    qk_norm=True, n_experts=8, top_k=2, expert_d_ff=16, held_experts=(0, 8),
    norm_eps=1e-6, max_len=CACHE, router="softmax", block=L,
    denoise_steps=STEPS, mask_id=MASK,
)
CFG = {
    "hidden_size": D, "vocab_size": VOCAB, "num_hidden_layers": 2,
    "head_dim": DK, "num_attention_heads": HEADS, "num_key_value_heads": HK,
    "rope_theta": 1e6, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "initializer_range": 0.12, "mlp_only_layers": [],
    "decoder_sparse_step": 1, "attention_bias": False,
    "program": {"model": MODEL},
}
SZ = ref.sizes(CFG)
KEY = jax.random.PRNGKey(40)
#: how far a logit of the program (bfloat16 products, float32 sums) lies
#: from the float32 reference's, on logits of 2 to 4: bfloat16 rounds a
#: product's operands to 3 digits, and two layers of them move a logit by
#: a hundredth or two at the median; a row whose second expert the two
#: choose differently is off by an expert's whole part, so the worst
#: position gets room. A causal mask inside the block moves the logits by
#: 0.5 and more (below)
LOGIT_MEDIAN, LOGIT_WORST = 0.03, 0.4


@functools.lru_cache(maxsize=None)
def forward(mode="f32"):
    """The reference's block-causal forward in ``mode``, jitted."""
    return jax.jit(lambda params, ids: ref.forward(params, ids, SZ, mode))


@pytest.fixture(scope="module")
def tiny():
    params = jax.jit(lambda key: ref.init_params(key, SZ))(KEY)
    graph = build_model("hybrid_lm", **MODEL)
    variables = adapter.to_program(params, dict(SZ, param_bytes=4))
    return params, graph, variables


def reference_logits(params, ids, mode="f32"):
    # padded to one length (the block-causal mask hides the pads from
    # every block before theirs): one program a mode
    padded = np.zeros((1, 32), np.int32)
    padded[0, :len(ids)] = ids
    return np.asarray(forward(mode)(params, jnp.asarray(padded))[0])


def assert_close(got, want):
    off = np.abs(np.asarray(got) - np.asarray(want))
    assert np.abs(want).max() > 1.5
    assert np.median(off) < LOGIT_MEDIAN and off.max() < LOGIT_WORST, (
        float(np.median(off)), float(off.max()))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, MASK, n).astype(np.int32)


# -- the model against the reference -------------------------------------------


def test_the_forward_pass_is_block_causal_and_the_references(tiny):
    """The builder's forward is the reference's, block-causal: a token
    moves the logits of its own block's earlier positions and of no
    earlier block. A causal mask inside the block is off by more than
    the tolerance."""
    params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, MASK)
    apply = jax.jit(graph.apply)
    got = apply(variables, ids)
    assert_close(got, forward()(params, ids))
    assert np.abs(np.asarray(got - forward("causal_block")(params, ids))
                  ).max() > LOGIT_WORST
    moved = np.abs(np.asarray(apply(variables, ids.at[0, 9].set(3)) - got)
                   ).max(-1)[0]
    assert not moved[:8].any() and moved[8:12].all()


@pytest.fixture(scope="module")
def pooled(tiny):
    """The engine's own path, program by program, with the logits kept:
    a prompt's whole blocks prefilled block-causal into a slot, then
    every block's denoising steps and its clean close over the pool,
    committing by the reference's own confidences."""
    params, graph, variables = tiny
    pool = SlotCachePool(graph, variables, slots=2, cache_len=CACHE)

    @jax.jit
    def prefill(variables, padded):
        cache = init_cache(graph, variables, 1, padded.shape[1])
        return _cached_apply(graph, variables, padded, cache, 0)

    @jax.jit
    def step(variables, buffers, ids, pos, live):
        return _cached_apply(graph, variables, ids, buffers, pos, step=True,
                             live=live, valid=jnp.broadcast_to(
                                 live[:, None], ids.shape))

    def run(slot, prompt, budget):
        """The program's logits and the reference's at every pass over
        the blocks that serve ``budget`` tokens after ``prompt``."""
        start = len(prompt) // L * L
        padded = np.zeros((1, max(8, start)), np.int32)
        padded[0, :start] = prompt[:start]
        _, cache = prefill(variables, jnp.asarray(padded))
        pool.write_prefill(slot, cache, start)
        seq, got, want = list(prompt[:start]), [], []
        tail, served = list(prompt[start:]), 0
        while served < budget:
            toks = np.array(tail + [0] * (L - len(tail)), np.int32)
            masked = np.arange(L) >= len(tail)
            n0 = int(masked.sum())
            for s in range(STEPS + 1):
                ids = np.where(masked, MASK, toks)
                at = jnp.zeros((2,), jnp.int32).at[slot].set(len(seq))
                live = jnp.zeros((2,), bool).at[slot].set(True)
                block = jnp.zeros((2, L), jnp.int32).at[slot].set(ids)
                logits, pool.buffers = step(variables, pool.buffers, block,
                                            at, live)
                full = reference_logits(params, seq + list(ids))[
                    len(seq):len(seq) + L]
                got.append(np.asarray(logits[slot]))
                want.append(full)
                if s == STEPS:
                    break
                full = np.where(np.arange(VOCAB) == MASK, -np.inf, full)
                conf = full.max(-1) - np.log(np.exp(
                    full - full.max(-1, keepdims=True)).sum(-1))
                count = n0 // STEPS + (s < n0 % STEPS)
                here = np.flatnonzero(masked)
                take = here[np.lexsort((here, -conf[here]))][:count]
                toks[take] = full[take].argmax(-1)
                masked[take] = False
            seq += list(toks)
            served += n0
            tail = []
        return np.concatenate(got), np.concatenate(want), seq

    return pool, run


@pytest.mark.parametrize("prompt_len, budget", [(8, 8), (13, 6), (6, 11)],
                         ids=["on-edges", "prompt-in-block",
                              "budget-in-block"])
def test_prefill_then_denoising_steps_through_the_pool_are_the_references(
        tiny, pooled, prompt_len, budget):
    """Logits, not tokens. Every pass over a block (each denoising step
    and the clean close) reads the slot's block-causal prefill and the
    rows the passes before it wrote: its logits are the reference's full
    block-causal forward over the prefix and the block's current tokens.
    The slot held a longer request before; a prompt that ends inside a
    block starts that block with its tail committed; a budget that ends
    inside one has the block denoised whole."""
    params, _, _ = tiny
    pool, run = pooled
    slot = pool.lease()
    run(slot, _tokens(21, 100), 9)                       # the last occupant
    pool.free(slot)
    assert pool.lease() == slot
    got, want, seq = run(slot, _tokens(prompt_len, prompt_len), budget)
    pool.free(slot)
    assert_close(got, want)
    assert len(seq) % L == 0 and len(seq) >= prompt_len + budget


# -- the engine ----------------------------------------------------------------


def replay(graph, variables, prompt, budget):
    """The denoising a request gets from the model's own full forward at
    every step (the engine's commitments, without pool or kernel)."""
    apply = jax.jit(graph.apply)
    start = len(prompt) // L * L
    seq, out = list(prompt[:start]), []
    tail = list(prompt[start:])
    while len(out) < budget:
        toks = tail + [0] * (L - len(tail))
        masked = [False] * len(tail) + [True] * (L - len(tail))
        n0 = sum(masked)
        for s in range(STEPS):
            ids = [MASK if m else t for t, m in zip(toks, masked)]
            padded = np.zeros((1, 32), np.int32)
            padded[0, :len(seq) + L] = seq + ids
            lg = np.asarray(apply(variables, jnp.asarray(padded))[0],
                            np.float64)[len(seq):len(seq) + L]
            lg[:, MASK] = -np.inf
            conf = -np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1))
            count = n0 // STEPS + (s < n0 % STEPS)
            for p in sorted((p for p in range(L) if masked[p]),
                            key=lambda p: (-conf[p], p))[:count]:
                toks[p], masked[p] = int(lg[p].argmax()), False
        seq += toks
        out += toks[L - n0:]
        tail = []
    return out[:budget]


REQUESTS = [(8, 9), (13, 12), (5, 6), (16, 8), (3, 2)]


@pytest.fixture(scope="module")
def served(tiny):
    """Five requests through an engine of two slots: prompts on a block's
    edge and inside one, budgets that end inside a block, slots re-leased
    while another denoises."""
    from mmlspark_tpu.core.telemetry import FlightRecorder

    _, graph, variables = tiny
    recorder = FlightRecorder(capacity=8192)
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                         decode_block=8, recorder=recorder)
    ids = {n: engine.submit(_tokens(n, n), max_new_tokens=new)
           for n, new in REQUESTS}
    results = engine.run()
    return ({n: results[rid] for n, rid in ids.items()}, recorder.events(),
            engine)


@pytest.mark.parametrize("prompt_len, new", REQUESTS)
def test_served_tokens_are_the_denoising_of_the_models_own_forward(
        tiny, served, prompt_len, new):
    """The engine serves what the model's own full forward commits step
    by step, to the budget; the float32 reference's replay of the same
    tokens, committing by its own confidences, finds each within rounding
    of its best, but in a trailing block the budget cut."""
    params, graph, variables = tiny
    result = served[0][prompt_len]
    assert result.status == "completed"
    tokens = np.asarray(result.tokens)
    got = list(tokens[prompt_len:])
    assert got == replay(graph, variables, list(tokens[:prompt_len]), new)
    seq = np.zeros((1, CACHE), np.int32)
    seq[0, :len(tokens)] = tokens
    r = ref.replay(SZ, KEY, jnp.asarray(seq), prompt_len, new)
    end = prompt_len + new
    assert r["cut"].sum() == (end - max(prompt_len, end // L * L)
                              if end % L else 0)
    keep = ~r["cut"]
    assert (r["gap"][keep] <= 0.06).all(), r["gap"][keep]


@pytest.fixture(scope="module")
def denoising(tiny):
    """The denoising program alone over a pool of three slots, jitted once
    for every count of blocks, and a clean block-causal prefill."""
    _, graph, variables = tiny
    pool = SlotCachePool(graph, variables, slots=3, cache_len=CACHE)
    program = jax.jit(make_denoise_block(graph), static_argnames=("most",))

    @jax.jit
    def prefill(variables, padded):
        cache = init_cache(graph, variables, 1, padded.shape[1])
        return _cached_apply(graph, variables, padded, cache, 0, head=False)

    return pool, program, prefill


@pytest.mark.parametrize("n_blocks", [1, 2, 4])
def test_the_fused_close_serves_the_replays_tokens_and_leaves_clean_rows(
        tiny, denoising, n_blocks):
    """A dispatch of ``n`` blocks closes every block but its last inside
    the next block's first step. Slot 0 (a prompt's tail in its first
    block) runs all ``n`` blocks and stays live; slot 1 (a prompt on a
    block's edge) closes after one; slot 2 holds no request. Each serves
    what the model's own full forward commits step by step, the pool
    holds the K/V of the clean block-causal forward over everything the
    slot closed, the counters are those of separate closes, and one
    program serves every count."""
    _, graph, variables = tiny
    pool, program, prefill = denoising
    prompts = {0: _tokens(13, 70 + n_blocks), 1: _tokens(8, 80 + n_blocks)}
    budgets = {0: L - 1 + L * (n_blocks - 1), 1: L}
    tok = np.zeros((3, L), np.int32)
    masked = np.ones((3, L), bool)
    rem = np.array([100, L, 0], np.int32)
    for slot, prompt in prompts.items():
        assert pool.lease() == slot
        start = len(prompt) // L * L
        padded = np.zeros((1, 16), np.int32)
        padded[0, :start] = prompt[:start]
        _, cache = prefill(variables, jnp.asarray(padded))
        pool.write_prefill(slot, cache, start)
        tail = prompt[start:]
        tok[slot, :len(tail)] = tail
        masked[slot, :len(tail)] = False
    out, live, pool.buffers, pos, counts, _ = program(
        variables, pool.buffers, pool.positions, pool.live, jnp.asarray(tok),
        jnp.asarray(masked), jnp.asarray(rem), jnp.int32(n_blocks), most=4)
    assert jit_cache_size(program) == 1
    out = np.asarray(out)
    ran = {0: n_blocks, 1: 1}
    for slot, prompt in prompts.items():
        start = len(prompt) // L * L
        served = out[slot, :ran[slot]].ravel()[len(prompt) - start:]
        assert list(served[:budgets[slot]]) == replay(
            graph, variables, list(prompt), budgets[slot])
        seq = np.zeros((1, 32), np.int32)
        clean = list(prompt[:start]) + list(out[slot, :ran[slot]].ravel())
        seq[0, :len(clean)] = clean
        _, cache = prefill(variables, jnp.asarray(seq))
        for name, (k, v) in cache.items():
            entry = pool.buffers[name]
            for got, want in ((entry.k, k), (entry.v, v)):
                got = np.asarray(got[slot, :, :len(clean)], np.float32)
                want = np.moveaxis(np.asarray(want[0, :len(clean)],
                                              np.float32), 1, 0)
                np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)
    assert np.asarray(live).tolist() == [True, False, False]
    assert np.asarray(pos)[:2].tolist() == [12 + L * n_blocks, 8 + L]
    counts = {name: int(c) for name, c in counts.items()}
    # slot 0 commits its first block's 3 masked positions, then 4 a block;
    # slot 1 its one block's 4
    assert counts["denoise_steps"] == STEPS * (n_blocks + 1)
    assert counts["tokens_committed"] == sum(budgets.values())
    assert counts["blocks_closed"] == n_blocks + 1
    # the last close closes slot 0 (live at the end) and, in a dispatch
    # of one block, slot 1 as well
    last_closed = int(np.asarray(live).sum()) + (n_blocks == 1)
    assert counts["closes_fused"] == counts["blocks_closed"] - last_closed
    for slot in sorted(prompts, reverse=True):   # leased again as 0, 1
        pool.free(slot)


def test_admission_emits_no_token_and_blocks_are_counted(served):
    """An admission's ``prefill`` event still names its bucket and its
    dispatch carries no token; a denoising program's ``dispatch`` carries
    the live slots' steps, the tokens they committed (two a step: four
    masked positions over two steps) and the blocks they closed, beside
    the routing counters."""
    _, events, engine = served
    assert engine.decode_compile_count == 1 == engine.num_decode_blocks
    dispatches = [e["attrs"] for e in events if e["name"] == "dispatch"]
    prefills = [a for a in dispatches if a["family"].startswith("prefill")]
    blocks = [a for a in dispatches if a["family"].startswith("denoise")]
    assert len(prefills) == len(REQUESTS)
    assert all(a["tokens"] == 0 for a in prefills)
    assert sum(a["tokens"] for a in blocks) == sum(n for _, n in REQUESTS)
    assert all({"denoise_steps", "tokens_committed", "blocks_closed",
                "expert_pairs", "experts_hit"} <= set(a) for a in blocks)
    steps = sum(a["denoise_steps"] for a in blocks)
    committed = sum(a["tokens_committed"] for a in blocks)
    closed = sum(a["blocks_closed"] for a in blocks)
    assert committed == sum(
        L - n % L if n % L else L for n, _ in REQUESTS) + L * (
        closed - len(REQUESTS))
    assert steps == STEPS * closed
    events_of = [e for e in events if e.get("span_name") == "request"]
    assert {e["name"] for e in events_of} >= {"start", "admitted", "prefill",
                                               "decode", "completed"}
    assert all("blocks" in e["attrs"] for e in events_of
               if e["name"] == "decode")


def test_a_dispatch_reports_the_micro_steps_it_ran(served):
    """A dispatch of ``n`` blocks runs ``n * S + 1`` micro-steps: its
    family and its slots' ``decode`` events say so, and the held experts
    hit are per micro-step of that count. The pairs are per pass of a
    block's rows, ``n * (S + 1)`` of them: every live slot routes a
    block's rows at each of a block's S steps and at its close."""
    _, events, _ = served
    ticks = {}
    for e in events:
        if e["name"] == "decode" and e.get("span_name") == "request":
            ticks.setdefault(e["tick"], []).append(e["attrs"])
    blocks = [e for e in events if e["name"] == "dispatch"
              and e["attrs"]["family"].startswith("denoise")]
    assert blocks and len(blocks) == len(ticks)
    for e in blocks:
        a, slots = e["attrs"], ticks[e["tick"]]
        n = max(s["blocks"] for s in slots)
        steps = n * STEPS + 1
        assert a["family"] == f"denoise[T={steps}]"
        assert all(s["block"] == steps for s in slots)
        assert 0 < a["experts_hit"] <= MODEL["n_experts"]
        assert a["expert_pairs"] == len(slots) * L * MODEL["top_k"]
        assert a["blocks_closed"] - a["closes_fused"] == len(slots)


@pytest.mark.parametrize("how", [
    "async_host", "paged", "kv_int8", "mesh", "prefill_chunk", "hand_off",
    "snapshot", "adopt_handoff", "generate"])
def test_what_block_generation_does_not_serve_yet_refuses(tiny, how):
    _, graph, variables = tiny
    kwargs = {"async_host": {"async_host": True}, "paged": {"paged": True},
              "kv_int8": {"kv_dtype": "int8"}, "mesh": {"mesh": {"data": 2}},
              "prefill_chunk": {"prefill_chunk": 8},
              "hand_off": {"role": "prefill"}}.get(how)
    if kwargs is not None:
        with pytest.raises(FriendlyError, match="diffusion over blocks"):
            ServeEngine(graph, variables, slots=2, cache_len=CACHE, **kwargs)
        return
    if how == "generate":
        with pytest.raises(FriendlyError, match="diffusion over blocks"):
            generate(graph, variables, jnp.zeros((1, 4), jnp.int32), 4)
        return
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE)
    with pytest.raises(FriendlyError, match="diffusion over blocks"):
        if how == "snapshot":
            engine.snapshot()
        else:
            engine.adopt_handoff({"prompt": [1, 2], "max_new_tokens": 4})


def test_a_budget_whose_last_block_leaves_the_pool_is_refused(tiny):
    _, graph, variables = tiny
    engine = ServeEngine(graph, variables, slots=2, cache_len=62)
    engine.submit(_tokens(30, 1), max_new_tokens=30)     # 60: a block's end
    with pytest.raises(FriendlyError, match="cache_len"):
        engine.submit(_tokens(30, 1), max_new_tokens=31)  # its block: 64
    with pytest.raises(ParamError, match="diffusion over blocks"):
        build_model("hybrid_lm", **dict(MODEL, mask_id=VOCAB))


# -- the kernels (interpreter) against their oracles ---------------------------


@pytest.mark.parametrize("length, s, grid", [(4, 40, 16), (3, 40, 16)],
                         ids=["grid-of-whole-blocks", "blocks-across-grid"])
def test_the_forward_kernel_takes_the_block_causal_mask(length, s, grid):
    """With blocks of ``length`` positions that divide the grid's rows or
    lie across them: a grid block above the diagonal is read where the
    mask reaches into it, and nothing else is."""
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, s, 4, 16))
               for i in range(3))
    got = flash_attention(q, k, v, causal=True, causal_block=length,
                          block=grid, interpret=True)
    want = dense_attention(q, k, v, causal=True, causal_block=length)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    causal = dense_attention(q, k, v, causal=True)
    assert np.abs(np.asarray(got - causal)).max() > 0.1


def test_the_grouped_read_takes_a_blocks_rows_and_the_write_places_them():
    """Four query rows a slot, 8 query heads a KV head: every row of a
    slot reads the slot's rows ``[0, length)``, its own block's among
    them, with one grid step for a KV head's 32 rows; the write puts a
    block's four rows at a multiple of four, in place, and nothing
    else."""
    key = jax.random.PRNGKey(8)
    b, t, h, hk, rows, d = 3, 4, 16, 2, 64, 128
    q = jax.random.normal(key, (b, t, h, d), jnp.float32)
    kc, vc = (jax.random.normal(jax.random.fold_in(key, i), (b, hk, rows, d))
              for i in (1, 2))
    kn, vn = (jax.random.normal(jax.random.fold_in(key, i), (b, hk, t, d))
              for i in (3, 4))
    at = jnp.asarray([0, 20, 60], jnp.int32)
    k2, v2 = cache_rows_write(kc, vc, kn, vn, at, interpret=True)
    for slot in range(b):
        lo = int(at[slot])
        want_k = kc[slot].at[:, lo:lo + t].set(kn[slot])
        np.testing.assert_array_equal(k2[slot], want_k)
        np.testing.assert_array_equal(
            v2[slot], vc[slot].at[:, lo:lo + t].set(vn[slot]))
    lengths = at + t
    got = flash_decode_grouped(q, k2, v2, lengths, interpret=True)
    for slot in range(b):
        n = int(lengths[slot])
        keys = jnp.moveaxis(k2[slot, :, :n], 0, 1)[None]
        values = jnp.moveaxis(v2[slot, :, :n], 0, 1)[None]
        want = dense_attention(q[slot:slot + 1], keys, values)
        np.testing.assert_allclose(got[slot:slot + 1], want, atol=2e-5,
                                   rtol=2e-5)


def _masked_attention(q, k, v, ends):
    """Attention of ``q`` (B, T, H, d) over unpacked head-major ``k`` /
    ``v`` (B, Hkv, L, d), query row ``r`` of slot ``b`` reading the rows
    ``[0, ends[b, r])`` (zeros where that is empty), in float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    b, t, h, d = q.shape
    g = h // k.shape[1]
    out = np.zeros(q.shape[:3] + (v.shape[3],))
    for slot in range(b):
        for r in range(t):
            n = int(ends[slot, r])
            if n <= 0:
                continue
            for i in range(h):
                s = k[slot, i // g, :n] @ q[slot, r, i] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[slot, r, i] = p @ v[slot, i // g, :n] / p.sum()
    return out


@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("lead", [0, 4], ids=["lead-0", "lead-4"])
def test_a_step_over_two_blocks_reads_each_block_to_its_own_end(packed, lead):
    """Eight query rows a slot from a multiple of four, across the edge of
    a 16-row tile too: the write places them in place (in pieces where
    the start is a multiple of four only), and with ``lead`` 4 the first
    four rows of a slot read its rows ``[0, length - 4)``, the last four
    ``[0, length)``, over a cache streamed in two blocks. A slot of length
    0 (dead) reads nothing; one of 3 leaves its leading rows nothing.
    With ``lead`` 0 the eight rows are the plain fold into the heads, bit
    for bit."""
    key = jax.random.PRNGKey(9)
    b, t, h, hk, rows = 6, 8, 16, 2, 64
    d = 64 if packed else 128
    f = 2 if packed else 1            # KV heads side by side in a row
    q = jax.random.normal(key, (b, t, h, d), jnp.float32)
    kc, vc = (jax.random.normal(jax.random.fold_in(key, i), (b, hk, rows, d))
              for i in (1, 2))
    kn, vn = (jax.random.normal(jax.random.fold_in(key, i), (b, hk, t, d))
              for i in (3, 4))

    def pack(a):  # (b, hk, n, d) -> (b, hk / f, n, f * d)
        n = a.shape[2]
        return jnp.moveaxis(jnp.moveaxis(a, 1, 2).reshape(
            b, n, hk // f, f * d), 2, 1)

    at = jnp.asarray([12, 0, 24, 56, 4, 20], jnp.int32)
    k2, v2 = cache_rows_write(pack(kc), pack(vc), pack(kn), pack(vn), at,
                              align=L, interpret=True)
    want_k, want_v = np.array(kc), np.array(vc)
    for slot in range(b):
        lo = int(at[slot])
        want_k[slot, :, lo:lo + t] = kn[slot]
        want_v[slot, :, lo:lo + t] = vn[slot]
    np.testing.assert_array_equal(k2, pack(want_k))
    np.testing.assert_array_equal(v2, pack(want_v))
    lengths = (at + t).at[1].set(0).at[4].set(3)
    got = flash_decode_grouped(q, k2, v2, lengths, block=32, lead=lead,
                               interpret=True)
    ends = np.repeat(np.asarray(lengths)[:, None], t, axis=1)
    if lead:
        ends[:, :lead] -= t - lead
    np.testing.assert_allclose(got, _masked_attention(q, want_k, want_v,
                                                      ends),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[1]).any()
    assert not np.asarray(got[4, :lead]).any()
    if not lead:
        # the parent's path: the rows folded into the heads of one row
        g = h // hk
        fold = q.reshape(b, t, hk, g, d).transpose(0, 2, 1, 3, 4)
        one = flash_decode_grouped(fold.reshape(b, 1, h * t, d), k2, v2,
                                   lengths, block=32, interpret=True)
        np.testing.assert_array_equal(got, one.reshape(
            b, hk, t, g, -1).transpose(0, 2, 1, 3, 4).reshape(b, t, h, -1))


# -- the softmax router ----------------------------------------------------------


def test_the_softmax_router_is_its_equation():
    """``r = softmax(x W_r)`` over all experts in float32, the top 2, each
    weight ``r_e`` over the chosen ``r``'s sum; the reference's route is
    the same, and without the renormalisation the weights sum to less
    than one."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (64, D), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (D, 8)) * 0.12
    experts, weights = router_topk(x, w, None, 2, score="softmax")
    r = np.asarray(jax.nn.softmax(np.asarray(x, np.float64) @ np.asarray(
        w, np.float64), axis=-1))
    top = np.argsort(-r, axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(top, -1))
    chosen = np.take_along_axis(r, np.asarray(experts), -1)
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    want_e, want_w = ref.route(x[None], {"router_w": w}, SZ, "f32")
    np.testing.assert_array_equal(experts, want_e[0])
    np.testing.assert_allclose(weights, want_w[0], rtol=1e-5)
    _, plain = ref.route(x[None], {"router_w": w}, SZ, "no_topk_norm")
    assert (np.asarray(plain).sum(-1) < 0.999).all()


def test_two_holders_of_four_experts_add_up_to_the_uncut_layer():
    """The share test of a deployment that would divide the experts: what
    two holders of 4 of the 8 compute with the softmax router, added up,
    is the whole layer as the uncut reference gives it."""
    key = jax.random.PRNGKey(5)
    shapes = ref.layer_leaves(SZ)
    p = {name: ref.mimo.make_leaf(jax.random.fold_in(key, i), *shapes[name],
                                  SZ)
         for i, name in enumerate(ref.ROUTED_LEAVES)}
    h = jax.random.normal(jax.random.fold_in(key, 99), (2, 9, D))
    whole = ref.moe(h, p, SZ, "f32")

    @jax.jit
    def holder(first):
        mine = [jax.lax.dynamic_slice_in_dim(p[name], first, 4)
                for name in ("e_gate_w", "e_up_w", "e_down_w")]
        return moe_ffn_held(h, p["router_w"], None, *mine, top_k=2,
                            first=first, interpret=True, score="softmax")

    held = [holder(first) for first in (0, 4)]
    for (out, _), first in zip(held, (0, 4)):
        part = ref.moe(h, p, SZ, "f32", share=(first, 4))
        np.testing.assert_allclose(out, part, atol=1e-5)
    np.testing.assert_allclose(sum(out for out, _ in held), whole,
                               atol=2e-5 * float(jnp.abs(whole).max()) + 1e-6)
    assert sum(int(c["pairs"]) for _, c in held) == 2 * 9 * 2
