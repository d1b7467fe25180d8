"""``hybrid_lm``'s layer kind ``conv`` (a gated short convolution in an
attention's place), its constant-size state in the serving pool beside
full-attention K/V, ``qk_norm`` and the top-4-of-32 router with every
expert held, against the plain reference of the family they were written
for (``benchmark/references/lfm2_moe.py``, the one copy): the reference
carries no state from step to step, so every cached step below tests that
what the pool keeps is what the filter needs.

The size is tiny and of the benchmark cut's pattern: ``[conv, conv, full,
conv]`` over ``[dense, dense, routed, routed]``, 8 query and 2 KV heads of
8 over a stream of 64, 3 taps, 8 experts of 16, top 2.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import lfm2_moe as adapter
from benchmark.references import lfm2_moe as ref
from mmlspark_tpu.core.exceptions import FriendlyError, ParamError
from mmlspark_tpu.models import build_model
from mmlspark_tpu.models.generate import _cached_apply, generate, init_cache
from mmlspark_tpu.ops import kv_cache
from mmlspark_tpu.ops.conv_decode import conv_decode, conv_decode_reference
from mmlspark_tpu.ops.kv_cache import SlotState, StateRows
from mmlspark_tpu.parallel.expert import moe_ffn_held, router_topk
from mmlspark_tpu.serve.cache_pool import SlotCachePool
from mmlspark_tpu.serve.engine import ServeEngine

VOCAB, CACHE, D, HEADS, HK, TAPS = 96, 64, 64, 8, 2, 3
PATTERN = ("conv", "conv", "full", "conv")
CFG = {
    "hidden_size": D, "vocab_size": VOCAB, "num_hidden_layers": 4,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "conv_L_cache": TAPS, "conv_bias": False, "num_attention_heads": HEADS,
    "num_key_value_heads": HK, "rope_theta": 1e6, "num_dense_layers": 2,
    "intermediate_size": 96, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "initializer_range": 0.12,
}
MODEL = dict(
    vocab_size=VOCAB, d_model=D, heads=HEADS, head_dim=D // HEADS,
    kv_heads=HK, attention=PATTERN,
    ffn=("dense", "dense", "routed", "routed"), rope_base=1e6,
    qk_norm=True, conv_kernel=TAPS, d_ff=96, n_experts=8, top_k=2,
    expert_d_ff=16, held_experts=(0, 8), norm_eps=1e-5, max_len=CACHE,
)
SZ = ref.sizes(CFG)
#: how far a logit of the program (bfloat16 products, float32 sums) lies
#: from the float32 reference's, on logits of 2 to 4: bfloat16 rounds a
#: product's operands to 3 digits, and four layers of them move a logit
#: by a hundredth or two at the median; a token whose second expert the
#: two choose differently is off by an expert's whole part, so the worst
#: position gets room. A state left out, a stale row or the taps in
#: another order moves the logits by 0.5 and more (the tests below)
LOGIT_MEDIAN, LOGIT_WORST = 0.03, 0.4
#: the widest gap of a served token below the reference's best: the same
#: rounding seen through the argmax (a served token is the program's
#: best, so it lies below the reference's best by what rounding moved the
#: two apart at most)
GAP = 0.06


@functools.lru_cache(maxsize=None)
def forward(mode="f32"):
    """The reference's forward in ``mode``, jitted (one program a shape)."""
    return jax.jit(lambda params, ids: ref.forward(params, ids, SZ, mode))


@pytest.fixture(scope="module")
def tiny():
    params = jax.jit(lambda key: ref.init_params(key, SZ))(
        jax.random.PRNGKey(35))
    graph = build_model("hybrid_lm", **MODEL)
    variables = adapter.to_program(params, dict(SZ, param_bytes=4))
    return params, graph, variables


def reference_logits(params, tokens, mode="f32"):
    # padded to one length (causality hides the pads): one program a mode
    ids = np.zeros((1, 32), np.int32)
    ids[0, :len(tokens)] = tokens
    return np.asarray(forward(mode)(params, jnp.asarray(ids))[0])


def served_gap(params, tokens, prompt_len, mode="f32"):
    logits = reference_logits(params, tokens, mode)
    at = logits[prompt_len - 1:len(tokens) - 1]
    served = np.asarray(tokens[prompt_len:])
    return float((at.max(-1) - at[np.arange(len(served)), served]).max())


def assert_close(got, want):
    off = np.abs(np.asarray(got) - np.asarray(want))
    assert np.abs(want).max() > 1.5
    assert np.median(off) < LOGIT_MEDIAN and off.max() < LOGIT_WORST, (
        float(np.median(off)), float(off.max()))


# -- the model against the reference -------------------------------------------


@pytest.mark.parametrize("qk_norm", [True, False], ids=["qk_norm", "plain"])
def test_the_forward_pass_is_the_references(tiny, qk_norm):
    """With the heads' norms and without them: the builder's ``qk_norm``
    against the reference with and without its norms. The wrong one of
    the two is off by more than the tolerance."""
    params, graph, variables = tiny
    if not qk_norm:
        graph = build_model("hybrid_lm", **dict(MODEL, qk_norm=False))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 29), 0, VOCAB)
    got = jax.jit(graph.apply)(variables, ids)
    assert got.dtype == jnp.float32
    right, wrong = (("f32", "no_qk_norm") if qk_norm
                    else ("no_qk_norm", "f32"))
    assert_close(got, forward(right)(params, ids))
    assert np.abs(np.asarray(got - forward(wrong)(params, ids))).max() > (
        LOGIT_WORST)


def test_prefill_then_cached_steps_give_the_references_logits(tiny):
    """A prefill writes the filter's inputs at every position, then every
    step reads the two before it: the logits of both are the reference's
    full forward's at the same positions, and a chunk against a live
    prefix (a traced position) reads the same rows."""
    params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 20), 0, VOCAB)
    want = np.asarray(forward()(params, ids))
    cache = init_cache(graph, variables, 2, 20)
    assert isinstance(cache["block0"], StateRows)
    assert cache["block0"].rows.shape == (2, 20, D)
    assert not isinstance(cache["block2"], StateRows)
    apply = jax.jit(lambda v, x, c, pos: _cached_apply(graph, v, x, c, pos))
    logits, cache = jax.jit(lambda v, x, c: _cached_apply(
        graph, v, x, c, 0))(variables, ids[:, :7], cache)
    chunk, cache = apply(variables, ids[:, 7:12], cache, jnp.asarray(7))
    step = jax.jit(lambda v, x, c, pos: _cached_apply(
        graph, v, x, c, pos, step=True))
    got = [np.asarray(logits), np.asarray(chunk)]
    for pos in range(12, 20):
        logits, cache = step(variables, ids[:, pos:pos + 1], cache, pos)
        got.append(np.asarray(logits))
    assert_close(np.concatenate(got, axis=1), want)
    assert np.asarray(cache["block0"].rows).all()


def test_generate_serves_the_references_tokens(tiny):
    params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0, VOCAB)
    out = np.asarray(jax.jit(lambda v, x: generate(graph, v, x, 12))(
        variables, ids))
    assert max(served_gap(params, row, 10) for row in out) <= GAP
    assert max(served_gap(params, row, 10, "state_zero")
               for row in out) > 5 * GAP


# -- through the pool ----------------------------------------------------------


@pytest.fixture(scope="module")
def pooled(tiny):
    """The engine's own path, program by program, with the logits kept: a
    bucketed prefill (right-padded), the pool's one write at the prompt's
    TRUE length, then the fused step over the pool's entries."""
    _, graph, variables = tiny
    pool = SlotCachePool(graph, variables, slots=2, cache_len=CACHE)

    @jax.jit
    def prefill(variables, padded, last):
        cache = init_cache(graph, variables, 1, padded.shape[1])
        valid = (jnp.arange(padded.shape[1]) <= last)[None, :]
        return _cached_apply(graph, variables, padded, cache, 0, valid=valid)

    @jax.jit
    def step(variables, buffers, tok, pos, live):
        return _cached_apply(graph, variables, tok[:, None], buffers, pos,
                             step=True, live=live, valid=live[:, None])

    def run(slot, tokens, prompt_len, bucket, after_write=None):
        """Logits for positions ``prompt_len - 1 ..`` of ``tokens``, fed
        one by one after the prompt's prefill into ``slot``."""
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt_len] = tokens[:prompt_len]
        logits, cache = prefill(variables, jnp.asarray(padded),
                                prompt_len - 1)
        pool.write_prefill(slot, cache, prompt_len)
        if after_write is not None:
            after_write(pool, cache)
        got = [np.asarray(logits[0, prompt_len - 1])]
        for pos in range(prompt_len, len(tokens)):
            tok = jnp.zeros((2,), jnp.int32).at[slot].set(int(tokens[pos]))
            logits, pool.buffers = step(
                variables, pool.buffers, tok,
                jnp.zeros((2,), jnp.int32).at[slot].set(pos),
                jnp.zeros((2,), bool).at[slot].set(True))
            got.append(np.asarray(logits[slot, 0]))
        return np.stack(got)

    return pool, run


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.mark.parametrize("prompt_len, bucket", [(1, 8), (2, 8), (3, 8),
                                                (13, 16), (16, 16)])
def test_prefill_then_decode_through_the_pool_is_the_references_forward(
        tiny, pooled, prompt_len, bucket):
    """Logits, not tokens. The slot held a longer request before (its
    state rows are live numbers), the bucket is longer than the prompt
    (the state the pool takes is the one at ``last``, not at the bucket's
    end), and a prompt shorter than the filter's reach leaves rows that
    no position falls on: they are nought."""
    params, _, _ = tiny
    pool, run = pooled
    slot = pool.lease()
    run(slot, _tokens(24, 100), 20, 32)             # the last occupant
    pool.free(slot)
    assert pool.lease() == slot
    assert np.asarray(pool.buffers["block0"].rows[slot]).all()
    tokens = _tokens(prompt_len + 7, prompt_len)
    got = run(slot, tokens, prompt_len, bucket)
    pool.free(slot)
    want = reference_logits(params, tokens)[prompt_len - 1:len(tokens)]
    assert_close(got, want)
    for mode in ("state_zero", "taps_reversed"):
        wrong = reference_logits(params, tokens, mode)
        assert np.abs(got - wrong[prompt_len - 1:len(tokens)]).max() > (
            LOGIT_WORST)


def test_the_state_is_written_at_the_true_length_and_nought_before_zero(
        tiny, pooled):
    """What one admission leaves in a re-leased slot: the filter's input
    at the prompt's last two positions (rows of the prefill's own linear
    state, which the logits tests hold against the reference), or nought
    for a position before 0, and nothing of the bucket's pads or of the
    last occupant."""
    _, graph, variables = tiny
    pool, run = pooled
    slot = pool.lease()
    run(slot, _tokens(21, 7), 20, 32)
    for prompt_len in (1, 2, 5):
        tokens = _tokens(prompt_len, 50 + prompt_len)
        seen = {}
        run(slot, tokens, prompt_len, 8,
            after_write=lambda pool, cache: seen.update(cache))
        g = np.asarray(seen["block0"].rows[0], np.float32)
        assert g[:prompt_len].all() and g.shape == (8, D)
        want = np.zeros((2, D), np.float32)
        want[max(0, 2 - prompt_len):] = g[max(0, prompt_len - 2):prompt_len]
        got = np.asarray(pool.buffers["block0"].rows[slot], np.float32)
        np.testing.assert_array_equal(got.reshape(2, D), want)
        if prompt_len == 1:
            assert not got[:D].any() and got[D:].all()
    pool.free(slot)


@pytest.mark.parametrize("fault", ["state_left_out", "stale_row"])
def test_a_state_that_is_wrong_shows_in_the_logits(tiny, pooled, fault):
    """The pool's state zeroed after the write (a decode that ignores its
    carried rows), or a row that no position falls on left as the last
    occupant had it: both read far above the tolerance."""
    params, _, _ = tiny
    pool, run = pooled
    slot = pool.lease()
    stale = jnp.asarray(_tokens(2 * D, 9).astype(np.float32) / 48 - 1)

    def spoil(pool, _cache):
        for name, entry in pool.buffers.items():
            if not isinstance(entry, SlotState):
                continue
            rows = entry.rows.at[slot].set(0)
            if fault == "stale_row":
                rows = entry.rows.at[slot, :D].set(
                    stale[:D].astype(entry.rows.dtype))
            pool.buffers[name] = SlotState(rows)

    prompt_len = 1 if fault == "stale_row" else 6
    tokens = _tokens(prompt_len + 5, 77)
    got = run(slot, tokens, prompt_len, 8, after_write=spoil)
    pool.free(slot)
    want = reference_logits(params, tokens)[prompt_len - 1:len(tokens)]
    # the prefill's own logits are sound; the steps after it are not
    assert_close(got[:1], want[:1])
    assert np.abs(got[1:] - want[1:]).max() > LOGIT_WORST


REQUESTS = ((13, 10), (5, 8), (1, 9), (2, 8), (3, 7))


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, two slots. First three requests each ALONE: the prompt
    of 1 token on the pool as it was made, then the prompt of 13, then the
    prompt of 5, whose second new token becomes its EOS below. Then all
    five together: the request of 5 now ends at that EOS, mid-block beside
    the request of 13, and the prompts of 1, 2 and 3 tokens are admitted
    into slots a longer request has left, while another decodes."""
    from mmlspark_tpu.core.telemetry import FlightRecorder

    _, graph, variables = tiny
    recorder = FlightRecorder(capacity=8192)
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                         decode_block=4, recorder=recorder)
    assert engine.pool.kinds == {
        "block0": "state", "block1": "state", "block2": "full",
        "block3": "state"}
    for name, kind in engine.pool.kinds.items():
        entry = engine.pool.buffers[name]
        if kind == "state":
            assert isinstance(entry, SlotState)
            assert entry.rows.shape == (2, (TAPS - 1) * D)
            assert entry.rows.dtype == jnp.bfloat16
    new = dict(REQUESTS)
    alone = {}
    for n in (1, 13, 5):
        rid = engine.submit(_tokens(n, n), max_new_tokens=new[n])
        alone[n] = np.asarray(engine.run()[rid].tokens)
    before = len(recorder.events())
    ids = {n: engine.submit(_tokens(n, n), max_new_tokens=new[n],
                            eos_id=int(alone[5][6]) if n == 5 else None)
           for n, _ in REQUESTS}
    results = engine.run()
    return ({n: results[rid] for n, rid in ids.items()}, alone,
            recorder.events()[before:], engine)


@pytest.mark.parametrize("prompt_len, new", REQUESTS)
def test_served_tokens_are_the_references_and_a_fresh_pools(
        tiny, served, prompt_len, new):
    """Every served token is the reference's best or rounding away from
    it, and the stream is the one the request got alone: a slot re-leased
    after a longer request gives the tokens the fresh pool gave, and a
    neighbour that ends mid-block changes no token."""
    params, _, _ = tiny
    result = served[0][prompt_len]
    tokens = np.asarray(result.tokens)
    assert result.status == "completed"
    if prompt_len == 5:
        # ended at its EOS, the second new token (or the first, if equal)
        new = 1 + int(served[1][5][5] != served[1][5][6])
    assert len(tokens) == prompt_len + new
    assert served_gap(params, tokens, prompt_len) <= GAP
    if prompt_len in served[1]:
        np.testing.assert_array_equal(tokens, served[1][prompt_len][:len(tokens)])


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f != "no_bias"])
def test_a_piece_left_out_of_the_reference_shows_on_the_served_tokens(
        tiny, served, fault):
    """With one piece of the mathematics left out of the reference, the
    served tokens fall away from its best, over the five requests' 36
    tokens. (``no_bias`` is not among them: a bias of 0.02 moves the
    second of eight experts for a token or two in a hundred, which 36
    tokens do not show; the router's own test below holds it.)"""
    params, _, _ = tiny
    assert max(served_gap(params, np.asarray(r.tokens), n, fault)
               for n, r in served[0].items()) > 2 * GAP


def test_admissions_count_the_state_they_write(served):
    by_name = {}
    for e in served[2]:
        by_name.setdefault(e["name"], []).append(e["attrs"])
    writes = by_name["serve.pool_write"]
    assert len(writes) == 5 and all(a["dispatches"] == 1 for a in writes)
    # three conv blocks' two rows, whatever the prompt's length
    assert all(a["bytes_state"] == 3 * 2 * D * 2 for a in writes)
    assert all(a["bytes"] == a["bytes_state"] + a["bytes_full"]
               and a["bytes_ring"] == a["bytes_latent"] == 0 for a in writes)
    assert sorted(a["bytes_full"] for a in writes) == [
        n * HK * 2 * (D // HEADS) * 2 for n in (1, 2, 3, 5, 13)]
    blocks = [a for a in by_name["dispatch"]
              if a["family"].startswith("decode")]
    assert blocks and all(
        {"expert_pairs", "experts_hit"} <= set(a) for a in blocks)


@pytest.mark.parametrize("how", ["prefill_chunk", "snapshot_restore"])
def test_the_state_is_carried_where_a_fill_or_an_engine_is_cut(
        tiny, served, how):
    """A chunked prefill carries the filter's inputs from chunk to chunk
    in its linear carry; a snapshot holds no device state and a restore
    prefills prompt and emitted tokens anew. Both give the tokens the
    uncut engine gave."""
    _, graph, variables = tiny
    want = served[1][13]
    kwargs = dict(slots=2, cache_len=CACHE, decode_block=4)
    if how == "prefill_chunk":
        engine = ServeEngine(graph, variables, prefill_chunk=8, **kwargs)
        rid = engine.submit(_tokens(13, 13), max_new_tokens=10)
    else:
        first = served[3]
        rid = first.submit(_tokens(13, 13), max_new_tokens=10)
        first.step()
        first.step()
        snapshot = first.snapshot()
        assert len(snapshot["active"][0]["emitted"]) in range(1, 10)
        first.run()
        engine = ServeEngine.restore(snapshot, graph, variables, **kwargs)
    np.testing.assert_array_equal(np.asarray(engine.run()[rid].tokens), want)


@pytest.mark.parametrize("how", ["kv_int8", "paged", "mesh", "hand_off"])
def test_what_holds_no_state_yet_refuses_and_names_the_kind(tiny, how):
    _, graph, variables = tiny
    kwargs = {"kv_int8": {"kv_dtype": "int8"}, "paged": {"paged": True},
              "mesh": {"mesh": {"data": 2}},
              "hand_off": {"role": "prefill"}}[how]
    with pytest.raises(FriendlyError, match="'state'"):
        ServeEngine(graph, variables, slots=2, cache_len=CACHE, **kwargs)


def test_a_pool_entry_takes_the_fused_step_and_nothing_else():
    entry = SlotState(jnp.zeros((2, 2 * D), jnp.bfloat16))
    proj = jnp.zeros((2, 4, 3 * D), jnp.bfloat16)
    taps = jnp.ones((TAPS, D))
    with pytest.raises(ParamError, match="fused decode step"):
        kv_cache.state_step(entry, proj, taps, jnp.zeros((2,), jnp.int32))
    with pytest.raises(ParamError, match="fused decode step"):
        kv_cache.state_step(entry, proj[:, :1], taps, 3)
    with pytest.raises(ParamError, match="scalar position"):
        kv_cache.state_step(StateRows(jnp.zeros((2, 8, D))), proj[:, :1],
                            taps, jnp.zeros((2,), jnp.int32))
    with pytest.raises(ParamError, match="conv_kernel >= 2"):
        build_model("hybrid_lm", **dict(MODEL, conv_kernel=1))


# -- the kernel (interpreter) against its oracle -------------------------------


@pytest.mark.parametrize("slots, width, dtype", [
    (6, 64, jnp.float32), (64, 128, jnp.bfloat16)],
    ids=["one-block-f32", "two-blocks-bf16"])
def test_conv_decode_is_its_oracle_and_leaves_dead_slots_alone(
        slots, width, dtype):
    rng = np.random.default_rng(0)
    proj = jnp.asarray(rng.normal(size=(slots, 3 * width)), dtype)
    state = jnp.asarray(rng.normal(size=(slots, 2 * width)), dtype)
    taps = jnp.asarray(rng.normal(size=(TAPS, width)), jnp.float32)
    live = jnp.asarray(rng.random(slots) < 0.7).at[0].set(False)
    y, new = conv_decode(proj, state, taps, live, interpret=True)
    want_y, want_new = conv_decode_reference(proj, state, taps, live)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new, np.float32),
                                  np.asarray(want_new, np.float32))
    # by hand, at float64: y = C (w0 s0 + w1 s1 + w2 B u), keep [s1, B u]
    p, s, w = (np.asarray(a, np.float64) for a in (proj, state, taps))
    g = np.asarray(jnp.asarray(p[:, :width] * p[:, 2 * width:], dtype),
                   np.float64)
    by_hand = p[:, width:2 * width] * (
        w[0] * s[:, :width] + w[1] * s[:, width:] + w[2] * g)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float64), by_hand,
                               rtol=tol, atol=tol)
    alive = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(new, np.float64)[~alive],
                                  s[~alive])
    np.testing.assert_array_equal(np.asarray(new, np.float64)[alive],
                                  np.concatenate((s[:, width:], g), 1)[alive])


# -- the router at this family's sizes -----------------------------------------


def test_router_topk_at_top_4_of_32_is_the_published_router():
    """The program adds 1e-20 to the sum of the chosen scores where the
    publication adds 1e-6: at a sum of four sigmoid scores that is under
    a millionth of a weight."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (64, D), jnp.float32)
    p = {"router_w": jax.random.normal(jax.random.fold_in(key, 1), (D, 32))
         * 0.12,
         "select_bias": 0.02 * jnp.where(jnp.arange(32) % 2 == 0, 1.0, -1.0)}
    sz = dict(SZ, top_k=4, experts=32, held=(0, 32))
    want_e, want_w, _ = ref.route(x[None], p, sz, "f32")
    experts, weights = router_topk(x, p["router_w"], p["select_bias"], 4)
    np.testing.assert_array_equal(np.sort(experts, -1),
                                  np.sort(want_e[0], -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(want_w[0], -1),
                               rtol=2e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    # the bias moves the choice: without it some token chooses otherwise
    plain, _ = router_topk(x, p["router_w"], 0 * p["select_bias"], 4)
    assert (np.sort(plain, -1) != np.sort(experts, -1)).any()


def test_four_holders_of_eight_experts_add_up_to_the_uncut_layer():
    """The share test of a deployment that divides the experts: what four
    holders of 8 of the 32 experts each compute, added up, is the whole
    layer as the uncut reference gives it (this cut holds all 32)."""
    key = jax.random.PRNGKey(5)
    sz = dict(SZ, top_k=4, experts=32, held=(0, 32), held_n=32)
    shapes = ref.layer_leaves(sz, "conv", "routed")
    p = {name: ref.make_leaf(jax.random.fold_in(key, i), *shapes[name], sz)
         for i, name in enumerate(ref.ROUTED_LEAVES)}
    h = jax.random.normal(jax.random.fold_in(key, 99), (2, 9, D))
    whole, _ = ref.routed_ffn(h, p, sz, "f32")
    parts = [ref.routed_ffn(h, p, sz, "f32", share=(first, 8))[0]
             for first in (0, 8, 16, 24)]
    np.testing.assert_allclose(sum(parts), whole, atol=1e-6)
    @jax.jit
    def holder(first):
        mine = [jax.lax.dynamic_slice_in_dim(p[name], first, 8)
                for name in ("e_gate_w", "e_up_w", "e_down_w")]
        return moe_ffn_held(h, p["router_w"], p["select_bias"], *mine,
                            top_k=4, first=first, interpret=True)

    held = [holder(first) for first in (0, 8, 16, 24)]
    np.testing.assert_allclose(sum(out for out, _ in held), whole,
                               atol=2e-5 * float(jnp.abs(whole).max()) + 1e-6)
    for (out, counters), part in zip(held, parts):
        np.testing.assert_allclose(out, part, atol=1e-5)
    assert sum(int(c["pairs"]) for _, c in held) == 2 * 9 * 4


# -- what was there builds as it did -------------------------------------------


@pytest.mark.parametrize("config, leaves, digest", [
    ("mimo-v2-flash", 83, "7f7b29050d916a26"),
    ("kanana-2-30b-a3b", 178, "679fa67b07cc33da"),
])
def test_the_routed_configurations_build_the_tree_they_built_before(
        config, leaves, digest):
    """``conv`` and ``qk_norm`` are off where a configuration does not ask
    for them: the two routed configurations of the benchmark build the
    parameter tree they built at the parent commit, path for path and
    shape for shape (the digest is of the sorted ``path shape dtype``
    lines, read there)."""
    import hashlib
    import json
    from pathlib import Path

    cfg = json.loads((Path(__file__).resolve().parent.parent / "benchmark"
                      / "configs" / f"{config}.json").read_text())
    graph = build_model("hybrid_lm", **cfg["program"]["model"])
    variables = jax.eval_shape(graph.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    rows = sorted(
        f"{jax.tree_util.keystr(path)} {tuple(leaf.shape)} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(variables))
    assert len(rows) == leaves
    assert hashlib.sha256("\n".join(rows).encode()).hexdigest()[:16] == digest
    assert not any("conv" in row or "_norm" in row.replace("kv_norm", "")
                   for row in rows)
