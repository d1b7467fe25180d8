"""``hybrid_lm`` (models/hybrid.py): one block class driven by a per-layer
pattern, served through the dense pool's two cache kinds, held against the
plain reference of the family it was written for
(``benchmark/references/mimo_v2_flash.py``, the one copy).

The size is tiny and of the benchmark cut's pattern: ``[full+dense, swa,
swa, full]``, 8 experts of which 4 are held, a window of 8, q/k heads of
24 and v heads of 16 with 8 rotary dimensions.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import mimo_v2_flash as adapter
from benchmark.references import mimo_v2_flash as ref
from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models import build_model
from mmlspark_tpu.serve.engine import ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.hybrid_helpers import CFG, VOCAB, WINDOW
from tests.serve_helpers import init_lm

CACHE = 64
MODEL = dict(
    vocab_size=VOCAB, d_model=32, heads=4, head_dim=24, v_head_dim=16,
    attention=("full", "swa", "swa", "full"),
    ffn=("dense", "routed", "routed", "routed"), kv_heads=1,
    swa_kv_heads=2, window=WINDOW, rope_base=5e6, swa_rope_base=1e4,
    rotary_dim=8, value_scale=0.707, swa_sink=True, d_ff=64, n_experts=8,
    top_k=2, expert_d_ff=16, held_experts=(0, 4), max_len=CACHE,
)
#: the widest gap of a served token below the reference's best (logits of
#: 3 to 4). Read over 10 keys of weights with five requests each: 0 to
#: 0.05 for 46 of the 50 requests, 0.07 to 0.26 for the four in which
#: program (bfloat16 products) and reference chose another second expert
#: for some token: a near-tie of two scores, which any change of the
#: arithmetic can make or unmake. The fixture's key is one where none
#: happens (0.006 at most); there a fault of the attention reads 0.45 or
#: more a request and the bias left out 0.86 over all of them
GAP = 0.03


@pytest.fixture(scope="module")
def tiny():
    sz = ref.sizes(CFG)
    params = ref.init_params(jax.random.PRNGKey(8), sz)
    graph = build_model("hybrid_lm", **MODEL)
    variables = adapter.to_program(params, dict(sz, param_bytes=4))
    return params, graph, variables


@functools.lru_cache(maxsize=None)
def reference(mode="f32"):
    """The reference's forward under ``jax.jit``: a program a length and
    mode, where the eager call compiles one an operation."""
    sz = ref.sizes(CFG)
    return jax.jit(lambda params, ids: ref.forward(params, ids, sz, mode))


def served_gap(params, tokens, prompt_len, mode="f32"):
    """The widest gap of a served token's reference logit below the
    reference's best at its position, over one request's tokens."""
    logits = reference(mode)(params, jnp.asarray(tokens)[None])[0]
    at = np.asarray(logits[prompt_len - 1:len(tokens) - 1])
    served = np.asarray(tokens[prompt_len:])
    return float((at.max(-1) - at[np.arange(len(served)), served]).max())


# -- the model against the reference -------------------------------------------


def test_the_forward_pass_is_the_references(tiny):
    params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 29), 0, VOCAB)
    want = reference()(params, ids)
    got = jax.jit(graph.apply)(variables, ids)
    assert got.dtype == jnp.float32
    off = np.abs(np.asarray(got - want))
    # bfloat16 products against float32, on logits of 3 to 4. A token
    # whose second expert the two choose differently (a near-tie of two
    # scores) is off by an expert's whole part, so the bulk is held tight
    # and the worst token loosely
    assert float(jnp.abs(want).max()) > 2.0
    assert np.median(off) < 0.01 and np.quantile(off, 0.9) < 0.04
    assert off.max() < 0.8


PROMPTS = (5, WINDOW, 13, 29, 3)


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, two slots, five requests: prompts shorter than, equal
    to and longer than the window, each generating past three turns of
    the ring while another request shares the blocks at another phase."""
    _, graph, variables = tiny
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                         decode_block=4)
    assert engine.pool.buffers["block1"][0].shape == (2, 2, WINDOW, 24)
    assert engine.pool.buffers["block0"][1].shape == (2, 1, CACHE, 16)
    ids = {}
    for n in PROMPTS:
        prompt = np.random.default_rng(n).integers(0, VOCAB, n)
        ids[n] = engine.submit(prompt.astype(np.int32),
                               max_new_tokens=3 * WINDOW + 2)
    results = engine.run()
    return {n: results[rid] for n, rid in ids.items()}


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_prefill_then_decode_through_the_pool_is_the_references_forward(
        tiny, served, prompt_len):
    """Every served token is the reference's best, or rounding away from
    it; with a piece of the attention's mathematics left out of the
    reference, the same tokens fall away from its best."""
    params, _, _ = tiny
    result = served[prompt_len]
    tokens = np.asarray(result.tokens)
    assert result.status == "completed"
    assert len(tokens) == prompt_len + 3 * WINDOW + 2
    assert served_gap(params, tokens, prompt_len) <= GAP
    for fault in ("no_sink", "full_window", "v_unscaled"):
        assert served_gap(params, tokens, prompt_len, fault) > 5 * GAP


def test_the_served_tokens_show_the_selection_bias(tiny, served):
    """The bias moves a token's choice only now and then onto or off a
    HELD expert, so it is looked for over all the requests together."""
    params, _, _ = tiny
    assert max(served_gap(params, np.asarray(r.tokens), n, "no_bias")
               for n, r in served.items()) > 5 * GAP


def test_bucketed_prefill_serves_what_exact_length_prefill_serves(tiny):
    """Per-token dropless routing is causal, so the family prefills in
    buckets like any other: token for token what a prefill at the exact
    length gives, and inside the compile pins.

    Three prompts, one a bucket (8, 16, 32), the two longer ones a token
    past the bucket below, where the padding is widest; over two slots,
    so one slot is leased again. Five prompts (11 and 23 beside these)
    held nothing more: the exact-length engine compiles a prefill
    program a length, and those two lay in buckets already taken."""
    _, graph, variables = tiny
    assert graph.extra["routing_drops"] is False
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n in (3, 9, 17)]
    served = {}
    for bucketed in (True, False):
        engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                             decode_block=4)
        assert engine._bucketed is True
        engine._bucketed = bucketed   # the switch a dropping router takes
        if bucketed:
            with serve_compile_guard(engine, min_decode=1, min_prefill=1):
                ids = [engine.submit(p, max_new_tokens=12) for p in prompts]
                results = engine.run()
            assert engine.prefill_compile_count <= 3  # buckets 8, 16, 32
        else:
            ids = [engine.submit(p, max_new_tokens=12) for p in prompts]
            results = engine.run()
        served[bucketed] = [np.asarray(results[i].tokens) for i in ids]
    for a, b in zip(served[True], served[False]):
        np.testing.assert_array_equal(a, b)


def test_chunked_prefill_is_taken_too(tiny):
    _, graph, variables = tiny
    prompt = np.arange(21, dtype=np.int32) % VOCAB
    whole = ServeEngine(graph, variables, slots=2, cache_len=CACHE)
    rid = whole.submit(prompt, max_new_tokens=9)
    want = np.asarray(whole.run()[rid].tokens)
    chunked = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                          prefill_chunk=8)
    rid = chunked.submit(prompt, max_new_tokens=9)
    np.testing.assert_array_equal(
        np.asarray(chunked.run()[rid].tokens), want)


def test_transformer_lm_is_built_as_it_was():
    """The new builder is a builder of its own: ``transformer_lm``'s
    parameter tree keeps its one fused ``qkv`` and its biases."""
    lm = build_model("transformer_lm", vocab_size=16, d_model=16, heads=2,
                     depth=1, max_len=8, attn_impl="dense")
    v = init_lm(lm)
    attn = v["block0"]["params"]["attn"]
    assert set(attn) == {"qkv", "attn_out"}
    assert attn["qkv"]["kernel"].shape == (16, 48)
    assert attn["qkv"]["kernel"].dtype == jnp.float32


def test_the_builder_stores_parameters_in_the_width_it_is_given():
    graph = build_model("hybrid_lm", **dict(MODEL, param_dtype="bfloat16"))
    v = init_lm(graph)
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(v)} == {
        jnp.dtype(jnp.bfloat16)}
    with pytest.raises(FriendlyError, match="param_dtype"):
        build_model("hybrid_lm", **dict(MODEL, param_dtype="float16"))
    with pytest.raises(FriendlyError, match="same length"):
        build_model("hybrid_lm", **dict(MODEL, ffn=("dense",)))


# -- what the dense pool holds, and what refuses -------------------------------


def test_one_donated_write_puts_a_prompts_last_rows_into_the_ring(tiny):
    """``_write_slot`` writes a prompt's last ``min(P, W)`` rows at
    ``pos % W`` into a ring and every row of a full block, in ONE
    dispatch, and counts the bytes of each kind."""
    from mmlspark_tpu.models.generate import init_cache

    _, graph, variables = tiny
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE)
    pool = engine.pool
    assert pool.kinds == {"block0": "full", "block1": "ring",
                          "block2": "ring", "block3": "full"}
    assert sorted(pool.lease() for _ in range(2)) == [0, 1]
    rows, length = 16, 13
    cache = init_cache(graph, variables, 1, rows)
    mark = jnp.arange(rows, dtype=jnp.float32)[None, :, None, None]
    cache = {name: (jnp.broadcast_to(mark, k.shape).astype(k.dtype),
                    jnp.broadcast_to(-mark, v.shape).astype(v.dtype))
             for name, (k, v) in cache.items()}
    dispatches, nbytes = pool.write_prefill(1, cache, length)
    assert dispatches == 1
    by = pool.bytes_by_kind(length)
    assert by["bytes_full"] == 2 * length * 1 * (24 + 16) * 2
    assert by["bytes_ring"] == 2 * WINDOW * 2 * (24 + 16) * 2
    assert nbytes == by["bytes_full"] + by["bytes_ring"]
    k_ring = np.asarray(pool.buffers["block1"][0][1, 0, :, 0], np.float32)
    # positions 5 .. 12 at row p % 8
    np.testing.assert_array_equal(k_ring, [8, 9, 10, 11, 12, 5, 6, 7])
    k_full = np.asarray(pool.buffers["block0"][0][1, 0, :, 0], np.float32)
    np.testing.assert_array_equal(k_full[:length], np.arange(length))
    assert not k_full[length:].any()
    # the other slot is as it was
    assert not np.asarray(pool.buffers["block1"][0][0], np.float32).any()
    # a prompt shorter than the ring leaves the rows past it alone
    pool.write_prefill(0, cache, 3)
    k_ring = np.asarray(pool.buffers["block1"][1][0, 1, :, 0], np.float32)
    np.testing.assert_array_equal(k_ring, [0, -1, -2, 0, 0, 0, 0, 0])


@pytest.mark.parametrize("how, said", [
    ("paged", "paged pool"),
    ("int8", "kv_dtype"),
    ("handoff", "hand-off"),
    ("mesh", "mesh"),
])
def test_what_holds_no_declared_geometry_refuses_loudly(tiny, how, said):
    _, graph, variables = tiny
    kwargs = {
        "paged": dict(paged=True, page_size=8),
        "int8": dict(kv_dtype="int8"),
        "handoff": dict(role="prefill"),
        # a model axis of 4 does not divide the window layers' 2 KV heads
        "mesh": dict(mesh={"data": 2, "model": 4}),
    }[how]
    with pytest.raises(FriendlyError, match=said):
        ServeEngine(graph, variables, slots=2, cache_len=CACHE, **kwargs)


def test_a_window_for_linear_rows_is_still_refused():
    lm = build_model("transformer_lm", vocab_size=16, d_model=16, heads=2,
                     depth=1, max_len=32, window=8, pos_embedding="rope",
                     attn_impl="dense")
    v = init_lm(lm)
    with pytest.raises(FriendlyError, match="declares one"):
        ServeEngine(lm, v, slots=2, cache_len=32)


# -- spans and counters --------------------------------------------------------


def test_the_blocks_fetch_carries_the_routing_counters(tiny):
    """``dispatch`` carries ``expert_pairs``, ``experts_hit`` and
    ``expert_rows`` (per routed layer and micro-step: the pairs on held
    experts, the held experts hit, the rows their products multiplied,
    pad rows too), ``serve.prefill`` the prompt's, and
    ``serve.pool_write`` the bytes of each cache kind: all from fetches
    and counts the engine makes anyway."""
    from mmlspark_tpu.core.telemetry import FlightRecorder

    _, graph, variables = tiny
    recorder = FlightRecorder(capacity=4096)
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                         decode_block=4, recorder=recorder)
    rng = np.random.default_rng(0)
    for n in (9, 17):
        engine.submit(rng.integers(0, VOCAB, n).astype(np.int32),
                      max_new_tokens=8)
    engine.run()
    by_name = {}
    for e in recorder.events():
        by_name.setdefault(e["name"], []).append(e["attrs"])
    blocks = [a for a in by_name["dispatch"]
              if a["family"].startswith("decode")]
    counters = {"expert_pairs", "experts_hit", "expert_rows"}
    assert blocks and all(counters <= set(a) for a in blocks)
    for a in blocks:
        # two live tokens choose 2 of 8 experts each; 4 are held
        assert 0 <= a["expert_pairs"] <= 2 * 2
        assert 0 <= a["experts_hit"] <= min(4, a["expert_pairs"])
        # an expert has two rows at the most: one tile of 16 an expert hit
        assert a["expert_rows"] == pytest.approx(16 * a["experts_hit"],
                                                 abs=0.02)
    assert any(a["expert_pairs"] > 0 for a in blocks)
    prefills = by_name["serve.prefill"]
    assert all(counters <= set(a) for a in prefills)
    assert all(a["expert_rows"] >= a["expert_pairs"] for a in prefills)
    # a prompt's pads route nowhere: no more pairs than real tokens give
    assert max(a["expert_pairs"] for a in prefills) <= 17 * 2
    writes = by_name["serve.pool_write"]
    assert all(a["dispatches"] == 1 for a in writes)
    assert all(a["bytes"] == a["bytes_full"] + a["bytes_ring"]
               for a in writes)
