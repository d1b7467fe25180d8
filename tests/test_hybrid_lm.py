"""``hybrid_lm`` (models/hybrid.py): one block class driven by a per-layer
pattern, served through the dense pool's two cache kinds, held against the
plain reference of the family it was written for
(``benchmark/references/mimo_v2_flash.py``, the one copy).

The size is tiny and of the benchmark cut's pattern: ``[full+dense, swa,
swa, full]``, 8 experts of which 4 are held, a window of 8, q/k heads of
24 and v heads of 16 with 8 rotary dimensions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import mimo_v2_flash as adapter
from benchmark.references import mimo_v2_flash as ref
from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models import build_model
from mmlspark_tpu.ops.attention import dense_attention
from mmlspark_tpu.ops.flash_attention import (
    cache_row_write,
    flash_attention,
    flash_decode_grouped,
)
from mmlspark_tpu.ops.grouped_matmul import grouped_matmul
from mmlspark_tpu.parallel.expert import (
    held_tiles,
    moe_ffn_held,
    router_topk,
)
from mmlspark_tpu.serve.engine import ServeEngine
from mmlspark_tpu.testing.compile_guard import serve_compile_guard

WINDOW, VOCAB, CACHE = 8, 96, 64
CFG = {
    "hidden_size": 32, "vocab_size": VOCAB, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "num_attention_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "rope_theta": 5e6, "swa_rope_theta": 1e4,
    "partial_rotary_factor": 0.334, "sliding_window": WINDOW,
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "layernorm_epsilon": 1e-5,
    "initializer_range": 0.16, "published": {"n_routed_experts": 8},
}
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
    return sz, params, graph, variables


def served_gap(sz, params, tokens, prompt_len, mode="f32"):
    """The widest gap of a served token's reference logit below the
    reference's best at its position, over one request's tokens."""
    logits = ref.forward(params, jnp.asarray(tokens)[None], sz, mode)[0]
    at = np.asarray(logits[prompt_len - 1:len(tokens) - 1])
    served = np.asarray(tokens[prompt_len:])
    return float((at.max(-1) - at[np.arange(len(served)), served]).max())


# -- the model against the reference -------------------------------------------


def test_the_forward_pass_is_the_references(tiny):
    sz, params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 29), 0, VOCAB)
    want = ref.forward(params, ids, sz)
    got = graph.apply(variables, ids)
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
    _, _, graph, variables = tiny
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
    sz, params, _, _ = tiny
    result = served[prompt_len]
    tokens = np.asarray(result.tokens)
    assert result.status == "completed"
    assert len(tokens) == prompt_len + 3 * WINDOW + 2
    assert served_gap(sz, params, tokens, prompt_len) <= GAP
    for fault in ("no_sink", "full_window", "v_unscaled"):
        assert served_gap(sz, params, tokens, prompt_len, fault) > 5 * GAP


def test_the_served_tokens_show_the_selection_bias(tiny, served):
    """The bias moves a token's choice only now and then onto or off a
    HELD expert, so it is looked for over all the requests together."""
    sz, params, _, _ = tiny
    assert max(served_gap(sz, params, np.asarray(r.tokens), n, "no_bias")
               for n, r in served.items()) > 5 * GAP


def test_bucketed_prefill_serves_what_exact_length_prefill_serves(tiny):
    """Per-token dropless routing is causal, so the family prefills in
    buckets like any other: token for token what a prefill at the exact
    length gives, and inside the compile pins."""
    _, _, graph, variables = tiny
    assert graph.extra["routing_drops"] is False
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n in (3, 9, 11, 17, 23)]
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
    _, _, graph, variables = tiny
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
    v = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    attn = v["block0"]["params"]["attn"]
    assert set(attn) == {"qkv", "attn_out"}
    assert attn["qkv"]["kernel"].shape == (16, 48)
    assert attn["qkv"]["kernel"].dtype == jnp.float32


def test_the_builder_stores_parameters_in_the_width_it_is_given():
    graph = build_model("hybrid_lm", **dict(MODEL, param_dtype="bfloat16"))
    v = graph.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
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

    _, _, graph, variables = tiny
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
    _, _, graph, variables = tiny
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
    v = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
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

    _, _, graph, variables = tiny
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


# -- kernels (interpreter) against the dense oracle ----------------------------


def _qkv(key, b, t, h, hk, dk, dv, dtype=jnp.bfloat16):
    kq, kk, kv, ks = jax.random.split(key, 4)
    return (jax.random.normal(kq, (b, t, h, dk), dtype),
            jax.random.normal(kk, (b, t, hk, dk), dtype),
            jax.random.normal(kv, (b, t, hk, dv), dtype),
            jax.random.normal(ks, (h,), jnp.float32))


@pytest.mark.parametrize("group", [2, 16])
@pytest.mark.parametrize("window, sink", [(None, False), (8, True)])
def test_flash_forward_with_a_sink_and_values_of_another_width(
        group, window, sink):
    h = 16 if group == 16 else 4
    q, k, v, s = _qkv(jax.random.PRNGKey(group), 2, 40, h, h // group,
                      24, 16)
    s = s if sink else None
    got = flash_attention(q, k, v, causal=True, window=window, sink=s,
                          block=16, interpret=True)
    want = dense_attention(q, k, v, causal=True, window=window, sink=s)
    assert got.shape == (2, 40, h, 16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    if sink:
        without = dense_attention(q, k, v, causal=True, window=window)
        assert float(jnp.abs(want.astype(jnp.float32)
                             - without.astype(jnp.float32)).max()) > 0.1


@pytest.mark.parametrize("group", [2, 16])
@pytest.mark.parametrize("ring", [False, True])
def test_grouped_decode_reads_full_rows_and_a_ring(group, ring):
    """One KV head's whole group of query heads a grid step, over
    head-major caches, against ``dense_attention`` at each row's own
    position: a full-length cache, and a ring whose rows have wrapped."""
    h, hk, dk, dv, b = (16 if group == 16 else 4), 1, 24, 16, 3
    hk = h // group
    total, w = 48, 16
    q, k, v, s = _qkv(jax.random.PRNGKey(group + ring), b, total, h, hk,
                      dk, dv)
    pos = jnp.asarray([5, 20, 47])   # before, past and far past a wrap
    s = s if ring else None
    want = jnp.stack([
        dense_attention(q[i:i + 1, p:p + 1], k[i:i + 1, :p + 1],
                        v[i:i + 1, :p + 1], causal=True,
                        window=w if ring else None, q_offset=p, sink=s)[0]
        for i, p in enumerate(np.asarray(pos))])
    q1 = jnp.stack([q[i, p] for i, p in enumerate(np.asarray(pos))])[:, None]
    if ring:
        ck = jnp.zeros((b, hk, w, dk), k.dtype)
        cv = jnp.zeros((b, hk, w, dv), v.dtype)
        for i, p in enumerate(np.asarray(pos)):
            for t in range(max(0, p - w + 1), p + 1):
                ck = ck.at[i, :, t % w].set(k[i, t])
                cv = cv.at[i, :, t % w].set(v[i, t])
        lengths = jnp.minimum(pos + 1, w)
    else:
        ck, cv = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
        lengths = pos + 1
    got = flash_decode_grouped(q1, ck, cv, lengths, sink=s, block=16,
                               interpret=True)
    assert got.shape == (b, 1, h, dv)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_a_row_with_nothing_to_read_comes_out_as_zeros():
    q, k, v, _ = _qkv(jax.random.PRNGKey(0), 2, 16, 4, 2, 24, 16)
    out = flash_decode_grouped(q[:, :1], jnp.moveaxis(k, 1, 2),
                               jnp.moveaxis(v, 1, 2),
                               jnp.asarray([0, 16]), interpret=True)
    assert not np.asarray(out[0], np.float32).any()
    assert np.asarray(out[1], np.float32).any()


def test_the_cache_row_write_touches_one_row_a_slot():
    k = jnp.ones((3, 2, 32, 24), jnp.bfloat16)
    v = jnp.ones((3, 2, 32, 16), jnp.bfloat16)
    kn = jnp.full((3, 2, 24), 7.0, jnp.bfloat16)
    vn = jnp.full((3, 2, 16), -7.0, jnp.bfloat16)
    at = jnp.asarray([0, 17, 31])
    k2, v2 = cache_row_write(k, v, kn, vn, at, interpret=True)
    for i, row in enumerate(np.asarray(at)):
        got = np.asarray(k2[i, :, :, 0], np.float32)
        assert (got[:, row] == 7.0).all()
        assert (np.delete(got, row, axis=1) == 1.0).all()
        assert (np.asarray(v2[i, :, row], np.float32) == -7.0).all()


def test_grouped_matmul_multiplies_each_tile_with_its_groups_matrix():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 32, 24)), jnp.float32)
    tile_group = jnp.asarray([2, 0, 0, 1], jnp.int32)
    got = grouped_matmul(x, w, tile_group, 3, tm=16, interpret=True)
    want = jnp.concatenate([x[i * 16:(i + 1) * 16] @ w[g]
                            for i, g in enumerate((2, 0, 0))])
    np.testing.assert_allclose(got[:48], want, rtol=1e-5, atol=1e-5)
    # the dead tile took no grid step: its rows were never written (the
    # interpreter leaves NaN there, a chip whatever the buffer held)
    assert got[48:].shape == (16, 24)


@pytest.mark.parametrize("k, n, tm", [
    (1792, 256, 16), (256, 1792, 32), (768, 256, 32), (256, 768, 16),
])
@pytest.mark.parametrize("live", [0, 3, 5])
def test_grouped_matmul_at_the_awkward_widths(k, n, tm, live):
    """The cells' expert widths that no power of two divides (1,792 and
    768), the smallest row tiles, a group over two tiles (1), a group
    with none (2), none live and all live: every live tile is the plain
    product of its rows with its group's matrix, and a contraction in
    one block gives what a split one gives, to float32 rounding."""
    rng = np.random.default_rng(k + n + live)
    groups = (0, 1, 1, 3, 4)
    x = jnp.asarray(rng.normal(size=(len(groups) * tm, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(5, k, n)) * k ** -0.5, jnp.bfloat16)
    whole = grouped_matmul(x, w, jnp.asarray(groups, jnp.int32), live, tm=tm,
                           interpret=True)
    split = grouped_matmul(x, w, jnp.asarray(groups, jnp.int32), live, tm=tm,
                           tk=128, tn=128, interpret=True)
    assert whole.shape == split.shape == (len(groups) * tm, n)
    rows = max(live, 1) * tm   # one tile runs where none is live
    want = jnp.concatenate([
        jnp.dot(x[i * tm:(i + 1) * tm], w[g],
                preferred_element_type=jnp.float32)
        for i, g in enumerate(groups[:max(live, 1)])])
    for got in (whole, split):
        # one rounding to bfloat16 of sums near 1
        np.testing.assert_allclose(got[:rows].astype(jnp.float32), want,
                                   atol=2e-2)
    # the two orders of one float32 sum, each rounded once to bfloat16
    np.testing.assert_allclose(np.asarray(whole[:rows], np.float32),
                               np.asarray(split[:rows], np.float32),
                               rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize("tm, k, n, tk, tn", [
    # synth-backlog, report-backlog and reason-backlog: a decode step's
    # gate/up and down products, then a prefill's
    (32, 2048, 1792, 2048, 896), (32, 1792, 2048, 1792, 1024),
    (16, 2048, 768, 2048, 768), (16, 768, 2048, 768, 2048),
    (16, 4096, 2048, 4096, 512), (16, 2048, 4096, 2048, 1024),
    (128, 2048, 1792, 2048, 896), (256, 768, 2048, 768, 2048),
    (128, 4096, 2048, 4096, 512),
    # widths that no lane tile divides are taken whole
    (16, 32, 24, 32, 24),
])
def test_the_weight_block_is_chosen_by_bytes(tm, k, n, tk, tn):
    from mmlspark_tpu.ops import grouped_matmul as gm

    assert gm._blocks(tm, k, n, 2) == (tk, tn)
    assert k % tk == 0 and n % tn == 0
    block = tk * tn * 2
    assert block >= min(1 << 20, k * n * 2)
    assert (2 * (tm * tk + block // 2 + tm * tn) * 2 + 4 * tm * tn
            <= gm._GMM_VMEM)


@pytest.mark.parametrize("weight_mib, scope_mib", [
    (48, 96),      # kanana-2-30b-a3b: 16 experts of 2,048 x 768
    (100, 78),     # room for half the weights at the most
    (224, 16),     # lfm2-8b-a1b's 32 of 2,048 x 1,792 and wider: the least
    (256, 16),     # mimo-v2-flash: 16 of 4,096 x 2,048
    (1, 96),       # never over three quarters of VMEM
])
def test_the_kernels_scope_leaves_no_room_to_stage_the_weights(weight_mib,
                                                               scope_mib):
    from mmlspark_tpu.ops import grouped_matmul as gm

    params = gm._compiler_params(weight_mib << 20)
    assert params.vmem_limit_bytes == scope_mib << 20
    assert params.vmem_limit_bytes >= gm._GMM_VMEM // 3 * 4
    assert gm._VMEM - params.vmem_limit_bytes < max(weight_mib << 20,
                                                    gm._VMEM // 4 + 1)


# -- the router and the expert layer -------------------------------------------


def test_router_topk_is_the_references_route():
    sz = ref.sizes(CFG)
    key = jax.random.PRNGKey(5)
    h = jax.random.normal(key, (1, 64, sz["d"]), jnp.float32)
    p = ref.init_layer(key, sz, 1)
    want_e, want_w, near = ref.route(h, p, sz, "f32")
    assert near.shape == (1, 64) and not near.all()
    got_e, got_w = router_topk(h[0], p["router_w"], p["select_bias"],
                               sz["top_k"])
    np.testing.assert_array_equal(np.sort(got_e, -1),
                                  np.sort(want_e[0], -1))
    np.testing.assert_allclose(np.sort(got_w, -1), np.sort(want_w[0], -1),
                               rtol=1e-6)


def test_the_selection_bias_moves_the_choice_and_no_weight():
    x = jnp.eye(4, dtype=jnp.float32)[:1]            # one token
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3)
    z = jax.nn.sigmoid(router[0])
    experts, weights = router_topk(x, router, jnp.zeros(4), 2)
    assert sorted(np.asarray(experts[0])) == [0, 1]
    # a bias lifts expert 3 over expert 1: chosen, at its own score
    biased, w = router_topk(x, router, jnp.asarray([0., 0., 0., 5.]), 2)
    assert sorted(np.asarray(biased[0])) == [0, 3]
    by_expert = dict(zip(np.asarray(biased[0]).tolist(),
                         np.asarray(w[0]).tolist()))
    assert by_expert[3] == pytest.approx(float(z[3] / (z[0] + z[3])))
    assert by_expert[0] == pytest.approx(float(z[0] / (z[0] + z[3])))
    # a bias that changes no choice changes nothing
    same, w2 = router_topk(x, router, jnp.asarray([0., 0., 0., 0.1]), 2)
    np.testing.assert_array_equal(np.sort(same), np.sort(experts))
    np.testing.assert_allclose(np.sort(w2), np.sort(weights))


def test_all_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The share test of the ``model-configs`` guide, section 4: the
    parts that the holders of experts 0-3 and 4-7 give add up to what
    the uncut reference gives for the whole layer; each holder routes
    over all 8 and adds nothing for a pair that fell elsewhere."""
    sz = ref.sizes(CFG)
    whole = dict(sz, held=(0, 8), held_n=8)
    key = jax.random.PRNGKey(11)
    p = ref.init_layer(key, whole, 1)
    assert p["e_gate_w"].shape[0] == 8
    h = jax.random.normal(key, (2, 19, sz["d"]), jnp.float32)
    uncut, _ = ref.routed_ffn(h, p, whole, "f32")
    parts, pairs = [], 0
    for first in (0, 4):
        held = slice(first, first + 4)
        mine = dict(p, **{name: p[name][held] for name in
                          ("e_gate_w", "e_up_w", "e_down_w")})
        out, counters = moe_ffn_held(
            h, p["router_w"], p["select_bias"], mine["e_gate_w"],
            mine["e_up_w"], mine["e_down_w"], top_k=sz["top_k"],
            first=first, interpret=True)
        np.testing.assert_allclose(
            out, ref.routed_ffn(h, mine, whole, "f32", share=(first, 4))[0],
            atol=1e-5)
        parts.append(out)
        pairs += int(counters["pairs"])
        assert 1 <= int(counters["hit"]) <= 4
    np.testing.assert_allclose(parts[0] + parts[1], uncut, atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 10 * 1e-5
    assert pairs == 2 * 19 * sz["top_k"]   # every pair fell on one holder


def test_a_pad_routes_nowhere():
    sz = ref.sizes(CFG)
    key = jax.random.PRNGKey(2)
    p = ref.init_layer(key, sz, 1)
    h = jax.random.normal(key, (1, 12, sz["d"]), jnp.float32)
    valid = (jnp.arange(12) < 7)[None]
    args = (p["router_w"], p["select_bias"], p["e_gate_w"], p["e_up_w"],
            p["e_down_w"])
    out, counters = moe_ffn_held(h, *args, top_k=2, first=0, valid=valid,
                                 interpret=True)
    short, fewer = moe_ffn_held(h[:, :7], *args, top_k=2, first=0,
                                interpret=True)
    np.testing.assert_allclose(out[:, :7], short, atol=1e-6)
    assert not np.asarray(out[:, 7:]).any()
    assert int(counters["pairs"]) == int(fewer["pairs"])



@pytest.mark.parametrize("tokens, held, top_k, experts, tm, most", [
    # a decode step and a prefill bucket of synth-backlog (128 slots, 4
    # of 32, all held), report-backlog (64 slots, 6 of 128, 16 held) and
    # reason-backlog (64 slots, 8 of 256, 16 held)
    (128, 32, 4, 32, 32, 48), (512, 32, 4, 32, 128, 48),
    (64, 16, 6, 128, 16, 40), (2048, 16, 6, 128, 256, 64),
    (64, 16, 8, 256, 16, 48), (2048, 16, 8, 256, 128, 144),
    (1, 4, 2, 8, 16, 4), (8192, 32, 4, 32, 512, 96),
])
def test_the_row_tile_follows_an_experts_share(tokens, held, top_k, experts,
                                               tm, most):
    assert held_tiles(tokens, held, top_k, experts) == (tm, most)
    assert tm in (16, 32, 64, 128, 256, 512)
    mean = tokens * top_k / experts
    assert tm >= min(2 * mean, 512) and (tm == 16 or tm / 2 < 2 * mean)

    def tiles(sizes):
        return sum(-(-size // tm) for size in sizes)

    # the worst routings fit: every token on ONE held expert (and its
    # other choices spread one a tile), and the even one
    mine = min(top_k, held)
    assert tiles([tokens] * mine) <= most
    assert tiles([tokens] + [1] * (held - 1)) <= most or (
        tokens + held - 1 > tokens * mine)
    even, over = divmod(tokens * mine, held)
    assert tiles([even + (i < over) for i in range(held)]) <= most


@pytest.mark.parametrize("top_k, bias0, live_rows", [
    (1, 9.0, 72),    # SKEW: all 72 tokens on expert 0, three tiles of 32
    (1, 9.0, 50),    # the same under a mask: two tiles
    (2, 9.0, 72),    # expert 0 and each token's own second choice
    (2, 0.0, 41),    # the router's own spread, masked
])
def test_the_expert_layer_is_dropless_under_skew(top_k, bias0, live_rows):
    """An expert that receives more than two tiles' rows takes a third
    tile; the layer still gives what the dense per-token reference
    gives, and ``rows`` counts the rows multiplied: live tiles x the
    row tile."""
    sz = dict(ref.sizes(CFG), top_k=top_k)
    key = jax.random.PRNGKey(3)
    p = ref.init_layer(key, sz, 1)
    p = dict(p, select_bias=p["select_bias"].at[0].set(bias0))
    tokens = 72
    h = jax.random.normal(key, (1, tokens, sz["d"]), jnp.float32)
    valid = (jnp.arange(tokens) < live_rows)[None]
    out, counters = moe_ffn_held(
        h, p["router_w"], p["select_bias"], p["e_gate_w"], p["e_up_w"],
        p["e_down_w"], top_k=top_k, first=0, valid=valid, interpret=True)
    want, _ = ref.routed_ffn(h, p, sz, "f32")
    np.testing.assert_allclose(out[:, :live_rows], want[:, :live_rows],
                               atol=1e-5)
    assert not np.asarray(out[:, live_rows:]).any()
    assert float(jnp.abs(want).max()) > 10 * 1e-5
    experts, _ = router_topk(h[0], p["router_w"], p["select_bias"], top_k)
    chosen = np.asarray(experts)[:live_rows]
    sizes = [(chosen == e).sum() for e in range(4)]   # experts 0-3 held
    tm, _ = held_tiles(tokens, 4, top_k, 8)
    assert tm == (32 if top_k == 1 else 64)
    if bias0:
        assert sizes[0] == live_rows
    assert int(counters["pairs"]) == sum(sizes)
    assert int(counters["hit"]) == sum(size > 0 for size in sizes)
    assert int(counters["rows"]) == sum(-(-size // tm) for size in sizes) * tm
