"""``hybrid_lm``'s attention kind ``mla`` (multi-head latent attention), its
latent cache entry and the routed layer's shared expert and scale, held
against the plain reference of the family they were written for
(``benchmark/references/deepseek_v3.py``, the one copy): a prefill EXPANDS
and the reference never absorbs, so every cached step below tests the
identity between the two forms.

The size is tiny and of the benchmark cut's pattern: ``[mla+dense,
mla+routed]``, 8 heads of 16 + 8 (q, k) and 16 (v) over a latent of 128
(the decode read takes its values in whole lanes), 8 experts of which 4
are held, top 2 scaled by 2.448, a shared expert of 32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import deepseek_v3 as adapter
from benchmark.references import deepseek_v3 as ref
from mmlspark_tpu.core.exceptions import FriendlyError, ParamError
from mmlspark_tpu.models import build_model
from mmlspark_tpu.models.generate import _cached_apply, generate, init_cache
from mmlspark_tpu.models.hybrid import HybridBlock, RoutedFFN
from mmlspark_tpu.ops import kv_cache
from mmlspark_tpu.ops.attention import dense_attention
from mmlspark_tpu.ops.flash_attention import (
    flash_decode_grouped,
    latent_row_write,
)
from mmlspark_tpu.ops.kv_cache import LatentRows
from mmlspark_tpu.ops.rope import apply_rope
from mmlspark_tpu.parallel.expert import router_topk
from mmlspark_tpu.serve.cache_pool import SlotCachePool
from mmlspark_tpu.serve.engine import ServeEngine

VOCAB, CACHE, HEADS, DN, DR, DV, RANK = 96, 64, 8, 16, 8, 16, 128
CFG = {
    "hidden_size": 32, "vocab_size": VOCAB, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
    "num_attention_heads": HEADS, "qk_nope_head_dim": DN,
    "qk_rope_head_dim": DR, "v_head_dim": DV, "kv_lora_rank": RANK,
    "q_lora_rank": None, "rope_theta": 1e4, "rope_interleave": True,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_shared_experts": 2, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.448, "rms_norm_eps": 1e-6,
    "initializer_range": 0.16, "published": {"n_routed_experts": 8},
}
MODEL = dict(
    vocab_size=VOCAB, d_model=32, heads=HEADS, head_dim=DN + DR,
    v_head_dim=DV, attention=("mla", "mla"), ffn=("dense", "routed"),
    rope_base=1e4, rope_interleave=True, kv_lora_rank=RANK,
    qk_nope_head_dim=DN, qk_rope_head_dim=DR, d_ff=64, n_experts=8, top_k=2,
    expert_d_ff=16, held_experts=(0, 4), shared_d_ff=32, routed_scale=2.448,
    norm_eps=1e-6, max_len=CACHE,
)
#: the widest gap of a served token below the reference's best (logits of
#: 3 to 4): bfloat16 products against float32 move a logit by some 0.01,
#: so a served token lies that far below the best at most, where the two
#: chose the same experts; the fixture's key is one where they do. A
#: planted fault of the attention reads 0.3 or more on the same tokens
GAP = 0.03


SZ = ref.sizes(CFG)


@functools.lru_cache(maxsize=None)
def forward(mode="f32"):
    """The reference's forward in ``mode``, jitted (one program a shape)."""
    return jax.jit(lambda params, ids: ref.forward(params, ids, SZ, mode))


@pytest.fixture(scope="module")
def tiny():
    params = jax.jit(lambda key: ref.init_params(key, SZ))(
        jax.random.PRNGKey(8))
    graph = build_model("hybrid_lm", **MODEL)
    variables = adapter.to_program(params, dict(SZ, param_bytes=4))
    return SZ, params, graph, variables


def served_gap(params, tokens, prompt_len, mode="f32"):
    # padded to one length (causality hides the pads): one program a mode
    ids = np.zeros((1, 32), np.int32)
    ids[0, :len(tokens)] = tokens
    logits = forward(mode)(params, jnp.asarray(ids))[0]
    at = np.asarray(logits[prompt_len - 1:len(tokens) - 1])
    served = np.asarray(tokens[prompt_len:])
    return float((at.max(-1) - at[np.arange(len(served)), served]).max())


# -- the model against the reference -------------------------------------------


def test_the_forward_pass_is_the_references(tiny):
    sz, params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 29), 0, VOCAB)
    want = forward()(params, ids)
    got = jax.jit(graph.apply)(variables, ids)
    assert got.dtype == jnp.float32
    off = np.abs(np.asarray(got - want))
    # bfloat16 products against float32 on logits of 3 to 4: the bulk
    # within a hundredth; a token whose second expert the two choose
    # differently is off by an expert's whole part, so the worst loosely
    assert float(jnp.abs(want).max()) > 2.0
    assert np.median(off) < 0.01 and np.quantile(off, 0.9) < 0.04
    assert off.max() < 0.8


def test_prefill_then_cached_steps_give_the_references_logits(tiny):
    """A prefill (expanded) writes the latent rows, then every step reads
    them absorbed: the logits of both are the reference's full forward's
    at the same positions."""
    sz, params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 20), 0, VOCAB)
    want = np.asarray(forward()(params, ids))
    cache = init_cache(graph, variables, 2, 20)
    assert all(isinstance(e, LatentRows) and e.rows.shape == (2, 20, 256)
               for e in cache.values())
    logits, cache = jax.jit(lambda v, x, c: _cached_apply(
        graph, v, x, c, 0))(variables, ids[:, :12], cache)
    step = jax.jit(lambda v, x, c, pos: _cached_apply(
        graph, v, x, c, pos, step=True))
    got = [np.asarray(logits)]
    for pos in range(12, 20):
        logits, cache = step(variables, ids[:, pos:pos + 1], cache, pos)
        got.append(np.asarray(logits))
    off = np.abs(np.concatenate(got, axis=1) - want)
    # as the uncached forward above: bfloat16 products, the bulk tight
    assert np.median(off) < 0.01 and np.quantile(off, 0.9) < 0.04
    assert off[:, 12:].max() < 0.8 and off[:, :12].max() < 0.8
    # the rows past a latent's 136 numbers are the pad lanes: nought
    assert not np.asarray(cache["block0"].rows[..., RANK + DR:]).any()
    assert np.asarray(cache["block0"].rows[..., :RANK + DR]).any()


def test_generate_serves_the_references_tokens(tiny):
    sz, params, graph, variables = tiny
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0, VOCAB)
    out = np.asarray(jax.jit(lambda v, x: generate(graph, v, x, 12))(
        variables, ids))
    assert max(served_gap(params, row, 10) for row in out) <= GAP
    assert max(served_gap(params, row, 10, "scale_128")
               for row in out) > 5 * GAP


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, two slots, three requests: the third is admitted while
    another decodes, so two requests at different phases share the pool's
    one latent array a block and the one fused step."""
    from mmlspark_tpu.core.telemetry import FlightRecorder

    _, _, graph, variables = tiny
    recorder = FlightRecorder(capacity=4096)
    engine = ServeEngine(graph, variables, slots=2, cache_len=CACHE,
                         decode_block=4, recorder=recorder)
    assert engine.pool.kinds == {"block0": "latent", "block1": "latent"}
    for entry in engine.pool.buffers.values():
        assert isinstance(entry, LatentRows)
        assert entry.rows.shape == (2, CACHE, 256)
        assert entry.rows.dtype == jnp.bfloat16
    ids = {}
    for n, new in ((5, 14), (21, 6), (13, 10)):
        prompt = np.random.default_rng(n).integers(0, VOCAB, n)
        ids[n] = engine.submit(prompt.astype(np.int32), max_new_tokens=new)
    results = engine.run()
    return {n: results[rid] for n, rid in ids.items()}, recorder.events()


@pytest.mark.parametrize("prompt_len, new", [(5, 14), (21, 6), (13, 10)])
def test_prefill_then_decode_through_the_pool_is_the_references_forward(
        tiny, served, prompt_len, new):
    """Every served token is the reference's best, or rounding away from
    it."""
    sz, params, _, _ = tiny
    result = served[0][prompt_len]
    tokens = np.asarray(result.tokens)
    assert result.status == "completed"
    assert len(tokens) == prompt_len + new
    assert served_gap(params, tokens, prompt_len) <= GAP


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_piece_left_out_of_the_reference_shows_on_the_served_tokens(
        tiny, served, fault):
    """With one piece of the mathematics left out of the reference, the
    served tokens fall away from its best, over the three requests' 30
    tokens: by 0.3 to 1.3 for a fault of the attention or the shared
    expert left out, by 0.08 for the scale read as 1 (one routed layer at
    this size, an expert's part a fortieth of a logit)."""
    sz, params, _, _ = tiny
    assert max(served_gap(params, np.asarray(r.tokens), n, fault)
               for n, r in served[0].items()) > 2 * GAP


def test_admissions_write_latent_rows_and_nothing_else(served):
    by_name = {}
    for e in served[1]:
        by_name.setdefault(e["name"], []).append(e["attrs"])
    writes = by_name["serve.pool_write"]
    assert len(writes) == 3 and all(a["dispatches"] == 1 for a in writes)
    assert all(a["bytes"] == a["bytes_latent"] > 0 for a in writes)
    assert all(a["bytes_full"] == a["bytes_ring"] == 0 for a in writes)
    # a prompt's rows at the STORED width (256 lanes), two layers
    assert sorted(a["bytes"] for a in writes) == [
        n * 256 * 2 * 2 for n in (5, 13, 21)]
    blocks = [a for a in by_name["dispatch"]
              if a["family"].startswith("decode")]
    assert blocks and all(
        {"expert_pairs", "experts_hit"} <= set(a) for a in blocks)


def test_one_donated_write_puts_a_prompts_rows_into_its_slot(tiny):
    _, _, graph, variables = tiny
    pool = SlotCachePool(graph, variables, slots=2, cache_len=CACHE)
    rows = jnp.broadcast_to(
        jnp.arange(1, 17, dtype=jnp.bfloat16)[None, :, None], (1, 16, 256))
    cache = {name: LatentRows(rows) for name in pool.buffers}
    pool.lease(), pool.lease()
    dispatches, nbytes = pool.write_prefill(1, cache, 11)
    assert dispatches == 1 and nbytes == 2 * 11 * 256 * 2
    assert pool.bytes_by_kind(11) == {
        "bytes_full": 0, "bytes_ring": 0, "bytes_latent": nbytes,
        "bytes_state": 0}
    got = np.asarray(pool.buffers["block1"].rows[:, :, 0], np.float32)
    np.testing.assert_array_equal(got[1, :11], np.arange(1, 12))
    assert not got[1, 11:].any() and not got[0].any()


# -- the absorbed step is the expanded one -------------------------------------


def _wide(variables):
    """Matrices of deviation 0.06 for the initialiser's 0.02: scores that
    matter."""
    return jax.tree_util.tree_map(
        lambda a: a * 3.0 if a.ndim > 1 else a, variables)


def _f32_block():
    return HybridBlock(
        heads=HEADS, kv_heads=1, head_dim=DN + DR, v_head_dim=DV,
        window=None, rope_base=1e4, rotary_dim=DR, value_scale=1.0,
        sink=False, ffn="dense", d_ff=64, eps=1e-6, dtype=jnp.float32,
        rope_interleave=True, kv_lora_rank=RANK)


@pytest.mark.parametrize("per_row", [False, True],
                         ids=["linear-read", "fused-step-kernel"])
def test_the_absorbed_step_equals_the_expanded_one_at_float32(per_row):
    """The same float32 block over 12 tokens at once (expanded, no cache)
    and over 11 and then one (absorbed, from float32 latent rows): the
    last position's output agrees to float32 round-off. bfloat16 anywhere
    in the absorbed products or the rows would read 1e-2."""
    block = _f32_block()
    assert block.cache_spec() == ("latent", None, 1, RANK + DR, RANK)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32), jnp.float32)
    variables = _wide(block.init(jax.random.PRNGKey(1), x))
    whole = jax.jit(block.apply)(variables, x)
    cache = LatentRows(jnp.zeros((2, 16, 256), jnp.float32))
    _, cache = jax.jit(lambda v, x, c: block.apply(
        v, x, cache=c, pos=0))(variables, x[:, :11], cache)
    pos = jnp.full((2,), 11) if per_row else jnp.asarray(11)
    step, cache = jax.jit(lambda v, x, c, pos: block.apply(
        v, x, cache=c, pos=pos, decode=True))(variables, x[:, 11:], cache,
                                              pos)
    size = float(jnp.abs(whole[:, 11:]).max())
    assert size > 0.5
    np.testing.assert_allclose(step, whole[:, 11:], atol=1e-5 * size)
    assert cache.rows.dtype == jnp.float32
    assert np.asarray(cache.rows[:, 11, :RANK + DR]).all()
    assert not np.asarray(cache.rows[:, 12:]).any()


def test_a_chunk_against_a_live_prefix_is_absorbed_too():
    block = _f32_block()
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, 32), jnp.float32)
    variables = _wide(block.init(jax.random.PRNGKey(1), x))
    whole = jax.jit(block.apply)(variables, x)
    cache = LatentRows(jnp.zeros((1, 16, 256), jnp.float32))
    _, cache = jax.jit(lambda v, x, c: block.apply(
        v, x, cache=c, pos=0))(variables, x[:, :7], cache)
    rest, _ = jax.jit(lambda v, x, c, pos: block.apply(
        v, x, cache=c, pos=pos))(variables, x[:, 7:], cache, jnp.asarray(7))
    np.testing.assert_allclose(
        rest, whole[:, 7:], atol=1e-5 * float(jnp.abs(whole).max()))


# -- the latent kernels (interpreter) against the dense oracle -----------------


def test_the_latent_read_is_dense_attention_over_expanded_heads():
    """Float32 rows ``[c ; k_rope]`` of 128 + 8 numbers in 256 lanes, 8
    query heads, live lengths 0, 1, a block's edge (16, 17) and the whole
    cache: the kernel over the rows, its values their first 128 columns,
    against ``dense_attention`` over per-head keys ``[c W_UK_h ; k_rope]``
    and values ``c W_UV_h``. Float32 throughout, so 1e-4 holds; a
    bfloat16 product of P and the rows would read 1e-2."""
    rng = np.random.default_rng(0)
    b, rows, wide = 6, 32, 256
    c = rng.normal(size=(b, rows, RANK)).astype(np.float32)
    k_rope = rng.normal(size=(b, rows, DR)).astype(np.float32)
    w_uk = rng.normal(size=(RANK, HEADS, DN)).astype(np.float32) / 11
    w_uv = rng.normal(size=(RANK, HEADS, DV)).astype(np.float32) / 11
    q_nope = rng.normal(size=(b, 1, HEADS, DN)).astype(np.float32)
    q_rope = rng.normal(size=(b, 1, HEADS, DR)).astype(np.float32)
    lengths = jnp.asarray([0, 1, 16, 17, 31, 32])
    scale = (DN + DR) ** -0.5
    latent = np.zeros((b, rows, wide), np.float32)
    latent[..., :RANK], latent[..., RANK:RANK + DR] = c, k_rope
    q_wide = np.zeros((b, 1, HEADS, wide), np.float32)
    q_wide[..., :RANK] = np.einsum("bthn,chn->bthc", q_nope, w_uk)
    q_wide[..., RANK:RANK + DR] = q_rope
    o_lat = flash_decode_grouped(
        jnp.asarray(q_wide), jnp.asarray(latent)[:, None], None, lengths,
        scale=scale, block=16, values_in_keys=RANK, interpret=True)
    got = np.einsum("bthc,chv->bthv", np.asarray(o_lat), w_uv)
    k = np.concatenate(
        (np.einsum("brc,chn->brhn", c, w_uk),
         np.broadcast_to(k_rope[:, :, None], (b, rows, HEADS, DR))), -1)
    v = np.einsum("brc,chv->brhv", c, w_uv)
    q = np.concatenate((q_nope, q_rope), -1)
    assert not got[0].any()                       # nothing live: zeros
    for i, n in enumerate(np.asarray(lengths)[1:], start=1):
        want = dense_attention(jnp.asarray(q[i:i + 1]),
                               jnp.asarray(k[i:i + 1, :n]),
                               jnp.asarray(v[i:i + 1, :n]))
        np.testing.assert_allclose(got[i:i + 1], want, atol=1e-4, rtol=1e-4)


def test_the_latent_read_refuses_what_it_cannot_stream():
    q = jnp.zeros((2, 1, 8, 256), jnp.float32)
    rows = jnp.zeros((2, 1, 32, 256), jnp.float32)
    n = jnp.asarray([3, 4])
    for bad in (dict(scale=None, values_in_keys=128),       # no scale
                dict(scale=0.2, values_in_keys=100),        # not whole lanes
                dict(scale=0.2, values_in_keys=384)):       # past the rows
        with pytest.raises(ValueError, match="latent read"):
            flash_decode_grouped(q, rows, None, n, interpret=True, **bad)
    with pytest.raises(ValueError, match="latent read"):      # two KV heads
        flash_decode_grouped(q, jnp.zeros((2, 2, 32, 256)), None, n,
                             scale=0.2, values_in_keys=128, interpret=True)


def test_the_latent_row_write_touches_one_row_a_slot():
    rows = jnp.ones((3, 32, 256), jnp.bfloat16)
    new = jnp.full((3, 256), 7.0, jnp.bfloat16)
    at = jnp.asarray([0, 17, 31])
    got = np.asarray(latent_row_write(rows, new, at, interpret=True),
                     np.float32)
    for i, row in enumerate(np.asarray(at)):
        assert (got[i, row] == 7.0).all()
        assert (np.delete(got[i], row, axis=0) == 1.0).all()


def test_the_step_over_latent_rows_takes_a_scale_and_no_window():
    entry = LatentRows(jnp.zeros((1, 8, 256), jnp.bfloat16))
    q = jnp.zeros((1, 1, 8, 136), jnp.bfloat16)
    k = jnp.zeros((1, 1, 1, 136), jnp.bfloat16)
    with pytest.raises(ParamError, match="scale"):
        kv_cache.decode_step(entry, q, k, k[..., :128], 0)
    with pytest.raises(ParamError, match="window"):
        kv_cache.decode_step(entry, q, k, k[..., :128], 0, scale=0.2,
                             window=4)
    pair = (jnp.zeros((1, 8, 1, 16), jnp.bfloat16),) * 2
    with pytest.raises(ParamError, match="only latent"):
        kv_cache.decode_step(pair, q[..., :16], k[..., :16], k[..., :16], 0,
                             scale=0.2)


# -- rotation, router and the expert layer -------------------------------------


def test_the_interleaved_rotation_by_hand():
    """Pairs ``(2i, 2i+1)`` rotated by ``pos * base^(-2i / D)``, computed
    pair by pair in float64; float32 round-off only (1e-6; a bfloat16
    rotation reads 1e-2). The default convention pairs ``(i, i + D/2)``:
    the same rotation of a head whose dimensions are de-interleaved."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1, 5, 2, 8)).astype(np.float32)
    positions = np.asarray([0, 1, 2, 7, 30])
    want = np.zeros_like(x, dtype=np.float64)
    for t, pos in enumerate(positions):
        for i in range(4):
            ang = pos * 1e4 ** (-2 * i / 8)
            a, b = x[0, t, :, 2 * i], x[0, t, :, 2 * i + 1]
            want[0, t, :, 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[0, t, :, 2 * i + 1] = a * np.sin(ang) + b * np.cos(ang)
    got = apply_rope(jnp.asarray(x), jnp.asarray(positions), base=1e4,
                     interleave=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    halves = apply_rope(jnp.asarray(np.concatenate(
        (x[..., 0::2], x[..., 1::2]), -1)), jnp.asarray(positions), base=1e4)
    np.testing.assert_allclose(halves[..., :4], got[..., 0::2], atol=2e-6)
    np.testing.assert_allclose(halves[..., 4:], got[..., 1::2], atol=2e-6)
    # the reference rotates positions 0 .. T-1 the same way
    np.testing.assert_allclose(
        ref.rope(jnp.asarray(x), 1e4, True),
        apply_rope(jnp.asarray(x), base=1e4, interleave=True), atol=2e-6)


def test_the_routed_scale_by_hand():
    """One token, four experts, top 2: the weights are ``2.448 * z_e /
    (z_a + z_b)`` exactly (float32: 1e-6), the choice is the unscaled
    router's, and the bias still enters no weight."""
    x = jnp.eye(4, dtype=jnp.float32)[:1]
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3)
    z = np.asarray(jax.nn.sigmoid(router[0]), np.float64)
    plain_e, plain_w = router_topk(x, router, jnp.zeros(4), 2)
    experts, weights = router_topk(x, router, jnp.zeros(4), 2, 2.448)
    np.testing.assert_array_equal(experts, plain_e)
    np.testing.assert_allclose(weights, 2.448 * np.asarray(plain_w),
                               rtol=1e-6)
    by_expert = dict(zip(np.asarray(experts[0]).tolist(),
                         np.asarray(weights[0]).tolist()))
    assert by_expert[0] == pytest.approx(2.448 * z[0] / (z[0] + z[1]),
                                         rel=1e-6)
    assert by_expert[1] == pytest.approx(2.448 * z[1] / (z[0] + z[1]),
                                         rel=1e-6)
    assert sum(by_expert.values()) == pytest.approx(2.448, rel=1e-6)
    biased, w = router_topk(x, router, jnp.asarray([0., 0., 0., 5.]), 2,
                            2.448)
    assert sorted(np.asarray(biased[0])) == [0, 3]
    assert float(w.sum()) == pytest.approx(2.448, rel=1e-6)
    # and it is the reference's route
    sz = ref.sizes(CFG)
    key = jax.random.PRNGKey(5)
    h = jax.random.normal(key, (1, 64, sz["d"]), jnp.float32)
    p = ref.init_layer(key, sz, 1)
    want_e, want_w, _ = ref.route(h, p, sz, "f32")
    got_e, got_w = router_topk(h[0], p["router_w"], p["select_bias"],
                               sz["top_k"], sz["route_scale"])
    np.testing.assert_array_equal(np.sort(got_e, -1), np.sort(want_e[0], -1))
    np.testing.assert_allclose(np.sort(got_w, -1), np.sort(want_w[0], -1),
                               rtol=1e-6)


def test_all_shares_add_up_to_the_uncut_layer_the_shared_expert_once():
    """The share test of the ``model-configs`` guide, section 4, with a
    shared expert: the holders of experts 0-3 and 4-7 each give their
    experts' part AND the whole shared expert (every chip has it, for its
    own tokens); summed over the holders with the shared expert counted
    ONCE that is the uncut layer, the program's and the reference's.
    Float32, so 1e-5 (a part is 0.1 to 1)."""
    sz = ref.sizes(CFG)
    whole = dict(sz, held=(0, 8), held_n=8)
    key = jax.random.PRNGKey(11)
    p = ref.init_layer(key, whole, 1)
    assert p["e_gate_w"].shape[0] == 8 and p["s_gate_w"].shape == (32, 32)
    h = jax.random.normal(key, (2, 19, sz["d"]), jnp.float32)
    shared = ref.shared_ffn(h, p, whole, "f32")
    uncut = ref.routed_ffn(h, p, whole, "f32")[0] + shared

    def holder(first, count):
        held = slice(first, first + count)
        layer = RoutedFFN(8, 2, 16, first, count, jnp.float32, jnp.float32,
                          shared_d_ff=32, scale=2.448)
        variables = {"params": {
            "router": p["router_w"], "select_bias": p["select_bias"],
            "experts": {"w_gate": p["e_gate_w"][held],
                        "w_up": p["e_up_w"][held],
                        "w_down": p["e_down_w"][held]},
            "shared_gate": {"kernel": p["s_gate_w"]},
            "shared_up": {"kernel": p["s_up_w"]},
            "shared_out": {"kernel": p["s_down_w"]}}}
        return layer.apply(variables, h)

    parts, pairs = [], 0
    for first in (0, 4):
        out, counters = holder(first, 4)
        mine = dict(p, **{name: p[name][first:first + 4] for name in
                          ("e_gate_w", "e_up_w", "e_down_w")})
        np.testing.assert_allclose(
            out - shared,
            ref.routed_ffn(h, mine, whole, "f32", share=(first, 4))[0],
            atol=1e-5)
        parts.append(out)
        pairs += int(counters["pairs"])
    np.testing.assert_allclose(parts[0] + parts[1] - shared, uncut,
                               atol=1e-5)
    assert float(jnp.abs(shared).max()) > 1e-2          # it is there
    assert float(jnp.abs(uncut - shared).max()) > 1e-2  # so are the experts
    assert pairs == 2 * 19 * sz["top_k"]
    # unscaled, the routed part is 1 / 2.448 of it
    np.testing.assert_allclose(
        ref.routed_ffn(h, p, whole, "unscaled_route")[0] * 2.448,
        uncut - shared, atol=1e-5)


def test_mimo_builds_the_tree_it_built():
    """``shared_d_ff`` 0 and ``routed_scale`` 1.0 add no parameter and no
    name: a model without them has the leaves it had."""
    lm = build_model("hybrid_lm", vocab_size=32, d_model=32, heads=4,
                     head_dim=8, attention=("full", "swa"),
                     ffn=("dense", "routed"), window=8, d_ff=64,
                     n_experts=4, expert_d_ff=16, max_len=32)
    v = jax.eval_shape(lm.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))
    assert sorted(v["block1"]["params"]["moe"]) == [
        "experts", "router", "select_bias"]
    assert sorted(v["block0"]["params"]) == [
        "attn", "ln1", "ln2", "mlp_gate", "mlp_out", "mlp_up"]
    assert sorted(v["block0"]["params"]["attn"]) == [
        "attn_out", "k", "q", "v"]


# -- what is not served yet refuses --------------------------------------------


@pytest.mark.parametrize("how, said", [
    ("paged", "paged pool"),
    ("int8", "kv_dtype"),
    ("handoff", "hand-off"),
    ("mesh", "mesh"),
])
def test_what_holds_no_latent_rows_refuses_loudly(tiny, how, said):
    _, _, graph, variables = tiny
    kwargs = {
        "paged": dict(paged=True, page_size=8),
        "int8": dict(kv_dtype="int8"),
        "handoff": dict(role="prefill"),
        "mesh": dict(mesh={"data": 2, "model": 2}),
    }[how]
    with pytest.raises(FriendlyError, match=said):
        ServeEngine(graph, variables, slots=2, cache_len=CACHE, **kwargs)


@pytest.mark.parametrize("bad, said", [
    (dict(kv_lora_rank=0), "kv_lora_rank"),
    (dict(qk_nope_head_dim=8), "head_dim"),
    (dict(qk_rope_head_dim=7, qk_nope_head_dim=17), "even"),
    (dict(attention=("mla", "mlx")), "attention kinds"),
    (dict(shared_d_ff=-1), "shared_d_ff"),
])
def test_the_builder_refuses_a_latent_layer_it_cannot_build(bad, said):
    with pytest.raises(ParamError, match=said):
        build_model("hybrid_lm", **dict(MODEL, **bad))
