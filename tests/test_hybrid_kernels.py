"""``hybrid_lm``'s kernels (interpreter) against their dense oracles, and its
router and expert layer against the family's reference
(``benchmark/references/mimo_v2_flash.py``). The model through the pool and
the engine: test_hybrid_lm.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import mimo_v2_flash as ref
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
from tests.hybrid_helpers import CFG


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
