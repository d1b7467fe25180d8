"""Sequence/context parallelism correctness: ring and Ulysses attention
must match the dense single-device reference exactly (up to float
tolerance), including gradients, and the transformer family must train
under a dp×sp×tp mesh with TP sharding rules applied.

The reference has no long-context support at all (SURVEY.md §5), so these
are capability-upgrade tests — the 8-device CPU mesh is the local[*]
analog (TestBase, core/test/base/.../TestBase.scala:36).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.attention import dense_attention
from mmlspark_tpu.parallel import (
    TRANSFORMER_TP_RULES,
    make_mesh,
    ring_attention,
    ulysses_attention,
)
from mmlspark_tpu.parallel.sharding import build_param_shardings, spec_for_path


def _qkv(rng, b=2, s=16, h=4, d=8):
    shape = (b, s, h, d)
    return (
        jnp.asarray(rng.normal(size=shape), jnp.float32),
        jnp.asarray(rng.normal(size=shape), jnp.float32),
        jnp.asarray(rng.normal(size=shape), jnp.float32),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(rng, causal):
    q, k, v = _qkv(rng)
    mesh = make_mesh({"seq": 8})
    expect = dense_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(rng, causal):
    q, k, v = _qkv(rng, h=4)
    mesh = make_mesh({"seq": 4})
    expect = dense_attention(q, k, v, causal=causal)
    got = ulysses_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def _gqa_qkv(rng, b=2, s=16, h=4, hk=2, d=8):
    return (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32),
        jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32),
        jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32),
    )


@pytest.mark.parametrize("window", [None, 6])
def test_ring_gqa_matches_dense(rng, window):
    """GQA through the ring (round 5): narrow kv chunks rotate, the
    repeat to query heads happens inside the local update — output must
    equal the dense GQA reference, window included."""
    q, k, v = _gqa_qkv(rng)
    mesh = make_mesh({"seq": 8})
    expect = dense_attention(q, k, v, causal=True, window=window)
    got = ring_attention(q, k, v, mesh, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_ulysses_gqa_matches_dense(rng):
    q, k, v = _gqa_qkv(rng, h=4, hk=2)
    mesh = make_mesh({"seq": 2})
    expect = dense_attention(q, k, v, causal=True)
    got = ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_ring_gqa_gradients_match_dense(rng):
    q, k, v = _gqa_qkv(rng, s=8)
    mesh = make_mesh({"seq": 4})

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_gqa_head_mismatch_is_friendly(rng):
    """ADVICE r4: direct callers get a FriendlyError, not a trace-time
    einsum shape mismatch deep in the inner body."""
    from mmlspark_tpu.core.exceptions import FriendlyError

    q, _, _ = _qkv(rng, h=4)
    _, k3, v3 = _gqa_qkv(rng, hk=3)  # 3 does not divide 4
    mesh = make_mesh({"seq": 4})
    with pytest.raises(FriendlyError, match="heads"):
        ring_attention(q, k3, v3, mesh, causal=True)
    with pytest.raises(FriendlyError, match="heads"):
        ulysses_attention(q, k3, v3, mesh, causal=True)


def test_ring_with_data_axis(rng):
    # dp × sp composition: batch on 'data', sequence on 'seq'
    q, k, v = _qkv(rng, b=4, s=8)
    mesh = make_mesh({"data": 2, "seq": 4})
    expect = dense_attention(q, k, v, causal=True)
    got = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_ring_gradients_match_dense(rng):
    q, k, v = _qkv(rng, b=1, s=8, h=2, d=4)
    mesh = make_mesh({"seq": 4})

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_ring_rejects_bad_seq_len(rng):
    from mmlspark_tpu.core.exceptions import FriendlyError

    q, k, v = _qkv(rng, s=12)  # 12 % 8 != 0
    mesh = make_mesh({"seq": 8})
    with pytest.raises(FriendlyError):
        ring_attention(q, k, v, mesh)


def test_transformer_impls_agree(rng):
    from mmlspark_tpu.models import build_model

    ids = jnp.asarray(rng.integers(0, 64, size=(2, 16)), jnp.int32)
    mesh = make_mesh({"seq": 4})
    outs = {}
    for impl in ("dense", "ring", "ulysses"):
        graph = build_model(
            "transformer_lm", vocab_size=64, d_model=32, heads=4, depth=2,
            max_len=16, attn_impl=impl, mesh=None if impl == "dense" else mesh,
        )
        variables = jax.jit(graph.init)(jax.random.PRNGKey(0), ids)
        outs[impl] = np.asarray(jax.jit(graph.apply)(variables, ids))
    # same params (same init seed), same math -> same logits
    np.testing.assert_allclose(outs["ring"], outs["dense"], atol=2e-2,
                               rtol=2e-2)
    np.testing.assert_allclose(outs["ulysses"], outs["dense"], atol=2e-2,
                               rtol=2e-2)


def test_tp_sharding_rules():
    mesh = make_mesh({"data": 2, "model": 4})
    spec = spec_for_path("block0/attn/qkv/kernel", TRANSFORMER_TP_RULES, mesh)
    assert tuple(spec) == (None, "model")
    spec = spec_for_path("block0/attn/attn_out/kernel", TRANSFORMER_TP_RULES,
                         mesh)
    assert tuple(spec) == ("model", None)
    # vocab-parallel embedding: rows over 'model' (pairs with the
    # column-sharded lm head — no cross-shard reduction between them)
    assert tuple(spec_for_path("embed/token/embedding",
                               TRANSFORMER_TP_RULES, mesh)) == ("model", None)
    assert tuple(spec_for_path("z/head/kernel",
                               TRANSFORMER_TP_RULES, mesh)) == (None, "model")
    # unmatched -> replicated
    assert tuple(spec_for_path("some/unknown/param",
                               TRANSFORMER_TP_RULES, mesh)) == ()
    # uneven dims degrade to replicated instead of failing
    params = {"x": {"qkv": {"kernel": jnp.zeros((8, 6))}}}  # 6 % 4 != 0
    sh = build_param_shardings(params, mesh, TRANSFORMER_TP_RULES)
    assert tuple(sh["x"]["qkv"]["kernel"].spec) == (None, None)


def test_trainer_dp_sp_tp(rng):
    """Full training step over a data×seq×model mesh with ring attention
    and Megatron-style param sharding — the multi-chip north star shape."""
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig

    mesh_axes = {"data": 2, "seq": 2, "model": 2}
    mesh = make_mesh(mesh_axes)
    graph = build_model(
        "transformer_lm", vocab_size=32, d_model=16, heads=4, depth=1,
        max_len=8, attn_impl="ring", mesh=mesh,
    )
    x = rng.integers(0, 32, size=(8, 8)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    trainer = SPMDTrainer(
        graph,
        TrainConfig(
            epochs=2, batch_size=4, learning_rate=1e-2, mesh_axes=mesh_axes,
            param_rules=TRANSFORMER_TP_RULES, log_every=1, shuffle=False,
        ),
    )
    variables = trainer.train(x, y)
    losses = [h["loss"] for h in trainer.history if "loss" in h]
    assert losses and all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # it actually learns
    out = graph.apply(variables, jnp.asarray(x[:2]))
    assert out.shape == (2, 8, 32)


def test_ulysses_flash_inner_matches_dense(rng, monkeypatch):
    """The REAL TPU branch of _ulysses_inner must agree with dense: the
    backend check is monkeypatched to take the flash path and the flash
    kernel forced into interpret mode (its compiled/interpreted bodies are
    identical), so the exact code path that runs on TPU executes here."""
    from functools import partial

    import jax

    import mmlspark_tpu.ops.flash_attention as fa
    import mmlspark_tpu.parallel.context_parallel as cp
    from mmlspark_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        fa, "flash_attention",
        partial(fa.flash_attention, block=16, interpret=True),
    )
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 32, 8, 8)), jnp.float32)
        for _ in range(3)
    )
    mesh = make_mesh({"seq": 8})
    got = np.asarray(cp.ulysses_attention(q, k, v, mesh, causal=True))
    want = np.asarray(dense_attention(q, k, v, causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ring_and_ulysses_sliding_window_match_dense():
    """window threads through both sequence-parallel paths: each shard's
    block masks reproduce the dense windowed function exactly."""
    mesh = make_mesh({"seq": 4})
    rng = np.random.default_rng(9)
    S, W = 32, 9
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, S, 4, 8)), jnp.float32)
        for _ in range(3)
    )
    want = np.asarray(dense_attention(q, k, v, causal=True, window=W))
    got_ring = np.asarray(
        ring_attention(q, k, v, mesh, causal=True, window=W)
    )
    got_uly = np.asarray(
        ulysses_attention(q, k, v, mesh, causal=True, window=W)
    )
    np.testing.assert_allclose(got_ring, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_uly, want, atol=1e-5, rtol=1e-5)


def test_ring_window_step_bound():
    """Windowed ring attention drops whole rotations: the live-rotation
    count is independent of device index and O(window / chunk)."""
    from mmlspark_tpu.parallel.context_parallel import _ring_window_steps

    # no window / non-causal: every rotation runs
    assert _ring_window_steps(8, 16, None, True) == 8
    assert _ring_window_steps(8, 16, 64, False) == 8
    # window inside one chunk: the own chunk + one older neighbor
    assert _ring_window_steps(8, 16, 1, True) == 1
    assert _ring_window_steps(8, 16, 16, True) == 2
    # window spanning chunks; never exceeds n. window=17 from the oldest
    # query row (pos i*c) reaches pos i*c - 16: still chunk i-1 -> 2
    # rotations; 18 reaches i*c - 17: chunk i-2 -> 3
    assert _ring_window_steps(8, 16, 17, True) == 2
    assert _ring_window_steps(8, 16, 18, True) == 3
    assert _ring_window_steps(8, 16, 1000, True) == 8


@pytest.mark.parametrize("window", [1, 5, 8, 9, 24])
def test_ring_window_skipped_rotations_exact(window):
    """Correctness across the skip boundary: windows smaller than, equal
    to, and spanning the per-device chunk (S=32 over 4 devices -> chunk
    8) all reproduce the dense windowed function."""
    mesh = make_mesh({"seq": 4})
    rng = np.random.default_rng(15)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 32, 4, 8)), jnp.float32)
        for _ in range(3)
    )
    want = np.asarray(dense_attention(q, k, v, causal=True, window=window))
    got = np.asarray(
        ring_attention(q, k, v, mesh, causal=True, window=window)
    )
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_ring_window_gradients_match_dense():
    """Differentiability through the TRUNCATED scan (n_steps < n): a
    broken transpose of the shortened rotation loop would surface here."""
    mesh = make_mesh({"seq": 4})
    rng = np.random.default_rng(16)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
        for _ in range(3)
    )
    g = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    W = 9  # 2 of 4 rotations live

    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, mesh, causal=True, window=W) * g),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(
            dense_attention(q, k, v, causal=True, window=W) * g),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )
