"""Mixture-of-experts / expert parallelism tests (virtual 8-device CPU
mesh, see conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.exceptions import ParamError
from mmlspark_tpu.parallel import EXPERT_RULES, make_mesh
from mmlspark_tpu.parallel.expert import (
    moe_dispatch,
    moe_ffn,
    router_probs,
    validate_experts,
)


def test_dispatch_routes_each_token_once():
    rng = jax.random.PRNGKey(0)
    probs = jax.nn.softmax(jax.random.normal(rng, (16, 4)), axis=-1)
    dispatch, combine, aux = moe_dispatch(probs, capacity=16)
    d = np.asarray(dispatch)
    # with ample capacity every token lands in exactly one (expert, slot)
    assert np.all(d.sum(axis=(1, 2)) == 1.0)
    # combine weights equal the chosen expert's router prob
    chosen = np.asarray(probs).max(axis=1)
    np.testing.assert_allclose(
        np.asarray(combine).sum(axis=(1, 2)), chosen, rtol=1e-6
    )
    assert np.isfinite(float(aux))


def test_dispatch_capacity_drops_overflow():
    # all tokens prefer expert 0; capacity 2 keeps exactly 2
    probs = jnp.tile(jnp.array([[0.9, 0.1]]), (8, 1))
    dispatch, _, _ = moe_dispatch(probs, capacity=2)
    kept = np.asarray(dispatch).sum()
    assert kept == 2.0


def test_moe_ffn_matches_per_token_expert_dense():
    # with ample capacity, each token's MoE output equals its argmax
    # expert's dense FFN scaled by that expert's router probability
    rng = np.random.default_rng(0)
    b, t, d, f, e = 2, 4, 8, 16, 3
    x = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32)
    b_in = jnp.asarray(rng.normal(size=(e, f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    b_out = jnp.asarray(rng.normal(size=(e, d)) * 0.1, jnp.float32)
    out, aux = moe_ffn(x, gate, w_in, b_in, w_out, b_out,
                       capacity_factor=float(e))  # capacity = n tokens
    probs = np.asarray(router_probs(x.reshape(-1, d), gate))
    chosen = probs.argmax(-1)
    flat = np.asarray(x).reshape(-1, d)
    def dense_expert(tok, c):
        h = np.asarray(jax.nn.gelu(tok @ np.asarray(w_in[c])
                                   + np.asarray(b_in[c])))
        return h @ np.asarray(w_out[c]) + np.asarray(b_out[c])

    want = np.stack(
        [probs[i, c] * dense_expert(flat[i], c)
         for i, c in enumerate(chosen)]
    ).reshape(b, t, d)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-3, atol=1e-4)
    assert np.isfinite(float(aux))


def test_dispatch_mask_excludes_padding():
    rng = jax.random.PRNGKey(1)
    probs = jax.nn.softmax(jax.random.normal(rng, (8, 2)), axis=-1)
    mask = jnp.array([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32)
    dispatch, combine, aux = moe_dispatch(probs, capacity=8, mask=mask)
    d = np.asarray(dispatch)
    # padding tokens route nowhere and consume no capacity
    assert np.all(d[4:].sum(axis=(1, 2)) == 0.0)
    assert np.all(d[:4].sum(axis=(1, 2)) == 1.0)
    # aux equals the unmasked aux computed on real tokens only
    _, _, aux_real = moe_dispatch(probs[:4], capacity=8)
    np.testing.assert_allclose(float(aux), float(aux_real), rtol=1e-6)


def test_moe_ffn_mask_zeroes_padding_rows():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 2, 6)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(6, 2)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(2, 6, 8)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(2, 8, 6)) * 0.1, jnp.float32)
    zeros_in, zeros_out = jnp.zeros((2, 8)), jnp.zeros((2, 6))
    mask = jnp.array([1, 1, 0, 0], jnp.float32)
    out, _ = moe_ffn(x, gate, w_in, zeros_in, w_out, zeros_out,
                     capacity_factor=2.0, mask=mask)
    assert np.all(np.asarray(out)[2:] == 0.0)  # padding rows untouched
    assert np.any(np.asarray(out)[:2] != 0.0)


def test_router_probs_normalized():
    x = jnp.ones((3, 5, 4))
    gate = jnp.eye(4, 6)
    p = router_probs(x, gate)
    np.testing.assert_allclose(np.asarray(p.sum(-1)),
                               np.ones((3, 5)), rtol=1e-6)


def test_validate_experts():
    with pytest.raises(ParamError):
        validate_experts(1)
    mesh = make_mesh({"expert": 4})
    with pytest.raises(ParamError):
        validate_experts(6, mesh)
    validate_experts(8, mesh)  # ok


def test_moe_lm_forward_and_grad():
    from mmlspark_tpu.models import build_model

    graph = build_model(
        "transformer_lm_moe", vocab_size=32, d_model=16, heads=2, depth=1,
        n_experts=4, max_len=8,
    )
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, size=(4, 8)), jnp.int32
    )
    variables = graph.init(jax.random.PRNGKey(0), ids[:1])
    # init must not persist per-call sown losses
    assert all("losses" not in v for v in variables.values())
    out = graph.apply(variables, ids)
    assert out.shape == (4, 8, 32)
    out2, updated = graph.apply(variables, ids, train=True)
    assert "losses" in updated["block0"]
    aux = jax.tree_util.tree_leaves(updated["block0"]["losses"])
    assert len(aux) == 1 and np.isfinite(float(aux[0]))


def test_trainer_moe_expert_parallel():
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig

    mesh_axes = {"data": 2, "expert": 4}
    mesh = make_mesh(mesh_axes)
    graph = build_model(
        "transformer_lm_moe", vocab_size=32, d_model=16, heads=2, depth=1,
        n_experts=4, max_len=8, mesh=mesh,
    )
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 32, size=(16, 8)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    trainer = SPMDTrainer(
        graph,
        TrainConfig(
            epochs=2, batch_size=8, learning_rate=1e-2,
            mesh_axes=mesh_axes, param_rules=EXPERT_RULES,
            log_every=1, shuffle=False,
        ),
    )
    variables = trainer.train(ids, labels)
    losses = [h["loss"] for h in trainer.history if "loss" in h]
    assert len(losses) >= 2 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    out = graph.apply(variables, jnp.asarray(ids[:2]))
    assert out.shape == (2, 8, 32)


def test_trainer_moe_checkpoint_resume(tmp_path):
    # regression: sown losses must not leak into the carried rest tree,
    # or restore against the init-derived target fails
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig

    graph = build_model(
        "transformer_lm_moe", vocab_size=16, d_model=8, heads=2, depth=1,
        n_experts=2, max_len=4,
    )
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 16, size=(8, 4)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    cfg = dict(
        batch_size=4, learning_rate=1e-2, log_every=1, shuffle=False,
        mesh_axes={"data": 2},  # keep batch at 4 -> 2 steps per epoch
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1,
    )
    SPMDTrainer(graph, TrainConfig(epochs=1, **cfg)).train(ids, labels)
    resumed = SPMDTrainer(graph, TrainConfig(epochs=2, **cfg))
    variables = resumed.train(ids, labels)
    steps = [h["step"] for h in resumed.history if "loss" in h]
    assert steps and min(steps) >= 2  # resumed past epoch 1
    out = graph.apply(variables, jnp.asarray(ids[:2]))
    assert out.shape == (2, 4, 16)


def test_moe_ffn_prime_token_count_keeps_group_size():
    """Non-smooth token counts must pad to the group multiple, not
    degenerate to 1-token groups (the old divisor-of-n scheme made
    capacity vacuous for prime B*T)."""
    rng = np.random.default_rng(3)
    b, t, d, f, e = 1, 13, 8, 16, 3  # 13 tokens: prime
    x = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32)
    b_in = jnp.asarray(rng.normal(size=(e, f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    b_out = jnp.asarray(rng.normal(size=(e, d)) * 0.1, jnp.float32)
    out, aux = moe_ffn(x, gate, w_in, b_in, w_out, b_out,
                       capacity_factor=float(e), group_size=8)
    assert out.shape == (b, t, d)
    assert np.isfinite(float(aux))
    # ample capacity: must match the per-token dense computation exactly,
    # including the final (padded) partial group
    probs = np.asarray(router_probs(x.reshape(-1, d), gate))
    chosen = probs.argmax(-1)
    flat = np.asarray(x).reshape(-1, d)

    def dense_expert(tok, c):
        h = np.asarray(jax.nn.gelu(tok @ np.asarray(w_in[c])
                                   + np.asarray(b_in[c])))
        return h @ np.asarray(w_out[c]) + np.asarray(b_out[c])

    want = np.stack(
        [probs[i, c] * dense_expert(flat[i], c)
         for i, c in enumerate(chosen)]
    ).reshape(b, t, d)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-3, atol=1e-4)


def test_moe_attention_features_uniform_with_dense_lm():
    """The MoE family carries the same attention feature set: a
    window + MQA + RoPE MoE LM builds, runs forward+grad, and records
    the config in extra (no learned position table under rope)."""
    from mmlspark_tpu.models import build_model

    m = build_model("transformer_lm_moe", vocab_size=32, d_model=16,
                    heads=4, depth=1, n_experts=2, max_len=16,
                    window=6, kv_heads=1, pos_embedding="rope")
    assert m.extra["window"] == 6 and m.extra["kv_heads"] == 1
    x = jnp.asarray(np.arange(16)[None] % 32, jnp.int32)
    v = m.init(jax.random.PRNGKey(0), x)
    assert "pos" not in v["embed"]["params"]
    loss = jax.jit(lambda p: jnp.mean(
        m.apply(p, x).astype(jnp.float32) ** 2))
    assert float(loss(v)) > 0
    g = jax.jit(jax.grad(loss))(v)
    assert jax.tree_util.tree_reduce(
        lambda a, b: a + float(jnp.sum(jnp.abs(b))), g, 0.0) > 0

    with pytest.raises(ParamError, match="kv_heads"):
        build_model("transformer_lm_moe", vocab_size=32, d_model=16,
                    heads=4, depth=1, n_experts=2, max_len=16, kv_heads=3)


def test_moe_ffn_dropless_matches_capacity_path():
    """The decode-step dropless router must equal the capacity path
    wherever the latter drops nothing (ample capacity) — the numerical
    contract that makes kv-cache MoE generation exact."""
    from mmlspark_tpu.parallel.expert import moe_ffn_dropless

    rng = np.random.default_rng(1)
    b, t, d, f, e = 2, 4, 8, 16, 3
    x = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32)
    b_in = jnp.asarray(rng.normal(size=(e, f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    b_out = jnp.asarray(rng.normal(size=(e, d)) * 0.1, jnp.float32)
    cap_out, _ = moe_ffn(x, gate, w_in, b_in, w_out, b_out,
                         capacity_factor=float(e))
    drop_out = moe_ffn_dropless(x, gate, w_in, b_in, w_out, b_out)
    np.testing.assert_allclose(np.asarray(drop_out), np.asarray(cap_out),
                               rtol=1e-4, atol=1e-5)


def test_moe_generate_kv_cache_matches_unpadded_oracle():
    """MoE generation (round 5): the kv-cache path routes the prefill
    through the capacity path over the UNPADDED prompt and decode steps
    droplessly. With capacity >= tokens (nothing ever dropped), greedy
    tokens must equal the growing-unpadded-buffer oracle — a plain
    scoring forward per step, the semantics a user scores with."""
    from mmlspark_tpu.core.exceptions import FriendlyError
    from mmlspark_tpu.models import generate
    from tests.serve_helpers import trained_lm

    m, v, ids = trained_lm(
        "transformer_lm_moe", steps=40, n_experts=2,
        capacity_factor=2.0,  # capacity = tokens
    )
    prompt = ids[:, :6]
    out = np.asarray(generate(m, v, prompt, max_new_tokens=8))
    buf = np.asarray(prompt)
    score = jax.jit(m.apply)  # a program a length, not one an operation
    for _ in range(8):
        lg = np.asarray(score(v, jnp.asarray(buf)))
        nxt = lg[:, -1].argmax(-1).astype(np.int32)
        buf = np.concatenate([buf, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, buf)
    # the pad-filled recompute path stays rejected
    with pytest.raises(FriendlyError, match="kv_cache"):
        generate(m, v, prompt, max_new_tokens=2, kv_cache=False)


def test_moe_one_token_prompt_prefill_uses_capacity_routing():
    """Regression (r5 review): a (B, 1) PROMPT is a prefill, not a
    decode step — its logits must equal the plain scoring forward even
    under a capacity so tight that the dropless decode router would
    disagree (all rows route to one expert; capacity keeps only one)."""
    from mmlspark_tpu.models import build_model, generate

    m = build_model(
        "transformer_lm_moe", vocab_size=8, d_model=16, heads=2, depth=1,
        max_len=8, n_experts=2, capacity_factor=0.5,
    )
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    prompt = jnp.full((4, 1), 3, jnp.int32)  # identical rows: one expert
    out = np.asarray(generate(m, v, prompt, max_new_tokens=1))
    want = np.asarray(m.apply(v, prompt))[:, -1].argmax(-1)
    np.testing.assert_array_equal(out[:, 1], want)
