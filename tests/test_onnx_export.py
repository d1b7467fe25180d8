"""ONNX export round-trips (the SerializableFunction write-path analog)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models import build_model
from mmlspark_tpu.models.onnx_export import export_onnx, save_onnx
from mmlspark_tpu.models.onnx_import import load_onnx


def test_mlp_round_trip(rng):
    g = build_model("mlp", num_outputs=3, hidden=(8, 6))
    v = g.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
    x = rng.normal(size=(5, 4)).astype(np.float32)
    want = np.asarray(g.apply(v, jnp.asarray(x)))
    g2 = load_onnx(export_onnx(g, v, (5, 4)))
    got = np.asarray(g2.apply(g2.init(), jnp.asarray(x)))
    # flax computes hidden layers in bfloat16; the ONNX path is float32,
    # so agreement is to bf16 resolution
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_linear_round_trip(rng):
    g = build_model("linear", num_outputs=2)
    v = g.init(jax.random.PRNGKey(1), jnp.zeros((1, 6)))
    x = rng.normal(size=(4, 6)).astype(np.float32)
    want = np.asarray(g.apply(v, jnp.asarray(x)))
    g2 = load_onnx(export_onnx(g, v, (4, 6)))
    got = np.asarray(g2.apply(g2.init(), jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_bilstm_tagger_round_trip(rng):
    g = build_model(
        "bilstm_tagger", vocab_size=30, embed_dim=6, hidden=5, num_tags=4
    )
    v = g.init(jax.random.PRNGKey(1), jnp.zeros((1, 7), jnp.int32))
    ids = rng.integers(0, 30, (3, 7)).astype(np.int32)
    want = np.asarray(g.apply(v, jnp.asarray(ids)))
    g2 = load_onnx(export_onnx(g, v, (3, 7)))
    got = np.asarray(g2.apply(g2.init(), jnp.asarray(ids)))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # per-token argmax tags agree exactly
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


def test_exported_graph_compiles_under_jit(rng):
    """Reshape targets bake static dims, so the imported graph must trace
    cleanly (shape constants resolve from initializers, not tracers)."""
    g = build_model(
        "bilstm_tagger", vocab_size=12, embed_dim=4, hidden=3, num_tags=2
    )
    v = g.init(jax.random.PRNGKey(0), jnp.zeros((1, 5), jnp.int32))
    g2 = load_onnx(export_onnx(g, v, (2, 5)))
    fwd = jax.jit(lambda vv, x: g2.apply(vv, x))
    ids = rng.integers(0, 12, (2, 5)).astype(np.int32)
    out = np.asarray(fwd(g2.init(), jnp.asarray(ids)))
    assert out.shape == (2, 5, 2)


def test_save_onnx_writes_file(tmp_path, rng):
    g = build_model("linear", num_outputs=2)
    v = g.init(jax.random.PRNGKey(0), jnp.zeros((1, 3)))
    path = str(tmp_path / "m.onnx")
    save_onnx(g, v, (2, 3), path)
    with open(path, "rb") as f:
        g2 = load_onnx(f.read())
    assert g2.layer_names == ["z"]


def test_unsupported_family_errors():
    g = build_model("resnet20_cifar10", width=8)
    v = jax.jit(g.init)(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    with pytest.raises(FriendlyError, match="no ONNX exporter"):
        export_onnx(g, v, (1, 32, 32, 3))


def test_transformer_lm_round_trip(rng):
    """Causal transformer -> primitive-op ONNX (decomposed LayerNorm,
    attention, tanh-gelu) -> import; logits agree to bf16 resolution and
    the block-output named-node cut works like on the flax graph."""
    B, T = 2, 10
    g = build_model(
        "transformer_lm", vocab_size=32, d_model=16, heads=4, depth=2,
        max_len=T, attn_impl="dense",
    )
    v = jax.jit(g.init)(jax.random.PRNGKey(1), jnp.zeros((1, T), jnp.int32))
    ids = rng.integers(0, 32, size=(B, T)).astype(np.int32)
    want = np.asarray(jax.jit(g.apply)(v, jnp.asarray(ids)))

    g2 = load_onnx(export_onnx(g, v, (B, T)))
    got = np.asarray(jax.jit(g2.apply)(g2.init(), jnp.asarray(ids)))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.95

    # named-node cut at a block output = flax-side layer_names contract
    hidden = np.asarray(
        g2.apply(g2.init(), jnp.asarray(ids), output_node="block0")
    )
    assert hidden.shape == (B, T, 16)
    flax_hidden = np.asarray(
        g.apply(v, jnp.asarray(ids), output_node="block0")
    )
    np.testing.assert_allclose(hidden, flax_hidden, rtol=5e-2, atol=5e-2)


def test_transformer_lm_non_causal_round_trip(rng):
    """Encoder (bidirectional) export drops the causal mask."""
    B, T = 2, 6
    g = build_model(
        "transformer_lm", vocab_size=16, d_model=8, heads=2, depth=1,
        max_len=T, causal=False, attn_impl="dense",
    )
    v = jax.jit(g.init)(jax.random.PRNGKey(2), jnp.zeros((1, T), jnp.int32))
    ids = rng.integers(0, 16, size=(B, T)).astype(np.int32)
    want = np.asarray(jax.jit(g.apply)(v, jnp.asarray(ids)))
    g2 = load_onnx(export_onnx(g, v, (B, T)))
    got = np.asarray(jax.jit(g2.apply)(g2.init(), jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


def test_transformer_lm_rope_round_trip(rng):
    """RoPE export (r5): position enters as the in-graph rotate-half of
    q/k against cos/sin constants — no position table in the payload —
    and the round trip must agree with the flax model like the
    learned-pos path does."""
    B, T = 2, 10
    g = build_model(
        "transformer_lm", vocab_size=32, d_model=16, heads=4, depth=2,
        max_len=T, attn_impl="dense", pos_embedding="rope",
    )
    v = jax.jit(g.init)(jax.random.PRNGKey(3), jnp.zeros((1, T), jnp.int32))
    ids = rng.integers(0, 32, size=(B, T)).astype(np.int32)
    want = np.asarray(jax.jit(g.apply)(v, jnp.asarray(ids)))
    g2 = load_onnx(export_onnx(g, v, (B, T)))
    got = np.asarray(jax.jit(g2.apply)(g2.init(), jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.95


def test_transformer_lm_window_round_trip(rng):
    """Sliding-window export (r5): the in-graph additive mask must ALSO
    kill out-of-window keys — silently exporting a full-causal graph
    for a window model would diverge past the window. Covered for both
    position modes, with T well past the window."""
    B, T, W = 2, 12, 4
    for pos_mode in ("learned", "rope"):
        g = build_model(
            "transformer_lm", vocab_size=32, d_model=16, heads=4,
            depth=1, max_len=T, attn_impl="dense", window=W,
            pos_embedding=pos_mode,
        )
        v = jax.jit(g.init)(jax.random.PRNGKey(5), jnp.zeros((1, T), jnp.int32))
        ids = rng.integers(0, 32, size=(B, T)).astype(np.int32)
        want = np.asarray(jax.jit(g.apply)(v, jnp.asarray(ids)))
        g2 = load_onnx(export_onnx(g, v, (B, T)))
        got = np.asarray(jax.jit(g2.apply)(g2.init(), jnp.asarray(ids)))
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2,
                                   err_msg=pos_mode)
        # allclose above is the mask-correctness gate (a dropped window
        # mask diverges logits wholesale past the window); the argmax
        # rate only guards gross divergence — random-init near-ties
        # flip a token or two between the bf16 flax model and the f32
        # export
        assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.85, pos_mode


def test_transformer_lm_gqa_round_trip(rng):
    """GQA export (r5): narrow K/V slices expand in-graph (Reshape →
    Expand → Reshape = jnp.repeat's kv-head-per-group layout); combined
    with RoPE to cover the full serving configuration."""
    B, T = 2, 8
    g = build_model(
        "transformer_lm", vocab_size=32, d_model=16, heads=4, depth=2,
        max_len=T, attn_impl="dense", kv_heads=2, pos_embedding="rope",
    )
    v = jax.jit(g.init)(jax.random.PRNGKey(6), jnp.zeros((1, T), jnp.int32))
    ids = rng.integers(0, 32, size=(B, T)).astype(np.int32)
    want = np.asarray(jax.jit(g.apply)(v, jnp.asarray(ids)))
    g2 = load_onnx(export_onnx(g, v, (B, T)))
    got = np.asarray(jax.jit(g2.apply)(g2.init(), jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.85
