"""Pallas flash-attention kernel vs the dense XLA reference.

On the CPU test mesh the kernel runs in interpreter mode — the identical
kernel body that compiles for TPU, so the blockwise math (streaming
softmax, causal/padding masks, VMEM scratch carry across the K grid) is
exercised everywhere.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.attention import dense_attention
from mmlspark_tpu.ops.flash_attention import flash_attention


def _qkv(rng, b=2, s=32, h=2, d=8):
    shape = (b, s, h, d)
    return tuple(
        jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(rng, causal):
    q, k, v = _qkv(rng)
    expect = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_flash_padding_seq_not_multiple_of_block(rng):
    # S=20 with block 16 -> padded to 32; padded keys must be masked out
    q, k, v = _qkv(rng, s=20)
    expect = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_flash_gradients_match_dense(rng):
    q, k, v = _qkv(rng, b=1, s=16, h=2, d=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_under_jit(rng):
    q, k, v = _qkv(rng, s=16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, block=8))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(dense_attention(q, k, v)),
        atol=1e-5, rtol=1e-5,
    )


def test_transformer_flash_impl(rng):
    from mmlspark_tpu.models import build_model

    ids = jnp.asarray(rng.integers(0, 64, size=(2, 16)), jnp.int32)
    dense_g = build_model("transformer_lm", vocab_size=64, d_model=32,
                          heads=4, depth=1, max_len=16, attn_impl="dense")
    flash_g = build_model("transformer_lm", vocab_size=64, d_model=32,
                          heads=4, depth=1, max_len=16, attn_impl="flash")
    variables = dense_g.init(jax.random.PRNGKey(0), ids)
    np.testing.assert_allclose(
        np.asarray(flash_g.apply(variables, ids)),
        np.asarray(dense_g.apply(variables, ids)),
        atol=2e-2, rtol=2e-2,
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_padded_seq(rng, causal):
    """Backward at a sequence length that is NOT a block multiple: padded
    rows/keys must contribute exactly zero gradient."""
    q, k, v = _qkv(rng, s=11)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block=8) ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
        )


def test_flash_gradients_multiblock(rng):
    """Grid accumulation across several q/k blocks in both bwd kernels."""
    q, k, v = _qkv(rng, b=1, s=32)
    w = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block=8) * w)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
        )


def _dense_window_ref(q, k, v, window):
    import jax
    import jax.numpy as jnp
    import numpy as np

    S = q.shape[1]
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    qpos = np.arange(S)[:, None]
    kpos = np.arange(S)[None, :]
    keep = (kpos <= qpos) & (kpos > qpos - window)
    s = jnp.where(keep[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("S,block,window", [
    (64, 16, 16),   # window == block
    (64, 16, 24),   # window spans block boundary
    (40, 16, 7),    # window < block, padded sequence
    (96, 32, 96),   # window == full length (degenerates to causal)
])
def test_sliding_window_forward_matches_dense(S, block, window):
    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, S, 2, 16)), jnp.float32)
        for _ in range(3)
    )
    got = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block=block
        )
    )(q, k, v)
    want = _dense_window_ref(q, k, v, window)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_sliding_window_grads_match_dense():
    S, block, window = 48, 16, 20
    rng = np.random.default_rng(6)
    q, k, v, g = (
        jnp.asarray(rng.normal(size=(1, S, 2, 16)), jnp.float32)
        for _ in range(4)
    )
    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, block=block) * g),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(_dense_window_ref(q, k, v, window) * g),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def test_window_requires_causal_and_positive():
    q = jnp.ones((1, 8, 1, 4), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=4)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, q, q, causal=True, window=0)


def test_transformer_lm_sliding_window():
    """window plumbs from the model builder through the flash kernel and
    changes the function (a token outside the window stops influencing
    the current position's logits)."""
    from mmlspark_tpu.models.registry import build_model

    m = build_model("transformer_lm", vocab_size=32, d_model=16, heads=2,
                    depth=1, max_len=24, attn_impl="flash", window=4)
    assert m.extra["window"] == 4
    x = jnp.asarray(np.arange(24)[None] % 32, jnp.int32)
    vars_ = m.init(jax.random.PRNGKey(0), x)
    base = np.asarray(jax.jit(m.apply)(vars_, x))
    # perturb a token 8 positions back: outside window=4 for the last pos
    x2 = np.array(x)
    x2[0, 24 - 9] = (x2[0, 24 - 9] + 1) % 32
    out2 = np.asarray(jax.jit(m.apply)(vars_, jnp.asarray(x2)))
    assert np.allclose(base[0, -1], out2[0, -1], atol=1e-5)
    # ...but inside the window it does influence
    x3 = np.array(x)
    x3[0, 24 - 2] = (x3[0, 24 - 2] + 1) % 32
    out3 = np.asarray(jax.jit(m.apply)(vars_, jnp.asarray(x3)))
    assert not np.allclose(base[0, -1], out3[0, -1], atol=1e-5)


def test_window_uniform_across_dense_and_flash():
    """window is one feature across impls: the dense path and the flash
    kernel produce the same windowed function for identical params."""
    from mmlspark_tpu.models.registry import build_model

    x = jnp.asarray(np.arange(16)[None] % 32, jnp.int32)
    outs = {}
    for impl in ("dense", "flash"):
        m = build_model("transformer_lm", vocab_size=32, d_model=16,
                        heads=2, depth=1, max_len=16, attn_impl=impl,
                        window=5)
        vars_ = m.init(jax.random.PRNGKey(0), x)  # same seed -> same params
        outs[impl] = np.asarray(
            jax.jit(m.apply)(vars_, x), np.float32
        )
    np.testing.assert_allclose(outs["dense"], outs["flash"],
                               atol=2e-2, rtol=2e-2)  # bf16 activations


def test_dense_window_requires_causal():
    from mmlspark_tpu.ops.attention import dense_attention

    q = jnp.ones((1, 8, 1, 4), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, q, q, window=4)


@pytest.mark.parametrize("h_q,h_kv,causal,window", [
    (4, 1, False, None),   # MQA
    (4, 2, True, None),    # GQA causal
    (6, 2, True, 20),      # GQA + sliding window
])
def test_gqa_matches_repeated_dense(h_q, h_kv, causal, window):
    """K/V with fewer heads: kernel output and all three grads match the
    dense reference run on explicitly repeated K/V (with the repeated
    grads summed back per kv head)."""
    from mmlspark_tpu.ops.attention import dense_attention

    S, d = 48, 16
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(2, S, h_q, d)), jnp.float32)
    k, v = (
        jnp.asarray(rng.normal(size=(2, S, h_kv, d)), jnp.float32)
        for _ in range(2)
    )
    g = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    kw = dict(causal=causal, window=window)

    got = jax.jit(lambda q, k, v: flash_attention(q, k, v, block=16, **kw)
                  )(q, k, v)
    want = jax.jit(lambda q, k, v: dense_attention(q, k, v, **kw)
                   )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, block=16, **kw) * g),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, **kw) * g),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
            err_msg=name,
        )


def test_gqa_rejects_non_dividing_heads():
    q = jnp.ones((1, 8, 3, 4), jnp.float32)
    kv = jnp.ones((1, 8, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q, kv, kv)


def test_transformer_lm_gqa():
    """kv_heads plumbs through the builder: the qkv projection shrinks
    and the model still runs forward+grad under flash and dense."""
    from mmlspark_tpu.models.registry import build_model

    x = jnp.asarray(np.arange(16)[None] % 32, jnp.int32)
    for impl in ("dense", "flash"):
        m = build_model("transformer_lm", vocab_size=32, d_model=16,
                        heads=4, depth=1, max_len=16, attn_impl=impl,
                        kv_heads=2)
        assert m.extra["kv_heads"] == 2
        vars_ = m.init(jax.random.PRNGKey(0), x)
        kernel = vars_["block0"]["params"]["attn"]["qkv"]["kernel"]
        assert kernel.shape[-1] == (4 + 2 * 2) * 4  # (h + 2*hk) * d
        loss = jax.jit(lambda p, m=m: jnp.mean(
            m.apply(p, x).astype(jnp.float32) ** 2))
        g = jax.jit(jax.grad(loss))(vars_)
        assert float(loss(vars_)) > 0
        assert jax.tree_util.tree_reduce(
            lambda a, b: a + float(jnp.sum(jnp.abs(b))), g, 0.0) > 0

    from mmlspark_tpu.core.exceptions import ParamError
    with pytest.raises(ParamError, match="kv_heads"):
        build_model("transformer_lm", vocab_size=32, d_model=16, heads=4,
                    depth=1, max_len=16, kv_heads=3)


def test_rope_relative_position_invariance():
    """<rope(q,p), rope(k,p')> depends only on p - p': shifting both
    positions by a constant leaves every pairwise dot product unchanged."""
    from mmlspark_tpu.ops.rope import apply_rope

    rng = np.random.default_rng(13)
    q, k = (
        jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
        for _ in range(2)
    )
    def dots(shift):
        pos = jnp.arange(8) + shift
        qr = apply_rope(q, pos)
        kr = apply_rope(k, pos)
        return np.asarray(jnp.einsum("bqhd,bkhd->bhqk", qr, kr))
    np.testing.assert_allclose(dots(0), dots(100), atol=1e-4, rtol=1e-4)


def test_rope_preserves_norm_and_dtype():
    from mmlspark_tpu.ops.rope import apply_rope

    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.bfloat16)
    r = apply_rope(x)
    assert r.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x, np.float32), axis=-1),
        np.linalg.norm(np.asarray(r, np.float32), axis=-1),
        atol=2e-1, rtol=2e-2,  # bf16 storage
    )
    with pytest.raises(ValueError, match="even"):
        apply_rope(jnp.ones((1, 4, 1, 5), jnp.float32))


def test_transformer_lm_rope():
    """pos_embedding='rope': no learned position table in the params,
    forward+grad runs, and the ONNX exporter handles it (r5 — full
    round-trip parity lives in tests/test_onnx_export.py)."""
    from mmlspark_tpu.core.exceptions import ParamError
    from mmlspark_tpu.models.onnx_export import export_onnx
    from mmlspark_tpu.models.registry import build_model

    m = build_model("transformer_lm", vocab_size=32, d_model=16, heads=2,
                    depth=1, max_len=16, attn_impl="flash",
                    pos_embedding="rope")
    assert m.extra["pos_embedding"] == "rope"
    x = jnp.asarray(np.arange(16)[None] % 32, jnp.int32)
    vars_ = m.init(jax.random.PRNGKey(0), x)
    assert "pos" not in vars_["embed"]["params"]
    loss = jax.jit(lambda p: jnp.mean(
        m.apply(p, x).astype(jnp.float32) ** 2))
    assert float(loss(vars_)) > 0
    g = jax.jit(jax.grad(loss))(vars_)
    assert jax.tree_util.tree_reduce(
        lambda a, b: a + float(jnp.sum(jnp.abs(b))), g, 0.0) > 0
    assert len(export_onnx(m, vars_, (1, 16))) > 0  # exports since r5
    with pytest.raises(ParamError, match="pos_embedding"):
        build_model("transformer_lm", vocab_size=32, d_model=16, heads=2,
                    depth=1, max_len=16, pos_embedding="alibi")


# -- the block chooser: pure Python, no kernel runs ---------------------------


@pytest.mark.parametrize("name,s,d,dv,want", [
    # gpt2-medium.train-dp4's call on one chip: (8, 1024, 16, 64)
    ("train_dp4", 1024, 64, 64, 1024),
    # gpt2-large's prefill buckets: a bucket is one block a head
    ("gpt2_large_32", 32, 64, 64, 32),
    ("gpt2_large_64", 64, 64, 64, 64),
    ("gpt2_large_128", 128, 64, 64, 128),
    ("gpt2_large_256", 256, 64, 64, 256),
    # mimo-v2-flash's prefill buckets, full and window layers alike
    # (q/k 192, v 128)
    ("mimo_512", 512, 192, 128, 512),
    ("mimo_1024", 1024, 192, 128, 1024),
    ("mimo_2048", 2048, 192, 128, 1024),
    ("mimo_4096", 4096, 192, 128, 1024),
    # no whole number of the largest block: padded to whole lane tiles
    # and no further, the block a divisor of those
    ("odd_1000", 1000, 64, 64, 1024),
    ("odd_700", 700, 64, 64, 768),
    ("odd_1152", 1152, 64, 64, 384),
    ("odd_5000", 5000, 64, 64, 1024),
    # shorter than a lane tile: one block of whole sublanes
    ("short_20", 20, 64, 64, 24),
    ("short_100", 100, 128, 128, 104),
])
def test_block_chooser(name, s, d, dv, want):
    from mmlspark_tpu.ops import flash_attention as fa

    blk = fa._flash_block(s, d, dv, 2)
    assert blk == want
    assert blk % fa.SUBLANES == 0
    # the block divides the length padded to whole lane tiles (whole
    # sublanes under one tile): a larger block adds no padded rows
    assert fa._round_up(s, blk) == fa._round_up(
        s, fa.LANES if s > fa.LANES else fa.SUBLANES)
    assert blk <= fa._FLASH_BLOCK_MOST
    assert fa._flash_vmem_bytes(blk, d, dv, 2) <= fa._FLASH_VMEM
    # float32 operands are held to the same budget
    assert fa._flash_vmem_bytes(
        fa._flash_block(s, d, dv, 4), d, dv, 4) <= fa._FLASH_VMEM


@pytest.mark.parametrize("window", [None, 40])
def test_chosen_block_pads_and_matches_dense(rng, window):
    # no block= : 136 rows are padded to two lane tiles and taken as ONE
    # block of 256, so padded keys AND padded query rows are masked
    q, k, v = _qkv(rng, b=1, s=136, h=2, d=8)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    kw = dict(causal=True, window=window)
    got, grads = jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw) * w),
        argnums=(0, 1, 2))(q, k, v)
    want, want_grads = jax.value_and_grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, **kw) * w),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
