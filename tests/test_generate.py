"""Autoregressive generation over the causal transformer family.

The reference has no generative model (SURVEY §5); generate() is part of
the long-context capability upgrade, so its tests are behavioral: a tiny
LM overfit on a periodic stream must CONTINUE the period, greedy decode
must be deterministic, and every attention configuration (window, GQA,
RoPE) must decode through the same utility.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models import beam_search, build_model, generate
from tests.serve_helpers import PERIOD, init_lm, ref_tokens, trained_lm

#: this file's models take 60 steps (the serving files' 30), as they did
trained = functools.partial(trained_lm, steps=60)


@pytest.mark.parametrize("config", [
    {},                                            # plain learned-pos
    {"window": 6},                                 # sliding window
    {"pos_embedding": "rope", "kv_heads": 1},      # RoPE + MQA
])
def test_overfit_lm_continues_the_period(config):
    m, v, ids = trained(**config)
    prompt = ids[:, :8]
    out = np.asarray(generate(m, v, prompt, max_new_tokens=8))
    want = (np.arange(16) % PERIOD) + 1
    np.testing.assert_array_equal(out[0], want)


@pytest.mark.parametrize("config", [
    {},                                            # plain learned-pos
    {"window": 6},                                 # rolled window cache
    {"pos_embedding": "rope", "kv_heads": 1},      # RoPE + MQA
    {"window": 6, "kv_heads": 1},                  # rolled cache + GQA
])
def test_kv_cache_matches_recompute_oracle(config):
    """The cached decode (one-token steps against preallocated K/V
    buffers) must produce the same tokens as the O(T²) full-recompute
    path — per config, since window masking, GQA buffer geometry, and
    RoPE offset tables are each their own cached code path."""
    m, v, ids = trained(**config)
    prompt = ids[:, :5]
    kv = np.asarray(generate(m, v, prompt, max_new_tokens=9))
    rc = np.asarray(generate(m, v, prompt, max_new_tokens=9,
                             kv_cache=False))
    np.testing.assert_array_equal(kv, rc)
    # sampling consumes the SAME rng stream on both paths
    skv = np.asarray(generate(m, v, prompt, max_new_tokens=9,
                              temperature=0.8, rng=jax.random.PRNGKey(7)))
    src = np.asarray(generate(m, v, prompt, max_new_tokens=9,
                              temperature=0.8, rng=jax.random.PRNGKey(7),
                              kv_cache=False))
    np.testing.assert_array_equal(skv, src)


@pytest.mark.parametrize("config", [
    {},
    {"pos_embedding": "rope", "kv_heads": 1},
])
def test_the_suites_jitted_reference_is_eager_generate(config):
    """The serving tests hold served streams to ``ref_tokens``, which is
    ``generate()`` under ``jax.jit``: the same tokens as the eager call,
    with and without an eos."""
    m, v, ids = trained(**config)
    prompt = np.asarray(ids[0, :8])
    for eos in (None, 3):
        eager = np.asarray(generate(m, v, prompt[None], 8, eos_id=eos))[0]
        np.testing.assert_array_equal(ref_tokens(m, v, prompt, 8, eos), eager)


def test_greedy_is_deterministic_and_sampling_needs_rng():
    m, v, ids = trained()
    prompt = ids[:, :8]
    a = np.asarray(generate(m, v, prompt, max_new_tokens=8))
    b = np.asarray(generate(m, v, prompt, max_new_tokens=8))
    np.testing.assert_array_equal(a, b)
    with pytest.raises(FriendlyError, match="rng"):
        generate(m, v, prompt, max_new_tokens=2, temperature=0.7)
    # sampling path runs and keeps the prompt intact
    s = np.asarray(generate(m, v, prompt, max_new_tokens=8,
                            temperature=0.7,
                            rng=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(s[:, :8], np.asarray(prompt))


def test_generate_guards():
    m = build_model("transformer_lm", vocab_size=8, d_model=16, heads=2,
                    depth=1, max_len=8)
    v = init_lm(m)
    prompt = jnp.zeros((1, 6), jnp.int32)
    with pytest.raises(FriendlyError, match="position table"):
        generate(m, v, prompt, max_new_tokens=4)  # 10 > max_len 8
    with pytest.raises(FriendlyError, match=">= 1"):
        generate(m, v, prompt, max_new_tokens=0)
    bidir = build_model("transformer_lm", vocab_size=8, d_model=16,
                        heads=2, depth=1, max_len=8, causal=False)
    bv = init_lm(bidir)
    with pytest.raises(FriendlyError, match="causal"):
        generate(bidir, bv, prompt, max_new_tokens=1)


def test_rope_generates_past_trained_max_len():
    """RoPE has no position table: generation may run past max_len (the
    structural-extrapolation property, impossible with learned pos)."""
    m, v, ids = trained(max_len=16, pos_embedding="rope")
    out = np.asarray(generate(m, v, ids, max_new_tokens=8))  # 24 > 16
    want = (np.arange(24) % PERIOD) + 1
    np.testing.assert_array_equal(out[0], want)


def test_eos_stops_rows_and_pads_the_tail():
    """eos_id: the trained model walks the period 1,2,3,4,...; stopping
    at eos_id=3 must keep tokens up to AND including the first 3, then
    pad — identically on the cache path and the recompute oracle."""
    m, v, ids = trained()
    prompt = ids[:, :8]  # ends ...3,4 → continuation 1,2,3,4,...
    kv = np.asarray(generate(m, v, prompt, max_new_tokens=8, eos_id=3))
    want = np.concatenate([
        np.asarray(prompt)[0], [1, 2, 3, 0, 0, 0, 0, 0],
    ])
    np.testing.assert_array_equal(kv[0], want)
    rc = np.asarray(generate(m, v, prompt, max_new_tokens=8, eos_id=3,
                             kv_cache=False))
    np.testing.assert_array_equal(kv, rc)
    # pad_id is honored for the tail fill
    pk = np.asarray(generate(m, v, prompt, max_new_tokens=8, eos_id=3,
                             pad_id=7))
    np.testing.assert_array_equal(
        pk[0], np.concatenate([np.asarray(prompt)[0],
                               [1, 2, 3, 7, 7, 7, 7, 7]])
    )


def test_rolled_window_cache_long_generation():
    """A sliding-window model generating far past both its window and
    its trained max_len: the decode carry holds O(window) K/V (the
    rolled circular buffers), RoPE extrapolates structurally, and the
    learned period must continue across many buffer wrap-arounds."""
    m, v, ids = trained(max_len=16, window=8, pos_embedding="rope")
    out = np.asarray(generate(m, v, ids, max_new_tokens=32))  # 48 >> W=8
    want = (np.arange(48) % PERIOD) + 1
    np.testing.assert_array_equal(out[0], want)


def test_top_k_and_top_p_sampling():
    """top_k=1 collapses sampling to greedy; a tight nucleus on a
    peaked (trained) model does too; loose filters reproduce the
    unfiltered stream rng-for-rng; guards reject meaningless configs."""
    m, v, ids = trained()
    prompt = ids[:, :8]
    greedy = np.asarray(generate(m, v, prompt, max_new_tokens=8))
    k1 = np.asarray(generate(m, v, prompt, max_new_tokens=8,
                             temperature=1.0, top_k=1,
                             rng=jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(k1, greedy)
    # the overfit model is sharply peaked: a 0.5 nucleus holds only the
    # top token, so nucleus sampling = greedy here
    p_small = np.asarray(generate(m, v, prompt, max_new_tokens=8,
                                  temperature=1.0, top_p=0.5,
                                  rng=jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(p_small, greedy)
    # loose filters change nothing about the sampled stream
    base = np.asarray(generate(m, v, prompt, max_new_tokens=8,
                               temperature=1.3,
                               rng=jax.random.PRNGKey(2)))
    loose = np.asarray(generate(m, v, prompt, max_new_tokens=8,
                                temperature=1.3, top_k=8, top_p=1.0,
                                rng=jax.random.PRNGKey(2)))
    np.testing.assert_array_equal(base, loose)
    with pytest.raises(FriendlyError, match="temperature"):
        generate(m, v, prompt, max_new_tokens=2, top_k=2)
    with pytest.raises(FriendlyError, match="top_k"):
        generate(m, v, prompt, max_new_tokens=2, temperature=1.0,
                 top_k=9, rng=jax.random.PRNGKey(0))
    with pytest.raises(FriendlyError, match="top_p"):
        generate(m, v, prompt, max_new_tokens=2, temperature=1.0,
                 top_p=1.5, rng=jax.random.PRNGKey(0))


def test_generate_rejects_moe_recompute_and_negative_temperature():
    m = build_model("transformer_lm", vocab_size=8, d_model=16, heads=2,
                    depth=1, max_len=16)
    v = init_lm(m)
    with pytest.raises(FriendlyError, match="temperature"):
        generate(m, v, jnp.zeros((1, 4), jnp.int32), max_new_tokens=2,
                 temperature=-0.5, rng=jax.random.PRNGKey(0))
    # MoE decodes on the kv-cache path (r5); only the pad-filled
    # recompute buffer stays rejected (capacity routing over pads is
    # not causal). Full MoE generation semantics: tests/test_moe.py.
    moe = build_model("transformer_lm_moe", vocab_size=8, d_model=16,
                      heads=2, depth=1, max_len=16, n_experts=2)
    mv = init_lm(moe)
    out = generate(moe, mv, jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)
    assert out.shape == (1, 6)
    with pytest.raises(FriendlyError, match="kv_cache"):
        generate(moe, mv, jnp.zeros((1, 4), jnp.int32), max_new_tokens=2,
                 kv_cache=False)


# -- beam search ------------------------------------------------------------


def test_beam_one_equals_greedy():
    m, v, ids = trained(window=6)
    prompt = ids[:, :5]
    greedy = np.asarray(generate(m, v, prompt, max_new_tokens=9))
    beam1 = np.asarray(beam_search(m, v, prompt, max_new_tokens=9,
                                   beams=1))
    np.testing.assert_array_equal(beam1, greedy)


def test_beam_full_width_is_exhaustive_at_two_steps():
    """With K = V beams and N = 2 steps, beam search IS exhaustive: step
    1 keeps every first token, step 2 scores all V² continuations. The
    best beam must therefore equal the brute-force argmax of the
    teacher-forced log-prob sum over all V² sequences — on an untrained
    model whose greedy path has no reason to be globally optimal."""
    V = 6
    m = build_model("transformer_lm", vocab_size=V, d_model=16, heads=2,
                    depth=1, max_len=12)
    v = m.init(jax.random.PRNGKey(4), jnp.zeros((1, 4), jnp.int32))
    prompt = jnp.asarray([[1, 2, 3, 4], [5, 0, 1, 2]], jnp.int32)
    b, p = prompt.shape
    got = np.asarray(beam_search(m, v, prompt, max_new_tokens=2, beams=V))

    # brute force: score every (t1, t2) continuation teacher-forced
    cands = np.stack(np.meshgrid(np.arange(V), np.arange(V),
                                 indexing="ij"), -1).reshape(-1, 2)
    best = np.zeros((b, 2), np.int32)
    for row in range(b):
        seqs = np.concatenate(
            [np.tile(np.asarray(prompt[row])[None], (V * V, 1)), cands],
            axis=1,
        )
        lg = np.asarray(m.apply(v, jnp.asarray(seqs)), np.float32)
        lp = jax.nn.log_softmax(jnp.asarray(lg), axis=-1)
        lp = np.asarray(lp)
        scores = (
            lp[np.arange(V * V), p - 1, cands[:, 0]]
            + lp[np.arange(V * V), p, cands[:, 1]]
        )
        best[row] = cands[scores.argmax()]
    np.testing.assert_array_equal(got[:, p:], best)


def test_beam_eos_and_return_all():
    m, v, ids = trained()
    prompt = ids[:, :8]
    out = np.asarray(beam_search(m, v, prompt, max_new_tokens=8,
                                 beams=3, eos_id=3))
    want = np.concatenate([np.asarray(prompt)[0],
                           [1, 2, 3, 0, 0, 0, 0, 0]])
    np.testing.assert_array_equal(out[0], want)
    seqs, scores = beam_search(m, v, prompt, max_new_tokens=4, beams=3,
                               return_all=True)
    assert seqs.shape == (1, 3, 12) and scores.shape == (1, 3)
    s = np.asarray(scores)
    assert np.all(s[:, :-1] >= s[:, 1:])  # sorted best-first
    np.testing.assert_array_equal(np.asarray(seqs)[0, 0, :8],
                                  np.asarray(prompt)[0])


def test_beam_guards_and_moe():
    m = build_model("transformer_lm", vocab_size=8, d_model=16, heads=2,
                    depth=1, max_len=16)
    v = init_lm(m)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(FriendlyError, match="beams"):
        beam_search(m, v, prompt, max_new_tokens=2, beams=0)
    with pytest.raises(FriendlyError, match="vocab"):
        beam_search(m, v, prompt, max_new_tokens=2, beams=9)
    moe = build_model("transformer_lm_moe", vocab_size=8, d_model=16,
                      heads=2, depth=1, max_len=16, n_experts=2)
    mv = init_lm(moe)
    out = beam_search(moe, mv, prompt, max_new_tokens=3, beams=2)
    assert out.shape == (1, 7)


def test_init_cache_friendly_errors():
    """cache_geometry raises the typed error — never a bare KeyError —
    when a graph lacks heads metadata or a cache-accepting block's
    variables lack the fused qkv kernel (the decode-API fuzz contract)."""
    from mmlspark_tpu.models.generate import init_cache

    m = build_model("transformer_lm", vocab_size=8, d_model=16, heads=2,
                    depth=1, max_len=8)
    v = init_lm(m)
    init_cache(m, v, 1, 8)  # healthy baseline

    del m.extra["heads"]  # build_model returns a fresh graph per call
    with pytest.raises(FriendlyError, match="heads"):
        init_cache(m, v, 1, 8)

    m2 = build_model("transformer_lm", vocab_size=8, d_model=16, heads=2,
                     depth=1, max_len=8)
    v2 = dict(v)
    block = next(name for name, _ in m2.blocks
                 if "attn" in v2.get(name, {}).get("params", {}))
    v2[block] = {"params": {}}  # strip the attn/qkv path
    with pytest.raises(FriendlyError, match="qkv"):
        init_cache(m2, v2, 1, 8)
