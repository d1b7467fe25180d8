"""Fused multi-token decode blocks (ISSUE 5 tentpole).

The contract under test (docs/SERVING.md "Decode blocks"): the engine's
fused block — one ``lax.scan`` of up to T greedy micro-steps per
dispatch, with on-device sampling, position advance, and a live/EOS/
budget mask — emits BYTE-IDENTICAL token streams to single-request
``generate()`` for every block size on the power-of-two ladder, across
ragged prompts, mid-block EOS, mid-block budget exhaustion, and mid-run
joins; compiles at most ``num_decode_blocks`` distinct XLA programs;
and performs at most ONE host sync per block.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models.generate import make_decode_block
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.serve.metrics import ServeMetrics
from mmlspark_tpu.testing.compile_guard import serve_compile_guard
from tests.serve_helpers import ref_tokens, trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


# -- parity: fused blocks vs generate() ------------------------------------


# tier-1 keeps the block=4 case (the cheapest one that exercises a real
# multi-token scan, ladder shrink, and mid-run join); the T=1 engine and
# the full ladder run as `slow` via tools/ci.sh's dedicated parity step
@pytest.mark.parametrize("block", [
    pytest.param(1, marks=pytest.mark.slow),
    4,
    pytest.param(32, marks=pytest.mark.slow),
])
def test_block_parity_ragged_prompts_and_budgets(lm, block):
    """T∈{1,4,32} engines emit generate()'s exact tokens over ragged
    prompts and heterogeneous budgets (blocks shrink near each slot's
    budget), including a mid-run submit() join."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:4], row[:1], row[:9], row[:6], row[:2]]
    budgets = [10, 7, 3, 12, 5]

    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=8,
                         decode_block=block)
    assert engine.decode_block == block
    results, rids = {}, []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        # three requests up front ...
        for p, n in zip(prompts[:3], budgets[:3]):
            rids.append(engine.submit(p, max_new_tokens=n))
        for _ in range(2):
            results.update({r.id: r for r in engine.step()})
        # ... two more join MID-RUN, while earlier requests are decoding
        for p, n in zip(prompts[3:], budgets[3:]):
            rids.append(engine.submit(p, max_new_tokens=n))
        while engine.busy:
            results.update({r.id: r for r in engine.step()})

    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, n),
            err_msg=f"block={block} request={rid}",
        )
        assert results[rid].generated == n
    assert engine.decode_compile_count <= engine.num_decode_blocks


@pytest.mark.parametrize("block", [
    4,
    pytest.param(32, marks=pytest.mark.slow),
])
def test_block_parity_mid_block_eos(lm, block):
    """A request hitting EOS mid-block goes dead ON DEVICE (pads for
    the rest of the block), retires at the boundary, and its stream
    still matches generate() with the same eos_id byte for byte."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :3])
    # pick an eos the trained model actually emits a few tokens in, so
    # the stop lands strictly inside a T>1 block
    free_run = ref_tokens(m, v, prompt, 12)
    eos = int(free_run[len(prompt) + 2])
    # generate() keeps the padded full-length array; the engine returns
    # prompt + tokens up to and including EOS — trim the ref to match
    full = ref_tokens(m, v, prompt, 12, eos_id=eos)
    stop = len(prompt) + int(np.argmax(full[len(prompt):] == eos))
    want = full[:stop + 1]

    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=block)
    rid = engine.submit(prompt, max_new_tokens=12, eos_id=eos)
    res = engine.run()[rid]
    np.testing.assert_array_equal(np.asarray(res.tokens), want)
    assert res.status == "completed"
    # the EOS token itself IS emitted (generate()'s advance semantics)
    assert int(res.tokens[-1]) == eos
    assert res.generated < 12


def test_mid_block_budget_exhaustion_direct_program(lm):
    """The raw block program (no engine ladder clamp shielding it):
    a row whose remaining budget is SMALLER than the scan length dies
    mid-block on the device budget mask — real tokens up to the budget,
    pads after, finished flag down — matching generate()'s stream."""
    m, v, ids = lm
    from mmlspark_tpu.models.generate import init_cache, _cached_apply

    prompt = np.asarray(ids[0, :5])
    budget = 3  # vs scan length 8: exhausts strictly inside the block
    t = 8
    want = ref_tokens(m, v, prompt, budget + 1)  # +1: first token via prefill

    cache = init_cache(m, v, 1, 32)
    logits, cache = _cached_apply(m, v, jnp.asarray(prompt)[None], cache, 0)
    first = int(np.asarray(
        jnp.argmax(logits[0, len(prompt) - 1].astype(jnp.float32))
    ))
    assert first == int(want[len(prompt)])

    block_fn = make_decode_block(m, pad_id=0)
    p = len(prompt)
    toks, live, _, pos = block_fn(
        v, cache,
        jnp.asarray([p], jnp.int32),          # next write position
        jnp.asarray([True]),                   # live
        jnp.asarray([first], jnp.int32),       # last token
        jnp.asarray([budget], jnp.int32),      # remaining budget < t
        jnp.asarray([-1], jnp.int32),          # no EOS
        t,
    )
    toks = np.asarray(toks)[0]
    assert toks.shape == (t,)
    np.testing.assert_array_equal(toks[:budget], want[p + 1:p + 1 + budget])
    assert not bool(np.asarray(live)[0])       # finished inside the block
    assert (toks[budget:] == 0).all()          # pads after budget death
    assert int(np.asarray(pos)[0]) == p + budget  # frozen once dead


@pytest.mark.slow  # trains its own RoPE model; ci.sh's parity step runs it
def test_true_32_scan_with_rope(lm):
    """A genuine T=32 scan (not a ladder shrink): a RoPE model's
    cache_len can exceed max_len, leaving room for a 32-token block."""
    m, v, ids = trained_lm(pos_embedding="rope")
    prompt = np.asarray(ids[0, :3])
    want = ref_tokens(m, v, prompt, 40)

    engine = ServeEngine(m, v, slots=2, cache_len=64, decode_block=32)
    rid = engine.submit(prompt, max_new_tokens=40)
    res = engine.run()[rid]
    np.testing.assert_array_equal(np.asarray(res.tokens), want)
    # the first full block really ran at T=32 (min_rem=39 after the
    # prefill token -> ladder picks 32)
    assert "32" in engine.metrics.decode_blocks


# -- one host sync per block -----------------------------------------------


def test_at_most_one_host_sync_per_block(lm, monkeypatch):
    """Counts device->host transfers (``jax.device_get`` calls plus any
    ``np.asarray`` over a ``jax.Array``) during the decode phase: one
    request decoding 16 tokens through T=8 blocks must sync at most
    twice — the (S, T) token block and the finished vector ride ONE
    fetch per block."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :4])
    engine = ServeEngine(m, v, slots=1, cache_len=32, decode_block=8)
    rid = engine.submit(prompt, max_new_tokens=17)  # 1 prefill + 16 decode

    syncs = {"n": 0}
    real_device_get = jax.device_get
    real_asarray = np.asarray

    def counting_device_get(x, *a, **kw):
        syncs["n"] += 1
        return real_device_get(x, *a, **kw)

    def counting_asarray(x, *a, **kw):
        if isinstance(x, jax.Array):
            syncs["n"] += 1
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(jax, "device_get", counting_device_get)
    monkeypatch.setattr(np, "asarray", counting_asarray)
    res = engine.run()[rid]
    monkeypatch.undo()

    np.testing.assert_array_equal(
        np.asarray(res.tokens), ref_tokens(m, v, prompt, 17)
    )
    # 16 decode tokens / blocks of 8 = 2 blocks -> at most 2 synced
    # fetches (1 per block), where the T=1 engine would have paid 16
    assert syncs["n"] <= 2, f"host syncs: {syncs['n']} (> 1 per block)"


# -- ladder / config -------------------------------------------------------


def test_decode_block_ladder_and_validation(lm):
    m, v, _ = lm
    with pytest.raises(FriendlyError, match="decode_block"):
        ServeEngine(m, v, slots=1, cache_len=32, decode_block=0)
    # non-power-of-two floors onto the ladder
    e = ServeEngine(m, v, slots=1, cache_len=32, decode_block=5)
    assert e.decode_block == 4 and e.num_decode_blocks == 3
    # block sizes clamp to min remaining budget (the parity rule)
    assert e._block_size(1) == 1
    assert e._block_size(3) == 2
    assert e._block_size(4) == 4
    assert e._block_size(100) == 4  # never past decode_block
    e1 = ServeEngine(m, v, slots=1, cache_len=32, decode_block=1)
    assert e1.num_decode_blocks == 1  # T=1 engine: the old contract


# -- metrics: per-token figures divide by tokens emitted -------------------


def test_metrics_tokens_emitted_equal_path_for_t1():
    a = ServeMetrics("m", slots=2)
    b = ServeMetrics("m", slots=2)
    # T=1 step: default tokens_emitted == n_active, explicit must match
    a.record_decode(2, 0.004)
    b.record_decode(2, 0.004, tokens_emitted=2, block=1)
    da, db = a.to_dict(), b.to_dict()
    assert da["per_token_ms"] == db["per_token_ms"] == 2.0
    assert da["per_token_ms_p50"] == db["per_token_ms_p50"]

    # T=8 block emitting 13 real tokens across 2 slots: per-token
    # divides by 13, not by n_active or by slots*T
    c = ServeMetrics("m", slots=2, decode_block=8)
    c.record_decode(2, 0.013, tokens_emitted=13, block=8)
    dc = c.to_dict()
    assert dc["per_token_ms"] == 1.0
    assert dc["decode_block"] == 8
    assert dc["decode_blocks"] == {"8": 1}


def test_metrics_tokens_per_tick():
    ms = ServeMetrics("m", slots=4, decode_block=8)
    ms.sample_tick(0, 4, 0.01, tokens_emitted=12)
    ms.sample_tick(0, 2, 0.01, tokens_emitted=4)
    d = ms.to_dict()
    assert d["tokens_per_tick"] == 8.0
    assert d["ticks"] == 2
