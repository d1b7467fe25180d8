"""Mesh-sharded serving (ISSUE 6 tentpole).

The contract under test (docs/SERVING.md "Sharded serving"): a
``ServeEngine`` built with ``mesh=`` runs the SAME bucketed-prefill +
fused-decode-block programs partitioned by GSPMD over a (data, model)
device mesh — slot-batched state over the data axis, params by the
Megatron ``TRANSFORMER_TP_RULES`` over the model axis — and everything
the single-device engine guarantees carries over: token streams
BYTE-IDENTICAL to ``generate()`` across ragged prompts / mid-run joins /
mid-block death, buffer donation, the compile-count pins
(``decode_compile_count <= num_decode_blocks``, prefill <= buckets),
one host sync per block, and typed errors for invalid topologies.
Runs on the 8 virtual CPU devices ``tests/conftest.py`` forces.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.parallel import (
    TRANSFORMER_TP_RULES,
    make_mesh,
    parse_mesh_axes,
    unmatched_param_paths,
)
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.testing.compile_guard import (
    compile_guard,
    serve_compile_guard,
)
from tests.serve_helpers import ref_tokens, trained_lm


@pytest.fixture(scope="module")
def lm():
    return trained_lm()


# -- mesh spec parsing -----------------------------------------------------


def test_parse_mesh_axes():
    assert parse_mesh_axes("data=4,model=2") == {"data": 4, "model": 2}
    assert parse_mesh_axes(" data=-1 , model=2 ") == {"data": -1,
                                                     "model": 2}
    with pytest.raises(FriendlyError, match="mesh spec"):
        parse_mesh_axes("data:4")
    with pytest.raises(FriendlyError, match="mesh spec"):
        parse_mesh_axes("")


# -- topology validation ---------------------------------------------------


def test_slots_not_divisible_by_data_axis_raises(lm):
    m, v, _ = lm
    with pytest.raises(FriendlyError, match="multiple of the mesh"):
        ServeEngine(m, v, slots=3, cache_len=32,
                    mesh={"data": 2, "model": 2})


# -- parity: sharded engine == single-device generate() --------------------


@pytest.mark.parametrize("mesh_axes", [
    {"data": 2, "model": 2},
    pytest.param({"data": 4}, marks=pytest.mark.slow),
    pytest.param({"data": 1, "model": 2}, marks=pytest.mark.slow),
])
def test_sharded_parity_ragged_prompts_and_joins(lm, mesh_axes):
    """The sharded engine emits generate()'s exact tokens over ragged
    prompts and heterogeneous budgets, including mid-run submit()
    joins, with the compile-count pins holding under the mesh."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    prompts = [row[:4], row[:1], row[:9], row[:6], row[:2]]
    budgets = [10, 7, 3, 12, 5]

    engine = ServeEngine(m, v, slots=4, cache_len=32, max_queue=8,
                         decode_block=4, mesh=mesh_axes)
    assert engine.mesh is not None
    results, rids = {}, []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        for p, n in zip(prompts[:3], budgets[:3]):
            rids.append(engine.submit(p, max_new_tokens=n))
        for _ in range(2):
            results.update({r.id: r for r in engine.step()})
        # two more join MID-RUN while earlier requests are decoding
        for p, n in zip(prompts[3:], budgets[3:]):
            rids.append(engine.submit(p, max_new_tokens=n))
        while engine.busy:
            results.update({r.id: r for r in engine.step()})

    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens), ref_tokens(m, v, p, n),
            err_msg=f"mesh={mesh_axes} request={rid}",
        )
    assert engine.decode_compile_count <= engine.num_decode_blocks
    assert engine.prefill_compile_count <= engine.num_prefill_buckets


@pytest.mark.slow  # ci.sh's sharded gate runs the full file unfiltered
def test_sharded_mid_block_eos(lm):
    """A request hitting EOS mid-block under a 2x2 mesh dies on device
    and matches generate() with the same eos_id byte for byte."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :3])
    free_run = ref_tokens(m, v, prompt, 12)
    eos = int(free_run[len(prompt) + 2])
    full = ref_tokens(m, v, prompt, 12, eos_id=eos)
    stop = len(prompt) + int(np.argmax(full[len(prompt):] == eos))
    want = full[:stop + 1]

    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4,
                         mesh={"data": 2, "model": 2})
    rid = engine.submit(prompt, max_new_tokens=12, eos_id=eos)
    res = engine.run()[rid]
    np.testing.assert_array_equal(np.asarray(res.tokens), want)
    assert res.status == "completed"
    assert int(res.tokens[-1]) == eos


# -- compile-count: NamedSharding args register zero new programs ----------


@pytest.mark.slow  # ci.sh's sharded gate runs the full file unfiltered
def test_sharded_retick_compiles_zero_new_programs(lm):
    """The satellite regression: once a sharded engine has served one
    wave of traffic, serving MORE traffic with the same shapes compiles
    ZERO new XLA programs — committed NamedSharding args re-enter the
    cached programs instead of registering as new signatures (the raw
    jax signature cache would grow here; ProgramCountingJit must not)."""
    m, v, ids = lm
    row = np.asarray(ids[0])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4,
                         mesh={"data": 2, "model": 2})
    rid = engine.submit(row[:4], max_new_tokens=9)
    engine.run()

    with compile_guard(
        lambda: engine.decode_compile_count, max_programs=0,
        label="sharded re-tick decode",
    ), compile_guard(
        lambda: engine.prefill_compile_count, max_programs=0,
        label="sharded re-tick prefill",
    ):
        rid = engine.submit(row[:4], max_new_tokens=9)
        res = engine.run()[rid]
    np.testing.assert_array_equal(
        np.asarray(res.tokens), ref_tokens(m, v, row[:4], 9)
    )


@pytest.mark.slow  # ci.sh's sharded gate runs the full file unfiltered
def test_sharded_one_host_sync_per_block(lm, monkeypatch):
    """The one-device_get-per-block contract survives sharding: 8
    decode tokens through T=4 blocks = at most 2 synced fetches
    (device_put of per-tick inputs must not count as a sync)."""
    m, v, ids = lm
    prompt = np.asarray(ids[0, :4])
    engine = ServeEngine(m, v, slots=2, cache_len=32, decode_block=4,
                         mesh={"data": 2, "model": 2})
    rid = engine.submit(prompt, max_new_tokens=9)  # 1 prefill + 8 decode

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
        np.asarray(res.tokens), ref_tokens(m, v, prompt, 9)
    )
    assert syncs["n"] <= 2, f"host syncs: {syncs['n']} (> 1 per block)"


# -- expiry under the mesh: device row dies, slot re-leases ----------------


def test_sharded_expire_active_slot_device_state(lm):
    """The expire-active regression on a 2x2 mesh: an ACTIVE request
    expiring mid-decode leaves its sharded live-mask row dead and its
    position zeroed, the survivor keeps byte parity, and the freed slot
    re-leases cleanly in the same run."""
    m, v, ids = lm
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=4,
                         decode_block=1, mesh={"data": 2, "model": 2})
    prompt_b = np.asarray(ids[0, :5])
    rid_a = engine.submit(np.asarray(ids[0, :4]), max_new_tokens=12,
                          deadline_ticks=2)
    rid_b = engine.submit(prompt_b, max_new_tokens=10)
    results = {r.id: r for r in engine.step()}  # tick 0: both admitted
    slot_a = next(s for s, st in engine._sched.active.items()
                  if st.req.id == rid_a)
    while rid_a not in results:
        results.update({r.id: r for r in engine.step()})
    assert results[rid_a].status == "expired"
    # the sharded (data-axis-split) pool state agrees: row dead, pos 0
    assert not bool(np.asarray(jax.device_get(engine.pool.live))[slot_a])
    assert int(np.asarray(jax.device_get(
        engine.pool.positions))[slot_a]) == 0
    # re-lease the freed slot under the mesh while B keeps decoding
    rid_c = engine.submit(np.asarray(ids[0, :6]), max_new_tokens=4)
    results.update(engine.run())
    assert results[rid_b].status == "completed"
    np.testing.assert_array_equal(
        np.asarray(results[rid_b].tokens), ref_tokens(m, v, prompt_b, 10)
    )
    assert results[rid_c].status == "completed"
    np.testing.assert_array_equal(
        np.asarray(results[rid_c].tokens),
        ref_tokens(m, v, np.asarray(ids[0, :6]), 4),
    )


# -- telemetry: mesh topology in the metrics surfaces ----------------------


def test_sharded_metrics_mesh_keys(lm):
    m, v, _ = lm
    engine = ServeEngine(m, v, slots=4, cache_len=32,
                         mesh={"data": 2, "model": 2})
    d = engine.metrics.to_dict()
    assert d["mesh_shape"] == {"data": 2, "model": 2}
    assert d["mesh_devices"] == 4
    # K+V pairs over depth blocks, slot rows split 2-way over the data
    # axis: per-device bytes must be a strict fraction of the total pool
    total = sum(
        a.size * a.dtype.itemsize
        for pair in engine.pool.buffers.values() for a in pair
    )
    assert 0 < d["cache_pool_bytes_per_device"] < total

    single = ServeEngine(m, v, slots=4, cache_len=32)
    ds = single.metrics.to_dict()
    assert ds["mesh_shape"] == {} and ds["mesh_devices"] == 1
    assert ds["cache_pool_bytes_per_device"] >= total


# -- rule coverage audit ---------------------------------------------------


def test_tp_rule_coverage_transformer_lm(lm):
    """Every transformer_lm param path matches SOME rule (embedding,
    unembed, norms included) — the whole-model audit is one call."""
    m, v, _ = lm
    assert unmatched_param_paths(v, TRANSFORMER_TP_RULES) == []
    # an unknown param is reported by its full path
    extra = {"novel": {"params": {"adapter": {"kernel": jnp.zeros((4, 4))}}}}
    missing = unmatched_param_paths(extra, TRANSFORMER_TP_RULES)
    assert missing == ["novel/params/adapter/kernel"]


def test_embedding_and_head_rules_shard(lm):
    """The extended rules place the vocab-parallel pair: embedding rows
    and lm_head columns over the model axis, norms replicated."""
    from mmlspark_tpu.parallel import build_param_shardings

    m, v, _ = lm
    mesh = make_mesh({"data": 2, "model": 2},
                     devices=jax.devices()[:4])
    sh = build_param_shardings(v, mesh, TRANSFORMER_TP_RULES)
    assert tuple(sh["embed"]["params"]["token"]["embedding"].spec) == \
        ("model", None)
    assert tuple(sh["z"]["params"]["head"]["kernel"].spec) == \
        (None, "model")
    assert tuple(sh["z"]["params"]["ln_f"]["scale"].spec) == ()
    assert tuple(sh["embed"]["params"]["pos"].spec) == ()
