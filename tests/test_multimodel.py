"""Multi-model serving (mmlspark_tpu.serve.multimodel).

The contract under test (docs/SERVING.md "Multi-model serving"): one
engine hosts several named deployments — stateful LM-decode engines
next to stateless power-of-two-bucketed batch deployments (ONNX-imported
graphs included) — behind one ``submit(model=...)/step()/run()`` facade,
and every request's output is BIT-IDENTICAL to a dedicated single-model
run: the LM emits the same tokens as a lone ``ServeEngine``, a batch
deployment emits the same rows as a direct ``graph.apply`` on the same
examples. Compile pins hold per deployment (the LM's decode/prefill
pins unchanged, batch dispatch bounded by ``num_batch_buckets``),
round-robin scheduling under a device budget never starves a model,
per-model SLOs shed independently, and the ``serve.batch`` fault site
carries the same retry/quarantine/degrade envelope as the LM sites.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import parse_fault_spec
from mmlspark_tpu.core.perf import SloTargets
from mmlspark_tpu.models import build_model
from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.serve.multimodel import (
    BatchDeployment,
    MultiModelEngine,
    engine_from_spec,
    parse_models_spec,
)
from mmlspark_tpu.serve.supervisor import ReplicaSet
from mmlspark_tpu.testing.compile_guard import (
    compile_guard,
    serve_compile_guard,
)
from tests.serve_helpers import init_lm, tiny_lm


def _mlp(num_outputs=3, hidden=(16,)):
    m = build_model("mlp", num_outputs=num_outputs, hidden=hidden)
    v = m.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.float32))
    return m, v


def _examples(n, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(dim,)).astype(np.float32) for _ in range(n)]


def _prompts(n, vocab=8, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, size=int(rng.integers(4, 12)))
        for _ in range(n)
    ]


# -- batch deployment ------------------------------------------------------


def test_batch_deployment_rejects_causal_graph():
    m = tiny_lm()
    with pytest.raises(FriendlyError, match="causal"):
        BatchDeployment(m, init_lm(m))


def test_batch_bucket_ladder():
    m, v = _mlp()
    dep = BatchDeployment(m, v, max_batch=8)
    assert [dep.batch_bucket(k) for k in (1, 2, 3, 4, 5, 8)] == \
        [1, 2, 4, 4, 8, 8]
    assert dep.num_batch_buckets == 4  # {1, 2, 4, 8}
    # non-power-of-two max_batch floors down the ladder
    assert BatchDeployment(m, v, max_batch=6).max_batch == 4


def test_batch_deployment_bit_parity_and_compile_pin():
    """A full bucket-sized submission group comes back BIT-EQUAL to a
    direct ``graph.apply`` on the stacked batch (padding is identity at
    bucket size), and however sizes vary the dispatch never compiles
    more than one program per ladder bucket."""
    m, v = _mlp()
    dep = BatchDeployment(m, v, max_batch=4)
    xs = _examples(4)
    direct = np.asarray(m.apply(v, jnp.asarray(np.stack(xs))))

    with compile_guard(lambda: dep.batch_compile_count,
                       max_programs=dep.num_batch_buckets,
                       label="batch dispatch"):
        ids = [dep.submit(x) for x in xs]
        results = {r.id: r for r in dep.step()}
        assert sorted(results) == ids
        for i, rid in enumerate(ids):
            r = results[rid]
            assert r.status == "completed"
            np.testing.assert_array_equal(np.asarray(r.output), direct[i])

        # ragged arrivals land on existing buckets, not new programs
        for k in (1, 3, 2, 4):
            for x in _examples(k, seed=k):
                dep.submit(x)
            got = dep.step()
            assert len(got) == k
            assert all(r.status == "completed" for r in got)
    assert dep.batch_compile_count <= dep.num_batch_buckets


def test_batch_padding_rows_do_not_leak():
    """A partial batch (k < bucket) returns exactly k results and each
    equals the unpadded direct apply row — the zero padding rows are
    sliced off, never surfaced."""
    m, v = _mlp()
    dep = BatchDeployment(m, v, max_batch=8)
    xs = _examples(3, seed=7)
    direct = np.asarray(m.apply(v, jnp.asarray(np.stack(xs))))
    ids = [dep.submit(x) for x in xs]
    results = {r.id: r for r in dep.step()}
    assert sorted(results) == ids
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            np.asarray(results[rid].output), direct[i]
        )


def test_batch_admission_control():
    m, v = _mlp()
    dep = BatchDeployment(m, v, max_batch=4, max_queue=2)
    dep.submit(np.zeros(8, np.float32))
    # shape/dtype lock: the first submit fixes the example geometry
    with pytest.raises(FriendlyError, match="does not match"):
        dep.submit(np.zeros(9, np.float32))
    with pytest.raises(FriendlyError, match="does not match"):
        dep.submit(np.zeros(8, np.float64))
    dep.submit(np.zeros(8, np.float32))
    with pytest.raises(FriendlyError, match="queue is full"):
        dep.submit(np.zeros(8, np.float32))
    assert dep.metrics.rejected == 1


# -- the multi-model engine ------------------------------------------------


def test_multimodel_concurrent_bit_identical(tmp_path):
    """The acceptance bar: one engine serves an LM plus two stateless
    models (one ONNX-imported) concurrently, and EVERY output is
    bit-identical to a dedicated single-model run — the LM under its
    unchanged compile pins, each batch deployment within its bucket
    pin."""
    from mmlspark_tpu.models.onnx_export import save_onnx

    lm = tiny_lm()
    lmv = init_lm(lm)
    clf, clfv = _mlp()
    onnx_path = str(tmp_path / "clf.onnx")
    save_onnx(clf, clfv, (1, 8), onnx_path)
    og = build_model("onnx", path=onnx_path)
    ogv = og.init()

    prompts = _prompts(6)
    xs = _examples(4, seed=3)
    oxs = _examples(4, seed=4)

    # dedicated single-model references
    ref_eng = ServeEngine(lm, lmv, slots=2, cache_len=32, max_queue=8)
    ref_ids = [ref_eng.submit(p, 5) for p in prompts[:2]]
    ref_res = ref_eng.run()
    ref_tokens = {i: ref_res[i].tokens for i in ref_ids}
    clf_direct = np.asarray(clf.apply(clfv, jnp.asarray(np.stack(xs))))
    ox_direct = np.asarray(og.apply(ogv, jnp.asarray(np.stack(oxs))))

    eng = MultiModelEngine(device_budget=2)
    lm_dep = eng.add_lm("lm", lm, lmv, slots=2, cache_len=32, max_queue=8)
    clf_dep = eng.add_batch("clf", clf, clfv, max_batch=4)
    ox_dep = eng.add_onnx("ox", onnx_path, max_batch=4)
    assert eng.models == ["lm", "clf", "ox"]

    with serve_compile_guard(lm_dep):
        gids = {}
        for i, p in enumerate(prompts[:2]):
            gids[("lm", i)] = eng.submit(p, model="lm", max_new_tokens=5)
        for i, x in enumerate(xs):
            gids[("clf", i)] = eng.submit(x, model="clf")
        for i, x in enumerate(oxs):
            gids[("ox", i)] = eng.submit(x, model="ox")
        res = eng.run()

    assert len(res) == len(gids)
    for i, rid in enumerate(ref_ids):
        got = res[gids[("lm", i)]]
        assert got.status == "completed"
        np.testing.assert_array_equal(got.tokens, ref_tokens[rid])
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(res[gids[("clf", i)]].output), clf_direct[i]
        )
        np.testing.assert_array_equal(
            np.asarray(res[gids[("ox", i)]].output), ox_direct[i]
        )
    assert clf_dep.batch_compile_count <= clf_dep.num_batch_buckets
    assert ox_dep.batch_compile_count <= ox_dep.num_batch_buckets

    # routing bookkeeping + per-model namespaces in the shared registry
    assert eng.model_of(gids[("lm", 0)]) == "lm"
    assert eng.model_of(gids[("ox", 3)]) == "ox"
    md = eng.metrics_dict()
    assert md["multimodel"] and md["deployments"] == 3
    assert md["submitted"] == 10 and md["completed"] == 10
    assert md["per_model"]["lm"]["kind"] == "lm"
    assert md["per_model"]["clf"]["kind"] == "batch"
    reg = md["registry"]
    for name in ("lm", "clf", "ox"):
        assert reg[f"model{name}.serve.completed"] > 0
    prom = eng.to_prometheus()
    assert "modellm_serve_completed_total" in prom
    assert "modelox_serve_completed_total" in prom
    # one collision-free exposition: no duplicate family lines
    samples = [
        ln.split()[0] for ln in prom.splitlines()
        if ln and not ln.startswith("#")
    ]
    assert len(samples) == len(set(samples))


def test_onnx_roundtrip_deployment_bit_equal(tmp_path):
    """Satellite: export -> import -> serve. The ONNX-imported graph's
    deployment output is bit-equal to calling the imported graph's
    ``apply`` directly on the same (bucket-sized) batch, and close to
    the original flax graph it round-tripped from."""
    from mmlspark_tpu.models.onnx_export import save_onnx

    m, v = _mlp(num_outputs=4, hidden=(16, 16))
    path = str(tmp_path / "roundtrip.onnx")
    save_onnx(m, v, (1, 8), path)
    og = build_model("onnx", path=path)
    ogv = og.init()

    xs = _examples(4, seed=11)
    stacked = jnp.asarray(np.stack(xs))
    direct = np.asarray(og.apply(ogv, stacked))

    dep = BatchDeployment(og, ogv, max_batch=4)
    ids = [dep.submit(x) for x in xs]
    results = {r.id: r for r in dep.step()}
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            np.asarray(results[rid].output), direct[i]
        )
    # the round trip itself only drifts by compute-dtype differences
    flax_out = np.asarray(m.apply(v, stacked))
    np.testing.assert_allclose(direct, flax_out, atol=5e-2)


def test_submit_routing_errors():
    lm = tiny_lm()
    clf, clfv = _mlp()
    eng = MultiModelEngine()
    eng.add_lm("lm", lm, init_lm(lm), slots=2, cache_len=32)
    eng.add_batch("classifier", clf, clfv, max_batch=4)

    # several deployments: model= is required
    with pytest.raises(FriendlyError, match="pass model="):
        eng.submit(np.zeros(8, np.float32))
    # unknown names suggest the nearest deployment
    with pytest.raises(FriendlyError, match="did you mean 'classifier'"):
        eng.submit(np.zeros(8, np.float32), model="clasifier")
    # LM-only kwargs are rejected on batch deployments and vice versa
    with pytest.raises(FriendlyError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int32), model="lm")
    with pytest.raises(FriendlyError, match="stateless batch"):
        eng.submit(np.zeros(8, np.float32), model="classifier",
                   max_new_tokens=4)
    with pytest.raises(FriendlyError, match="unknown request id"):
        eng.model_of(123)


def test_duplicate_and_invalid_deployment_names():
    clf, clfv = _mlp()
    eng = MultiModelEngine()
    eng.add_batch("clf", clf, clfv)
    with pytest.raises(FriendlyError, match="already exists"):
        eng.add_batch("clf", clf, clfv)
    with pytest.raises(FriendlyError, match="invalid"):
        eng.add_batch("a.b", clf, clfv)
    with pytest.raises(FriendlyError, match="managed by MultiModelEngine"):
        eng.add_batch("other", clf, clfv, registry=object())


def test_fairness_under_saturating_lm_stream():
    """Satellite: with device_budget=1 and a saturating LM stream, the
    round-robin cursor still admits the classifier within ceil(D/B)=2
    ticks — no deployment starves behind a hot neighbour."""
    lm = tiny_lm()
    clf, clfv = _mlp()
    eng = MultiModelEngine(device_budget=1)
    eng.add_lm("lm", lm, init_lm(lm), slots=2, cache_len=32,
               max_queue=32, decode_block=4)
    eng.add_batch("clf", clf, clfv, max_batch=4)

    # saturate the LM first: plenty of queued decode work every tick
    for p in _prompts(8):
        eng.submit(p, model="lm", max_new_tokens=8)
    for _ in range(3):
        eng.step()
    # now a classifier burst arrives mid-stream
    clf_gids = {eng.submit(x, model="clf") for x in _examples(4)}
    ticks_to_serve = None
    for t in range(1, 5):
        got = {r.id for r in eng.step()}
        if clf_gids & got:
            ticks_to_serve = t
            break
    assert ticks_to_serve is not None and ticks_to_serve <= 2, (
        f"classifier starved for {ticks_to_serve} ticks under "
        "a saturating LM stream"
    )
    eng.run()  # drain


def test_per_model_shed_independence():
    """Satellite: each deployment carries its OWN SloMonitor — one
    model burning its SLO sheds only its own admissions; the neighbour
    keeps completing with zero shed ticks."""
    clf_a, v_a = _mlp()
    clf_b, v_b = _mlp(num_outputs=2)
    eng = MultiModelEngine()
    # an unmeetable TTFT target: any real dispatch latency burns it
    dep_a = eng.add_batch(
        "burns", clf_a, v_a, max_batch=2,
        slo=SloTargets(ttft_p99_ms=1e-9, min_samples=1),
    )
    dep_b = eng.add_batch("fine", clf_b, v_b, max_batch=2)

    # enough traffic for the window to fill, then keep submitting
    for round_ in range(4):
        for x in _examples(2, seed=round_):
            eng.submit(x, model="burns")
            eng.submit(x, model="fine")
        for _ in range(4):
            eng.step()

    assert dep_a.metrics.slo_shed_ticks_total > 0
    assert dep_b.metrics.slo_shed_ticks_total == 0
    assert dep_b.metrics.completed == 8
    reg = eng.registry.to_dict()
    assert reg["modelburns.serve.slo_shed_ticks"] > 0
    assert reg["modelfine.serve.slo_shed_ticks"] == 0


# -- serve.batch fault envelope --------------------------------------------


def test_serve_batch_transient_faults_absorbed():
    """Transient dispatch faults on the serve.batch site retry and every
    example still completes — same envelope as the LM decode sites."""
    m, v = _mlp()
    inj = parse_fault_spec("seed=3,serve.batch:transient=0.4")
    dep = BatchDeployment(m, v, max_batch=4, faults=inj, retry_limit=8)
    ids = [dep.submit(x) for x in _examples(8)]
    results = {}
    for _ in range(50):
        for r in dep.step():
            results[r.id] = r
        if not dep.busy:
            break
    assert sorted(results) == ids
    assert all(r.status == "completed" for r in results.values())
    assert dep.metrics.retries_total >= 1
    assert dep.metrics.faults_injected_total >= 1


def test_serve_batch_retry_exhaustion_quarantines_batch():
    """Retry exhaustion fails the WHOLE in-flight batch as terminal
    'failed' results — the deployment keeps serving instead of dying."""
    m, v = _mlp()
    inj = parse_fault_spec("seed=1,serve.batch:transient=1.0")
    dep = BatchDeployment(m, v, max_batch=4, faults=inj, retry_limit=1)
    ids = [dep.submit(x) for x in _examples(3)]
    results = {r.id: r for r in dep.step()}
    assert sorted(results) == ids
    assert all(r.status == "failed" for r in results.values())
    assert all(r.output is None for r in results.values())
    assert all(r.generated == 0 for r in results.values())
    assert dep.metrics.quarantined_total == 3
    assert dep.metrics.failed == 3
    # still serving: the next batch quarantines too instead of raising
    dep.submit(_examples(1)[0])
    assert all(r.status == "failed" for r in dep.step())


class _OnceOOM:
    """Minimal injector stand-in: one RESOURCE_EXHAUSTED on the first
    fire, silent after — deterministic OOM drill without rate math."""

    listener = None

    def __init__(self):
        self.fired = False

    def fire(self, site, *, tick, request=None, replica=None):
        if not self.fired:
            self.fired = True
            raise RuntimeError("RESOURCE_EXHAUSTED: injected oom drill")


def test_serve_batch_oom_degrades_and_recovers():
    """RESOURCE_EXHAUSTED halves the batch admission cap down the
    EXISTING bucket ladder (no new program), requeues the batch intact,
    and clean dispatches re-escalate the cap back to max_batch."""
    m, v = _mlp()
    dep = BatchDeployment(m, v, max_batch=4, faults=_OnceOOM(),
                          degrade_recover_ticks=2)
    ids = [dep.submit(x) for x in _examples(4)]
    assert dep.step() == []  # the OOM tick: requeued, nothing retired
    assert dep.degraded and dep.queue_depth == 4
    before = dep.batch_compile_count
    results = {}
    for _ in range(10):
        for r in dep.step():
            results[r.id] = r
        if not dep.busy and not dep.degraded:
            break
    assert sorted(results) == ids
    assert all(r.status == "completed" for r in results.values())
    assert not dep.degraded  # cap re-escalated after clean dispatches
    # degradation rode existing ladder buckets: no new programs beyond
    # the ladder's own ceiling
    assert dep.batch_compile_count <= dep.num_batch_buckets
    assert dep.batch_compile_count >= before


def test_engine_kill_is_terminal():
    from mmlspark_tpu.core.faults import EngineKilled

    class _Kill:
        listener = None

        def fire(self, site, *, tick, request=None, replica=None):
            raise EngineKilled("injected kill")

    m, v = _mlp()
    dep = BatchDeployment(m, v, max_batch=2, faults=_Kill())
    dep.submit(_examples(1)[0])
    with pytest.raises(EngineKilled):
        dep.step()
    with pytest.raises(FriendlyError, match="killed"):
        dep.step()


# -- spec grammar ----------------------------------------------------------


def test_parse_models_spec_grammar():
    entries = parse_models_spec(
        "lm=transformer_lm:slots=4:cache_len=64:"
        "slo=ttft_p99_ms=50+error_rate=0.5;"
        "clf=mlp:max_batch=8:hidden=16x16:input_shape=8;"
        "ox=onnx:path=/tmp/m.onnx"
    )
    by_name = {e.name: e for e in entries}
    assert list(by_name) == ["lm", "clf", "ox"]
    assert by_name["lm"].deploy_kwargs == {
        "slots": 4, "cache_len": 64,
        "slo": "ttft_p99_ms=50,error_rate=0.5",  # '+' spells ','
    }
    assert by_name["clf"].deploy_kwargs == {"max_batch": 8}
    assert by_name["clf"].build_config == {
        "hidden": (16, 16), "input_shape": 8,
    }
    assert by_name["ox"].build_config == {"path": "/tmp/m.onnx"}

    with pytest.raises(FriendlyError, match="expected 'name=arch'"):
        parse_models_spec("justaname")
    with pytest.raises(FriendlyError, match="duplicate deployment name"):
        parse_models_spec("a=mlp;a=linear")
    with pytest.raises(FriendlyError, match="key=value"):
        parse_models_spec("a=mlp:oops")
    with pytest.raises(FriendlyError, match="spec is empty"):
        parse_models_spec(" ; ")


def test_engine_from_spec_kind_detection_and_wrong_keys():
    eng = engine_from_spec(
        "lm=transformer_lm:slots=2:cache_len=32:vocab_size=8:"
        "d_model=32:heads=2:depth=1:max_len=32;"
        "clf=mlp:max_batch=4:num_outputs=3:hidden=16x16:input_shape=8",
        seed=0,
    )
    assert isinstance(eng.deployment("lm"), ServeEngine)
    assert isinstance(eng.deployment("clf"), BatchDeployment)

    # deployment keys of the wrong kind name the offending entry
    with pytest.raises(FriendlyError, match="'clf' .* do not apply"):
        engine_from_spec(
            "clf=mlp:slots=4:hidden=16x16:input_shape=8", seed=0
        )
    with pytest.raises(FriendlyError, match="'lm' .* do not apply"):
        engine_from_spec(
            "lm=transformer_lm:max_batch=4:vocab_size=8:d_model=32:"
            "heads=2:depth=1:max_len=32", seed=0
        )
    # archs without a recorded input_shape need the spec key
    with pytest.raises(FriendlyError, match="input_shape"):
        engine_from_spec("clf=mlp:hidden=16x16", seed=0)


def test_registry_unknown_model_suggests_and_names_onnx():
    """Satellite: a typo'd build_model name suggests the nearest
    registered architecture and points at the ONNX escape hatch for
    foreign graphs."""
    with pytest.raises(FriendlyError, match="did you mean 'mlp'"):
        build_model("mpl")
    with pytest.raises(FriendlyError, match="onnx"):
        build_model("definitely_not_a_model")


# -- demo + CLI surface ----------------------------------------------------


def test_run_demo_multimodel(tmp_path):
    from mmlspark_tpu.serve.demo import run_demo

    tel = str(tmp_path / "tel")
    out = run_demo(
        models=(
            "lm=transformer_lm:slots=2:cache_len=32:vocab_size=8:"
            "d_model=32:heads=2:depth=1:max_len=32;"
            "clf=mlp:max_batch=4:num_outputs=3:hidden=16x16:"
            "input_shape=8"
        ),
        n_requests=3, max_new_tokens=4, arrivals_per_tick=2, seed=0,
        device_budget=2, telemetry_dir=tel,
    )
    assert out["multimodel"] and out["deployments"] == 2
    assert out["submitted"] == 6 and out["completed"] == 6
    assert set(out["per_model"]) == {"lm", "clf"}
    assert out["per_model"]["lm"]["decode_compile_count"] >= 1
    assert out["per_model"]["clf"]["batch_compile_count"] >= 1
    for fname in ("events.jsonl", "metrics.json", "trace.json",
                  "metrics.prom"):
        assert os.path.exists(os.path.join(tel, fname))
    with open(os.path.join(tel, "events.jsonl")) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    routed = [e for e in events if e.get("name") == "routed"]
    assert {e["attrs"]["model"] for e in routed} == {"lm", "clf"}
    with open(os.path.join(tel, "metrics.prom")) as f:
        prom = f.read()
    # the telemetry dir carries the MERGED TelemetryHub exposition:
    # per-model prefixes become {model=...} labels on shared families
    assert 'serve_ttft_ms_count{model="lm"}' in prom
    assert 'serve_ttft_ms_count{model="clf"}' in prom


# -- replica routing with a model dimension --------------------------------


def test_replica_set_model_routing():
    """The supervisor's routing key grows a model dimension: replicas
    partition over the models round-robin, submit requires model= and
    routes within that model's replicas only."""
    lm_a = tiny_lm(depth=1)
    lm_b = tiny_lm(depth=2)
    va, vb = init_lm(lm_a), init_lm(lm_b, seed=1)
    rs = ReplicaSet(
        lm_a, va, replicas=2, slots=2, cache_len=32,
        models={"small": (lm_a, va), "big": (lm_b, vb)},
    )
    assert rs.models == ["small", "big"]
    assert rs.replica_model(0) == "small"
    assert rs.replica_model(1) == "big"

    with pytest.raises(FriendlyError, match="model="):
        rs.submit(np.zeros(4, np.int32), 4)
    with pytest.raises(FriendlyError, match="unknown model"):
        rs.submit(np.zeros(4, np.int32), 4, model="medium")

    # bit-parity per model against dedicated engines
    prompts = _prompts(4)
    ref_small = ServeEngine(lm_a, va, slots=2, cache_len=32)
    ref_big = ServeEngine(lm_b, vb, slots=2, cache_len=32)
    ids_s = [ref_small.submit(p, 4) for p in prompts[:2]]
    ids_b = [ref_big.submit(p, 4) for p in prompts[2:]]
    res_s, res_b = ref_small.run(), ref_big.run()
    toks_s = [res_s[i].tokens for i in ids_s]
    toks_b = [res_b[i].tokens for i in ids_b]

    gs = [rs.submit(p, 4, model="small") for p in prompts[:2]]
    gb = [rs.submit(p, 4, model="big") for p in prompts[2:]]
    res = rs.run()
    for g, toks in zip(gs + gb, toks_s + toks_b):
        np.testing.assert_array_equal(res[g].tokens, toks)

    md = rs.metrics_dict()
    assert md["per_replica"]["replica0"]["model"] == "small"
    assert md["per_replica"]["replica1"]["model"] == "big"

    # the model kwarg is rejected on single-model sets
    rs_single = ReplicaSet(lm_a, va, replicas=1, slots=2, cache_len=32)
    with pytest.raises(FriendlyError, match="multi-model"):
        rs_single.submit(np.zeros(4, np.int32), 4, model="small")

    with pytest.raises(FriendlyError, match="at least one model"):
        ReplicaSet(lm_a, va, replicas=2, models={})
    with pytest.raises(FriendlyError, match="replicas"):
        ReplicaSet(lm_a, va, replicas=1,
                   models={"a": (lm_a, va), "b": (lm_b, vb)})
