"""SPMD trainer tests on the 8-device CPU mesh: loss decreases, gradient
sync across shards is correct, checkpoint/resume works (reference analog:
ValidateCntkTrain.scala e2e tiny-epoch training)."""

import jax
import pytest
import numpy as np

from mmlspark_tpu.data.dataset import Dataset
from mmlspark_tpu.models import build_model
from mmlspark_tpu.stages.dnn_learner import DNNLearner
from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig, masked_loss


def _two_blob_data(n=256, d=8, seed=0):
    rng = np.random.default_rng(seed)
    half = n // 2
    x = np.concatenate(
        [rng.normal(-1.5, 1.0, (half, d)), rng.normal(1.5, 1.0, (half, d))]
    ).astype(np.float32)
    y = np.concatenate([np.zeros(half), np.ones(half)]).astype(np.int32)
    perm = rng.permutation(n)
    return x[perm], y[perm]


def test_loss_decreases_and_learns():
    x, y = _two_blob_data()
    g = build_model("mlp", num_outputs=2, hidden=(16,))
    trainer = SPMDTrainer(
        g, TrainConfig(epochs=5, batch_size=64, learning_rate=1e-2,
                       log_every=1)
    )
    variables = trainer.train(x, y)
    losses = [h["loss"] for h in trainer.history]
    assert losses[-1] < losses[0] * 0.5
    logits = np.asarray(g.apply(variables, x))
    acc = float((np.argmax(logits, 1) == y).mean())
    assert acc > 0.95


def test_batch_sharded_over_mesh_matches_single_device():
    """Gradient sync: training over the 8-way data axis must match the math
    of unsharded training (same seed, same batches => same params)."""
    x, y = _two_blob_data(n=128)
    cfg = dict(epochs=2, batch_size=32, learning_rate=5e-3, shuffle=False,
               log_every=1)
    g = build_model("mlp", num_outputs=2, hidden=(8,))
    v8 = SPMDTrainer(g, TrainConfig(**cfg)).train(x, y)
    v1 = SPMDTrainer(
        g, TrainConfig(**cfg, mesh_axes={"data": 1})
    ).train(x, y)
    for a, b in zip(jax.tree_util.tree_leaves(v8), jax.tree_util.tree_leaves(v1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


def test_mask_weighted_loss_ignores_padding():
    import jax.numpy as jnp

    logits = jnp.array([[2.0, 0.0], [0.0, 2.0], [9.0, -9.0]])
    labels = jnp.array([0, 1, 1])  # third row wrong but masked out
    full = masked_loss("softmax_xent", logits, labels,
                       jnp.array([True, True, True]))
    masked = masked_loss("softmax_xent", logits, labels,
                         jnp.array([True, True, False]))
    assert float(masked) < float(full)


def test_checkpoint_resume(tmp_path):
    x, y = _two_blob_data(n=64)
    g = build_model("mlp", num_outputs=2, hidden=(8,))

    def cfg(epochs):
        return TrainConfig(
            epochs=epochs, batch_size=32, learning_rate=1e-2,
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1,
            shuffle=False, log_every=1,
        )

    t1 = SPMDTrainer(g, cfg(epochs=1))
    t1.train(x, y)
    # resume run: picks up from the saved step, continues to epoch 2
    t2 = SPMDTrainer(g, cfg(epochs=2))
    t2.train(x, y)
    assert t2.history[0]["step"] > 0  # did not restart from step 0


def test_dnn_learner_stage_end_to_end():
    x, y = _two_blob_data(n=128)
    ds = Dataset({"features": x, "label": y})
    learner = DNNLearner(
        model_name="mlp",
        model_config={"hidden": (16,)},
        epochs=4,
        batch_size=32,
        learning_rate=1e-2,
    )
    model = learner.fit(ds)
    out = model.transform(ds)
    preds = np.argmax(out["scores"], axis=1)
    assert (preds == y).mean() > 0.9
    assert model.train_history  # history carried on the model


def test_dnn_learner_drops_nan_labels():
    x, y = _two_blob_data(n=64)
    yf = y.astype(np.float64)
    yf[:8] = np.nan
    ds = Dataset({"features": x, "label": yf})
    model = DNNLearner(model_name="mlp", epochs=1, batch_size=32).fit(ds)
    assert model.weights is not None


def test_regression_mse_loss():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 4)).astype(np.float32)
    w = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
    y = x @ w
    ds = Dataset({"features": x, "label": y})
    model = DNNLearner(
        model_name="linear", loss="mse", epochs=60, batch_size=64,
        learning_rate=0.1, optimizer="momentum",
    ).fit(ds)
    out = model.transform(ds)
    pred = out["scores"][:, 0]
    resid = np.mean((pred - y) ** 2) / np.var(y)
    assert resid < 0.05


def test_mid_epoch_resume_continues_data_position(tmp_path):
    """Kill mid-epoch; resume must continue at the next batch, not replay
    the epoch (step arithmetic drives the LR schedule and history)."""
    x, y = _two_blob_data(n=96)  # 3 steps/epoch at batch 32
    g = build_model("mlp", num_outputs=2, hidden=(8,))

    def cfg(epochs):
        return TrainConfig(epochs=epochs, batch_size=32, learning_rate=1e-2,
                           checkpoint_dir=str(tmp_path / "ck"),
                           checkpoint_every=1, shuffle=False, log_every=1)

    # full 2-epoch run for ground truth step count
    t_full = SPMDTrainer(g, cfg(2))
    t_full.train(x, y)
    total_steps_full = t_full.history[-1]["step"]
    # now simulate crash after 1 epoch + resume to 2 epochs
    import shutil
    shutil.rmtree(tmp_path / "ck")
    SPMDTrainer(g, cfg(1)).train(x, y)
    t_resumed = SPMDTrainer(g, cfg(2))
    t_resumed.train(x, y)
    assert t_resumed.history[-1]["step"] == total_steps_full
    assert t_resumed.history[0]["step"] == 3  # continued, no replay


def test_steps_per_dispatch_exactness():
    """Chaining K steps in one lax.scan dispatch is an execution strategy,
    not a semantic change: final params must match the 1-step path,
    including an epoch tail that doesn't fill a chunk (10 steps, K=4)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(80, 6)).astype(np.float32)  # 10 batches of 8
    y = (x[:, 0] > 0).astype(np.int32)
    graph = build_model("mlp", num_outputs=2, hidden=(8,))

    def run(k):
        tr = SPMDTrainer(
            graph,
            TrainConfig(epochs=2, batch_size=8, learning_rate=1e-2,
                        steps_per_dispatch=k, seed=3),
        )
        return tr.train(x, y)

    v1, v4 = run(1), run(4)
    flat1 = jax.tree_util.tree_leaves(v1)
    flat4 = jax.tree_util.tree_leaves(v4)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_remat_is_semantics_preserving():
    """jax.checkpoint trades FLOPs for memory; final params must match the
    non-remat run exactly."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = (x[:, 1] > 0).astype(np.int32)
    graph = build_model("mlp", num_outputs=2, hidden=(8,))

    def run(remat):
        tr = SPMDTrainer(
            graph,
            TrainConfig(epochs=2, batch_size=16, learning_rate=1e-2,
                        remat=remat, seed=5),
        )
        return tr.train(x, y)

    for a, b in zip(
        jax.tree_util.tree_leaves(run(False)),
        jax.tree_util.tree_leaves(run(True)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_grad_accum_matches_full_batch_sgd():
    """grad_accum=K averages micro-batch gradients before ONE optimizer
    update, so SGD training must reproduce the no-accumulation params up
    to compute precision. The model family computes in bf16, so the
    micro vs full forward differs at bf16 epsilon (2^-8 relative) per
    step — tolerances are bf16-scale, not f32-exact."""
    import jax.numpy as jnp

    from mmlspark_tpu.models import build_model

    rng = np.random.default_rng(3)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)

    def run(accum):
        graph = build_model("mlp", num_outputs=2, hidden=(16,))
        tr = SPMDTrainer(
            graph,
            TrainConfig(epochs=2, batch_size=16, learning_rate=0.1,
                        optimizer="sgd", grad_accum=accum, shuffle=False,
                        log_every=100),
        )
        v = tr.train(x, y)
        return jax.tree_util.tree_leaves(v), [
            h["loss"] for h in tr.history if "loss" in h
        ]

    p1, l1 = run(1)
    p2, l2 = run(2)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-2)
    np.testing.assert_allclose(l1, l2, atol=2e-3, rtol=2e-2)


def test_grad_accum_exact_on_padded_tail():
    """The tail batch (4 real rows + 12 padding at n=20, batch=16) must
    produce the SAME update under accumulation: micro losses accumulate
    as weighted sums normalized once, so padding concentrated in some
    micro-batches cannot shrink the step."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(20, 8)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)

    def run(accum):
        graph = build_model("mlp", num_outputs=2, hidden=(16,))
        tr = SPMDTrainer(
            graph,
            TrainConfig(epochs=1, batch_size=16, learning_rate=0.1,
                        optimizer="sgd", grad_accum=accum, shuffle=False,
                        log_every=100),
        )
        v = tr.train(x, y)
        return jax.tree_util.tree_leaves(v)

    for a, b in zip(run(1), run(2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-2)


def test_atomic_store_opt_state_roundtrip(tmp_path):
    """The checkpoint store must round-trip a real optimizer state
    EXACTLY: every leaf bit-identical, every dtype preserved (adam's
    int32 step count included), and the JSON meta sidecar intact."""
    import optax

    from mmlspark_tpu.train.resilience import AtomicCheckpointStore

    params = {
        "w": np.arange(6, dtype=np.float32).reshape(2, 3) / 7.0,
        "b": np.linspace(-1, 1, 3).astype(np.float16),
    }
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    grads = jax.tree_util.tree_map(np.ones_like, params)
    _, opt = tx.update(grads, opt, params)  # non-trivial mu/nu/count
    state = {"params": params, "opt_state": jax.device_get(opt)}

    store = AtomicCheckpointStore(str(tmp_path / "ck"))
    store.save(4, state, meta={"note": "roundtrip"})
    target = jax.tree_util.tree_map(np.zeros_like, state)
    restored, meta, step = store.restore(target)
    assert step == 4
    assert meta == {"note": "roundtrip"}
    for a, b in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(restored),
    ):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_split_merge_variables_exact_reconstruction():
    """_split_variables must strip ONLY the sown per-call losses;
    _merge_variables must reassemble everything else exactly."""
    from mmlspark_tpu.train.trainer import (
        _merge_variables,
        _split_variables,
    )

    rng = np.random.default_rng(0)
    variables = {
        "block0": {
            "params": {"w": rng.normal(size=(2, 2)).astype(np.float32)},
            "batch_stats": {"mean": np.zeros(2, np.float32)},
            "losses": {"aux": np.float32(0.5)},
        },
        "head": {"params": {"b": np.ones(3, np.float32)}},
    }
    params, rest = _split_variables(variables)
    assert set(params) == {"block0", "head"}
    assert "losses" not in rest["block0"]
    assert "params" not in rest["block0"]
    merged = _merge_variables(params, rest)
    expected = {
        "block0": {
            "params": variables["block0"]["params"],
            "batch_stats": variables["block0"]["batch_stats"],
        },
        "head": {"params": variables["head"]["params"]},
    }
    assert jax.tree_util.tree_structure(merged) == \
        jax.tree_util.tree_structure(expected)
    for a, b in zip(
        jax.tree_util.tree_leaves(merged),
        jax.tree_util.tree_leaves(expected),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_accum_divisibility_guard():
    from mmlspark_tpu.core.exceptions import FriendlyError
    from mmlspark_tpu.models import build_model

    graph = build_model("mlp", num_outputs=2, hidden=(8,))
    x = np.zeros((12, 4), np.float32)
    y = np.zeros((12,), np.int32)
    tr = SPMDTrainer(
        graph,
        TrainConfig(epochs=1, batch_size=12, grad_accum=5, shuffle=False),
    )
    with pytest.raises(FriendlyError, match="grad_accum"):
        tr.train(x, y)


# -- regions of the step ------------------------------------------------------

STEP_REGIONS = ("train.feed", "train.dispatch", "train.sync", "train.log")


def _step_regions(events):
    return [e for e in events if e["name"].startswith("train.")]


@pytest.mark.parametrize("steps_per_dispatch", [1, 2])
def test_every_step_leaves_five_regions(steps_per_dispatch):
    """One group of the loop is one ``train.step`` with the feed, the
    dispatch, the sync and the log inside it, in that order; the pull that
    finds the epoch's end leaves nothing."""
    x, y = _two_blob_data(n=256)
    g = build_model("mlp", num_outputs=2, hidden=(8,))
    trainer = SPMDTrainer(g, TrainConfig(
        epochs=2, batch_size=64, learning_rate=1e-2, log_every=1,
        steps_per_dispatch=steps_per_dispatch))
    trainer.train(x, y)
    groups = 2 * 4 // steps_per_dispatch
    regions = _step_regions(trainer.recorder.events())
    assert len(regions) == 5 * groups
    for i in range(groups):
        five = regions[5 * i:5 * i + 5]
        # each event is written when its region ends: the step's last
        assert [e["name"] for e in five] == [*STEP_REGIONS, "train.step"]
        first = i * steps_per_dispatch
        assert {e["tick"] for e in five} == {first}
        assert [e["attrs"].get("parent") for e in five] == [
            "train.step"] * 4 + [None]
        step = five[-1]["attrs"]
        inner = [e["attrs"] for e in five[:4]]
        assert all(a["t0"] >= step["t0"] for a in inner)
        assert sum(a["ms"] for a in inner) <= step["ms"] + 0.01
        assert [a["t0"] for a in inner] == sorted(a["t0"] for a in inner)
    # the program compiles in the first dispatch and in no later one
    dispatches = [e["attrs"] for e in regions if e["name"] == "train.dispatch"]
    assert dispatches[0]["compiles"] >= 1
    assert all("compiles" not in a for a in dispatches[2:])
    # the step events keep their form, one a group at this cadence
    steps = [e for e in trainer.recorder.events() if e["name"] == "step"]
    assert [e["tick"] for e in steps] == [
        (i + 1) * steps_per_dispatch - 1 for i in range(groups)]
    assert trainer.telemetry.histogram("train.step_ms").count == groups


def test_a_raise_out_of_the_step_event_leaves_the_recorder_whole():
    """The benchmark ends its training window by raising out of
    ``recorder.record("step", ...)``: the regions that were open record
    themselves with the error and let it through."""
    from mmlspark_tpu.core.telemetry import FlightRecorder

    class WindowClosed(Exception):
        pass

    class Closing(FlightRecorder):
        def record(self, name, **kw):
            super().record(name, **kw)
            if name == "step" and kw["tick"] == 2:
                raise WindowClosed

    x, y = _two_blob_data(n=256)
    g = build_model("mlp", num_outputs=2, hidden=(8,))
    recorder = Closing()
    trainer = SPMDTrainer(g, TrainConfig(
        epochs=2, batch_size=64, learning_rate=1e-2, log_every=1),
        recorder=recorder)
    with pytest.raises(WindowClosed):
        trainer.train(x, y)
    events = recorder.events()
    assert [e["tick"] for e in events if e["name"] == "step"] == [0, 1, 2]
    regions = _step_regions(events)
    assert len(regions) == 5 * 3
    assert [e["name"] for e in regions[-5:]] == [*STEP_REGIONS, "train.step"]
    errors = [e["attrs"].get("error") for e in regions]
    assert errors == [None] * 13 + ["WindowClosed"] * 2
    # nothing is left open: a second call starts at the top again
    with pytest.raises(WindowClosed):
        trainer.train(x, y)
    again = _step_regions(recorder.events())[15:]
    assert [e["attrs"].get("parent") for e in again[:5]] == [
        "train.step"] * 4 + [None]
