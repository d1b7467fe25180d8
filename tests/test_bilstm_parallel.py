"""BiLSTM multi-chip legs (BASELINE config #5, reference notebook 304).

The reference runs its BiLSTM through CNTKModel data-parallel only
(SURVEY.md §5: no sequence parallelism exists there). Parity leg: DP
training on the mesh. Upgrade leg: sequence-dim sharding via the chunked
recurrence chain (parallel/sequence_rnn.py) — exact against the dense
flax path, and differentiable.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models import build_model
from mmlspark_tpu.parallel import bilstm_seq_parallel_apply, make_mesh


@pytest.fixture(scope="module")
def tagger():
    graph = build_model(
        "bilstm_tagger", vocab_size=31, embed_dim=8, hidden=6, num_tags=5
    )
    variables = graph.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32)
    )
    return graph, variables


def _ids(rng, b, t, vocab=31):
    return rng.integers(0, vocab, size=(b, t)).astype(np.int32)


def _seq_parallel(graph, ids, mesh):
    """The chunked chain under jit: eager, a ``shard_map`` dispatches every
    step of the chain as a program of its own."""
    return jax.jit(lambda v: bilstm_seq_parallel_apply(graph, v, ids, mesh))


def test_seq_parallel_matches_dense(tagger):
    graph, variables = tagger
    rng = np.random.default_rng(0)
    ids = _ids(rng, 3, 16)
    mesh = make_mesh({"seq": 8})
    dense = np.asarray(graph.apply(variables, jnp.asarray(ids)))
    par = np.asarray(_seq_parallel(graph, ids, mesh)(variables))
    np.testing.assert_allclose(par, dense, atol=1e-5, rtol=1e-5)


def test_seq_parallel_data_seq_mesh(tagger):
    """2D data x seq mesh: batch and time sharded simultaneously."""
    graph, variables = tagger
    rng = np.random.default_rng(1)
    ids = _ids(rng, 4, 12)
    mesh = make_mesh({"data": 2, "seq": 4})
    dense = np.asarray(graph.apply(variables, jnp.asarray(ids)))
    par = np.asarray(_seq_parallel(graph, ids, mesh)(variables))
    np.testing.assert_allclose(par, dense, atol=1e-5, rtol=1e-5)


def test_seq_parallel_rejects_indivisible(tagger):
    graph, variables = tagger
    ids = _ids(np.random.default_rng(2), 2, 9)
    mesh = make_mesh({"seq": 8})
    with pytest.raises(ValueError, match="not divisible"):
        bilstm_seq_parallel_apply(graph, variables, ids, mesh)


def test_seq_parallel_grads_match_dense(tagger):
    """ppermute transposes cleanly: the seq-sharded forward trains.
    Gradients w.r.t. every variable match the dense path."""
    graph, variables = tagger
    rng = np.random.default_rng(3)
    ids = _ids(rng, 2, 8)
    tags = rng.integers(0, 5, size=(2, 8)).astype(np.int32)
    mesh = make_mesh({"seq": 4})

    def loss_dense(v):
        logits = graph.apply(v, jnp.asarray(ids))
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(lp, jnp.asarray(tags)[..., None], -1)
        )

    def loss_par(v):
        logits = bilstm_seq_parallel_apply(graph, v, ids, mesh)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(lp, jnp.asarray(tags)[..., None], -1)
        )

    from jax.flatten_util import ravel_pytree

    # under jit: an eager shard_map backward dispatches every step of
    # the chain op by op
    gd = jax.jit(jax.grad(loss_dense))(variables)
    gp = jax.jit(jax.grad(loss_par))(variables)
    flat_d, _ = ravel_pytree(gd)
    flat_p, _ = ravel_pytree(gp)
    # tolerance: the bf16 head matmul backward accumulates in a
    # different order under shard_map; LSTM grads are f32
    np.testing.assert_allclose(
        np.asarray(flat_p), np.asarray(flat_d), atol=2e-3, rtol=2e-2
    )


def test_bilstm_mixed_axis_training_step(tagger):
    """BASELINE config #5's training claim end-to-end: ONE jitted SGD
    step with batch sharded over 'data' AND time sharded over 'seq'
    simultaneously. The backward traverses the chunked recurrence chain
    (ppermute transpose); loss must decrease over a few steps and the
    trained weights must still agree with the dense forward."""
    from mmlspark_tpu.parallel import bilstm_seq_parallel_train_step

    graph, variables = tagger
    rng = np.random.default_rng(5)
    ids = _ids(rng, 4, 12)
    tags = (ids % 5).astype(np.int32)
    mesh = make_mesh({"data": 2, "seq": 4})

    losses = []
    v = variables
    for _ in range(4):
        loss, v = bilstm_seq_parallel_train_step(
            graph, v, ids, tags, mesh, learning_rate=5e-2
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    par = np.asarray(bilstm_seq_parallel_apply(graph, v, ids, mesh))
    dense = np.asarray(graph.apply(v, jnp.asarray(ids)))
    np.testing.assert_allclose(par, dense, atol=1e-5, rtol=1e-5)


def test_bilstm_dp_training_on_mesh():
    """Reference-parity leg: data-parallel BiLSTM training over the mesh
    (the multi-chip shape notebook 304's eval implies), loss decreasing."""
    from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig

    graph = build_model(
        "bilstm_tagger", vocab_size=31, embed_dim=8, hidden=6, num_tags=5
    )
    rng = np.random.default_rng(4)
    n = jax.device_count()
    ids = _ids(rng, 8 * n, 8)
    # learnable rule: tag = token parity — loss must drop fast
    tags = (ids % 5).astype(np.int32)
    trainer = SPMDTrainer(
        graph,
        TrainConfig(
            epochs=6, batch_size=4 * n, learning_rate=5e-2,
            mesh_axes={"data": n}, log_every=1, shuffle=False,
        ),
    )
    trainer.train(ids, tags)
    losses = [h["loss"] for h in trainer.history]
    assert losses[-1] < losses[0] * 0.8, losses
