"""The comparison that decides ``correct``, shown to fail: the control (the
reference in a lower precision, put in the program's place) at a size the
CPU holds, and whole runs with the timed path broken underneath. The look
for a chip is skipped (``run_cell`` is what follows it); everything else is
a run's own code.

Readings at this size, on the CPU (seeds 1-4): the program's first-step
losses lie within 1.0e-5 of the reference's, the float8 control's 7.1e-5 to
1.5e-4 away (int8 reads 1.5e-5 to 2.7e-5 here: at a width of 32 it is no
coarser than bfloat16, so this size uses float8; the chip's cells use what
their files name). Half a batch reads 0.45-0.52 on the first gradient's
norm and a quarter 0.94-1.31, against 2.4e-4 for sound runs; a state left
unchanged reads 1 on the parameters' change by construction.
"""

import json

import numpy as np
import pytest

from benchmark import check, family, limits, run

import perfbench_tiny as tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("perfbench"))
    return root, tiny.build(root)


def compared(result):
    return {k: v["value"] for k, v in result["compared"].items()}


# -- the control --------------------------------------------------------------


def test_the_training_control_fails_and_the_faults_fail(tree):
    root, manifest = tree
    files = run.cell_files(manifest, "tiny.tiny-train", root)
    files["control_mode"] = "fp8"
    readings = limits.training_seed(files, seed=2, root=root)
    lim = files["limits"]
    ok, _ = check.verdict(readings["control"], lim)
    assert not ok and readings["control"]["loss_gap"] > lim["loss_gap"]
    for fault in ("half_batch", "no_exchange"):
        ok, _ = check.verdict(readings[fault], lim)
        assert not ok, fault
        assert readings[fault]["grad_gap"] > 10 * lim["grad_gap"]


def test_the_serving_control_reads_wider_than_the_stated_precision():
    ref = family.load(run.ROOT, "references", "gpt2")
    sz = {"d": 64, "3d": 192, "f": 256, "v": 512, "p": 64, "layers": 2,
          "heads": 4, "eps": 1e-6}
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 512, 16).astype(np.int32),
                rng.integers(0, 512, 40).astype(np.int32)) for _ in range(6)]
    stated, control = [], []
    for seed in (1, 2, 3):
        stated.append(check.served_gaps(ref, sz, seed, samples, 64,
                                        "bf16")["control_gap"])
        control.append(check.served_gaps(ref, sz, seed, samples, 64,
                                         "fp8")["control_gap"])
    assert min(control) > 3 * max(stated) > 0


def test_a_missing_or_infinite_number_is_not_correct():
    ok, table = check.verdict({"a": 0.1, "b": float("nan")},
                              {"a": 1.0, "b": 1.0, "c": 1.0})
    assert not ok
    assert table["b"]["value"] is None and table["c"]["value"] is None
    json.dumps(table)
    assert check.verdict({"a": 0.0}, {"a": 0})[0]


def test_leaves_with_a_dead_gradient_are_left_out_by_rule_not_by_name():
    ref = family.load(run.ROOT, "references", "gpt2")
    sz = {"d": 32, "3d": 96, "f": 128, "v": 96, "p": 32, "layers": 2,
          "heads": 4, "eps": 1e-6}
    x, y = (np.random.default_rng(1).integers(0, 96, (3, 4, 32))
            .astype(np.int32) for _ in range(2))
    ref_run = check.reference_steps(ref, sz, 5, list(zip(x, y)), 1e-4)
    grads = check.leaf_norms(ref, ref_run["first_grad"])
    median = np.median(list(grads.values()))
    dead = {k for k, g in grads.items()
            if g < check.DEAD_GRADIENT_SHARE * median}
    assert dead == {"qkv_b[0].k", "qkv_b[1].k"}
    gaps = check.train_gaps(ref, ref_run, ref_run["losses"],
                            float(np.sqrt(sum(g * g for g in grads.values()))),
                            ref_run["end"])
    assert gaps["leaves_left_out"] == 2
    assert gaps["loss_gap"] == gaps["grad_gap"] == gaps["delta_gap"] == 0.0
    # a state left unchanged reads 1 on the change, whatever the seed
    still = check.train_gaps(ref, ref_run, ref_run["losses"], 1.0,
                             ref_run["start"])
    assert still["delta_gap"] == pytest.approx(1.0)


# -- whole runs, sound and broken ---------------------------------------------


@pytest.mark.parametrize("cell", ["tiny.tiny-open", "tiny.tiny-train"])
def test_a_sound_run_is_correct(tree, cell):
    root, manifest = tree
    result, _ = tiny.run_cell(root, manifest, cell, seed=2**31 + 7)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    names = {"tiny.tiny-open": {"ttft_ms_p50", "setup_s"},
             "tiny.tiny-train": {"train_tokens_per_s", "setup_s"}}[cell]
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tree, monkeypatch):
    from mmlspark_tpu.serve import engine as engine_mod

    real = engine_mod.make_decode_block

    def broken(graph, pad_id=0):
        block = real(graph, pad_id)

        def decode_block(*args):
            toks, live, buffers, pos = block(*args)
            return (toks + 1) % 96, live, buffers, pos

        return decode_block

    monkeypatch.setattr(engine_mod, "make_decode_block", broken)
    root, manifest = tree
    result, _ = tiny.run_cell(root, manifest, "tiny.tiny-backlog")
    assert result["correct"] is False
    assert compared(result)["served_gap"] > tiny.LIMITS[
        "tiny-backlog"]["served_gap"]


def keep_rows(share):
    """``masked_loss`` with all but the first ``share`` of the batch left
    out and the mean taken over the rest."""
    from mmlspark_tpu.train import trainer as trainer_mod

    real = trainer_mod.masked_loss

    def broken(kind, logits, labels, mask):
        import jax.numpy as jnp

        rows = logits.shape[0]
        return real(kind, logits, labels,
                    mask * (jnp.arange(rows) < int(rows * share)))

    return trainer_mod, broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_a_broken_training_step_is_not_correct(tree, monkeypatch, fault):
    if fault == "state_unchanged":
        import optax

        monkeypatch.setattr(optax, "apply_updates", lambda p, u: p)
    else:
        mod, broken = keep_rows(0.5 if fault == "half_batch" else 0.25)
        monkeypatch.setattr(mod, "masked_loss", broken)
    root, manifest = tree
    result, _ = tiny.run_cell(root, manifest, "tiny.tiny-train", seed=3)
    assert result["correct"] is False
    got, lim = compared(result), tiny.LIMITS["tiny-train"]
    if fault == "state_unchanged":
        assert got["delta_gap"] == pytest.approx(1.0, abs=1e-3)
    else:
        assert got["grad_gap"] > 10 * lim["grad_gap"]
