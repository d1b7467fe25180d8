"""A model family as data. A second family (grouped KV heads, rotary
positions; ``data/gqa_rope/``) goes through the real harness as new files
only; the ``gpt2`` family is pinned through its move by readings taken from
the parent's code; a family that lacks what its cell needs fails before any
weight is made; and no module of the harness imports a family by name.

Readings at the second family's size, on the CPU: the program's
``served_gap`` over 16 seeds of the cell (51 to 71 served tokens each) lies
between 0 and 0.0018; over 24 seeded sequences of 48 tokens the reference
in bfloat16 reads 0 to 0.0018 and the int8 control 0.0041 to 0.0081 (6
seeds), float8 0.026 to 0.044. The cell's limit is 0.0035.
"""

import hashlib
import os
import re

import jax
import numpy as np
import pytest

from benchmark import check, family, run, trace_reduce
from benchmark.readers import decode_blocks
from benchmark.readers.decode_share import micro_steps

import perfbench_family as second
import perfbench_tiny as tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("perfbench_family"))
    return root, second.build(root)


def cell_family(tree):
    root, manifest = tree
    files = run.cell_files(manifest, second.CELL, root)
    return files, family.resolve(files["config"], "backlog",
                                 files["control_mode"], root)


# -- a second family, as new files only ----------------------------------------


def test_the_family_is_found_under_the_root_it_was_added_to(tree):
    files, fam = cell_family(tree)
    root = tree[0]
    for module in (fam.reference, fam.counts, fam.adapter):
        assert module.__file__.startswith(root), module.__file__
    assert fam.builder == "transformer_lm"
    assert fam.sz["kv_d"] == 32 and fam.sz["d"] == 64
    # the harness's own cells still mean the gpt2 family
    large = run.cell_files(tree[1], "gpt2-large.chat-backlog", root)
    assert family.resolve(large["config"], "backlog", "int8",
                          root).reference.__file__.endswith(
        os.path.join("references", "gpt2.py"))


def test_the_second_family_serves_correctly_through_the_real_harness(tree):
    root, manifest = tree
    result, division = tiny.run_cell(root, manifest, second.CELL, seed=2)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    limit = result["compared"]["served_gap"]["limit"]
    assert result["compared"]["served_gap"]["value"] <= limit == 0.0035
    assert result["details"]["tokens_compared"] > 40
    assert "weights_s" in division["setup_division"]


def test_the_int8_control_of_the_second_family_reads_over_its_limit(tree):
    files, fam = cell_family(tree)
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 96, 16).astype(np.int32),
                rng.integers(0, 96, 48).astype(np.int32)) for _ in range(24)]
    stated, control = (check.served_gaps(
        fam.reference, fam.sz, 2, samples,
        files["config"]["program"]["engine"]["cache_len"], mode)["control_gap"]
        for mode in ("bf16", files["control_mode"]))
    assert stated < files["limits"]["served_gap"] < control


def toy_trace(*_):
    """One decode program of 4 ms that ran the kernel four times: at two
    layers, two micro-steps of 2 ms, the kernel 1 ms a call."""
    ms = 1_000_000
    return trace_reduce.Trace(
        {0: [(i * ms, (i + 1) * ms, f"attn.{i}") for i in range(4)]},
        {0: [(0, 4 * ms, "jit_decode_block(1)")]}, [])


def test_the_rooflines_come_from_the_familys_own_counts(tree, monkeypatch):
    root, manifest = tree
    seen = {}
    evaluate = run.evaluate

    def spy(entries, state, root):
        seen.update(entries=entries, state=state)
        return evaluate(entries, state, root)

    monkeypatch.setattr(run, "evaluate", spy)
    monkeypatch.setattr(trace_reduce, "load", toy_trace)
    result, _ = tiny.run_cell(root, manifest, second.CELL, seed=2,
                              trace=True)
    assert result["correct"] is True, result["compared"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(second.COUNTED) <= set(got)
    state, sz = seen["state"], seen["state"]["sz"]
    counts = state["counts"]
    assert counts.__file__.startswith(root) and "gqa_rope" in counts.__file__
    # the same events, counted as gpt2 would count them
    gpt2 = family.load(root, "counts", "gpt2")
    counted = [e for e in seen["entries"] if e["name"] in second.COUNTED]
    as_gpt2 = {k: v["value"] for k, v in evaluate(
        counted, dict(state, counts=gpt2), root).items()}
    steps = list(micro_steps(decode_blocks(state)))
    rows = sum(sum(lens) for lens in steps) / len(steps)
    live = sum(len(lens) for lens in steps) / len(steps)
    narrower = sz["kv_d"] / sz["d"]
    assert narrower == 0.5
    # the kernel: the K and V rows read are kv_d wide, the queries in and
    # the outputs out are the same under both counts
    share = 100.0 / tiny.PEAK["hbm_bytes_per_s"] / 0.001
    q_and_o = share * 4 * live * sz["d"]
    assert got["attn_decode_roofline"] == pytest.approx(
        share * 4 * rows * sz["kv_d"] + q_and_o)
    assert (got["attn_decode_roofline"] - q_and_o) / (
        as_gpt2["attn_decode_roofline"] - q_and_o) == pytest.approx(narrower)
    # the whole micro-step: the rows read and written, beside parameters
    # that each family counts for itself
    share = 100.0 / tiny.PEAK["hbm_bytes_per_s"] / 0.002
    kv = share * sz["layers"] * 4 * (rows + live) * sz["d"]
    assert got["decode_hbm_roofline"] == pytest.approx(
        share * counts.stored_param_bytes(sz) + narrower * kv)
    assert as_gpt2["decode_hbm_roofline"] == pytest.approx(
        share * gpt2.stored_param_bytes(sz) + kv)
    assert got["decode_step_mfu_pct"] < as_gpt2["decode_step_mfu_pct"]


# -- what a family lacks is said before any weight is made ---------------------


def broken_files(tree, fault):
    root, manifest = tree
    files = run.cell_files(manifest, second.CELL, root)
    if fault == "no such reference":
        program = dict(files["config"]["program"], reference="nowhere")
        files["config"] = dict(files["config"], program=program)
    elif fault == "a serving-only reference under a train mix":
        files["mix"] = tiny.MIXES["tiny-train"]
    elif fault == "a control the reference cannot round to":
        files["control_mode"] = "int4"
    return files


@pytest.mark.parametrize("fault, said", [
    ("no such reference", r"no module 'nowhere' under benchmark/references"),
    ("a serving-only reference under a train mix",
     r"gqa_rope.*lacks loss_sum, split_leaves"),
    ("a control the reference cannot round to", r"gqa_rope.*'int4'"),
])
def test_a_family_that_lacks_what_its_cell_needs_fails_first(
        tree, monkeypatch, fault, said):
    from benchmark import serving

    def no_weights(*_):
        raise AssertionError("weights were made")

    monkeypatch.setattr(serving, "build_weights", no_weights)
    root, manifest = tree
    with pytest.raises(SystemExit, match=said):
        run.run_cell(manifest, broken_files(tree, fault), second.CELL, 1, 1.0,
                     False, tiny.DEVICE, tiny.PEAK, root)


def test_a_count_a_reader_needs_and_the_family_lacks_is_named(tree):
    root, manifest = tree
    _, fam = cell_family(tree)
    needed = run.counts_needed(manifest["per_layer"], root)
    assert {"decode_step_bytes", "attn_decode_flops",
            "train_flops_per_token"} <= set(needed)
    with pytest.raises(SystemExit, match="gqa_rope.*train_flops_per_token"):
        family.require(fam.counts, needed, "the counts module")


def test_no_module_of_the_harness_imports_a_family_by_name():
    bench = os.path.join(family.ROOT, "benchmark")
    by_name = re.compile(
        r"^\s*(from|import)\s+benchmark(\.(references|counts|adapters)\b"
        r"|\s+import\s+[^\n]*\b(references|counts|adapters|reference)\b)",
        re.M)
    sources = [os.path.join(bench, n) for n in sorted(os.listdir(bench))]
    sources += [os.path.join(bench, "readers", n)
                for n in sorted(os.listdir(os.path.join(bench, "readers")))]
    read = 0
    for path in sources:
        if not path.endswith(".py"):
            continue
        with open(path) as f:
            text = f.read()
        read += 1
        assert not by_name.search(text), path
        if not path.endswith("family.py"):   # where an absent name is gpt2
            assert "gpt2" not in text.lower(), path
    assert read > 25


# -- the gpt2 family, pinned through its move ----------------------------------
# Every number below was printed by the PARENT's code (commit 4954744:
# benchmark/reference.py and benchmark/flops.py) before anything moved.

TINY = {"vocab_size": 96, "n_positions": 64, "n_embd": 32, "n_layer": 2,
        "n_head": 4, "n_inner": None, "layer_norm_epsilon": 1e-06,
        "program": {"adapter": "transformer_lm"}}
LIVE = ([1], [33, 257, 1024], list(range(40, 40 + 16 * 37, 37)))
GOLDEN = {
    "gpt2-large": {
        "sizes": {"d": 1280, "3d": 3840, "f": 5120, "v": 50257, "p": 1024,
                  "layers": 36, "heads": 20, "eps": 1e-06},
        "decode_step_flops": (1544419840, 4874903040, 25644113920),
        "decode_step_bytes": (3091447108, 3333827908, 4030373188),
        "attn_decode_bytes": (10240, 6743040, 26091520),
        "attn_decode_flops": (5120, 6727680, 26009600),
        "prefill_flops": (1544419840, 46946122240, 943906040320,
                          1546411256320),
        "train_flops_per_token": (4668372480.0, 4916098560.0),
        "stored_param_bytes": 3091078468},
    "gpt2-medium": {
        "sizes": {"d": 1024, "3d": 3072, "f": 4096, "v": 50257, "p": 1024,
                  "layers": 24, "heads": 16, "eps": 1e-06},
        "decode_step_flops": (707004416, 2249889792, 11809882112),
        "decode_step_bytes": (1415496004, 1544765764, 1916256580),
        "attn_decode_bytes": (8192, 5394432, 20873216),
        "attn_decode_flops": (4096, 5382144, 20807680),
        "prefill_flops": (707004416, 20089407488, 406814099456,
                          670168156160),
        "train_flops_per_token": (2139740160.0, 2271860736.0),
        "stored_param_bytes": 1415299396},
}


def test_gpt2_init_params_are_the_parents_leaf_for_leaf():
    fam = family.resolve(TINY, "train", "fp8")
    assert fam.reference.__name__ == "benchmark.references.gpt2"
    params = jax.jit(lambda k: fam.reference.init_params(k, fam.sz))(
        family.seed_key(3000000019))
    digest = hashlib.sha256()
    for name in sorted(params):
        leaf = np.asarray(params[name])
        digest.update(name.encode())
        digest.update(str(leaf.shape).encode())
        digest.update(leaf.tobytes())
    assert len(params) == 18
    assert hashlib.sha256(np.asarray(params["qkv_w"]).tobytes()).hexdigest()[
        :16] == "af44c3f6fab0ae41"
    assert float(np.asarray(params["wte"], np.float64).sum()) == \
        pytest.approx(1.274012240936372, abs=1e-9)
    assert digest.hexdigest() == ("c96a834fe53da0b66dd3e518ef407211"
                                  "7f90d8a26cceff089a818790f4eb4515")


@pytest.mark.parametrize("config", sorted(GOLDEN))
def test_gpt2_sizes_and_counts_are_the_parents(config):
    want = GOLDEN[config]
    cfg = run.load_json(family.ROOT, "benchmark", "configs", f"{config}.json")
    fam = family.resolve(cfg, "backlog", "int8")
    assert fam.reference.sizes(cfg) == want["sizes"]
    # what counting adds to them: the widths the parent held as constants
    assert fam.sz == dict(want["sizes"], param_bytes=4, kv_bytes=2)
    c, sz = fam.counts, fam.sz
    for name in ("decode_step_flops", "decode_step_bytes",
                 "attn_decode_bytes", "attn_decode_flops"):
        assert tuple(getattr(c, name)(sz, lens, {}) for lens in LIVE) == \
            want[name], name
    assert tuple(c.prefill_flops(sz, n) for n in (1, 33, 640, 1024)) == \
        want["prefill_flops"]
    assert tuple(c.train_flops_per_token(sz, n) for n in (128, 1024)) == \
        want["train_flops_per_token"]
    assert c.stored_param_bytes(sz) == want["stored_param_bytes"]
    # a configuration that states other widths is counted at them
    stated = dict(cfg, program=dict(cfg["program"],
                                    stored={"param_bytes": 2}))
    halved = c.sizes(stated, want["sizes"])
    assert c.stored_param_bytes(halved) * 2 == want["stored_param_bytes"]
