"""The ``sdar_moe`` family's tiny cell through the real harness on the CPU
(``perfbench_sdar.py``): one traced run, sound, reporting every metric the
chip's cell reports; its counts checked by hand (a denoising micro-step's
``L`` rows a live slot reading its clean prefix and its block, the head
in two of a block's three micro-steps, experts as hit) and held under the
whole step's at made-up lengths; the control and the planted faults
reading not ``correct``; the chip's configuration file against the
catalog's row and the published config's arithmetic.

Readings at this size, on the CPU (matrices N(0, 0.12): logits of about
3; a step's order held to the reference's confidences within
``ORDER_TIE``). The program's served tokens: 0.0 on two seeds (every
compared token the reference's best). Over 12 seeded sequences of 48
tokens on seeds 4 and 5, each mode under its own most favourable allowed
order, the float8 control reads 0.64 and 1.06, ``causal_block`` 1.37 and
2.33, ``dirty_close`` 2.92 and 3.09, ``no_topk_norm`` 0.84 and 1.16,
``no_qk_norm`` 1.00 and 1.75 (int8 0.64 and 0.81; bfloat16 0.27 and
0.47: on seed 4 one row of 576, where a rounding turned a router
choice). The
cell's limit is 0.12: under a fifth of the smallest control's or fault's
reading.
"""

import json
import os

import numpy as np
import pytest

from benchmark import check, family, run, trace_reduce
from benchmark.readers.block_steps import micro_steps
from benchmark.readers.routed import counters

import perfbench_sdar as sdar
import perfbench_tiny as tiny

MS = 1_000_000
LAYERS, L = sdar.LAYERS, sdar.BLOCK
#: the metrics the chip's cell reports from the CPU's run
REPORTED = sdar.COUNTED + (
    "decode_step_ms.block", "tpot_ms_p50",
    "compiles_in_window.backlog", "device_idle_pct.backlog", "admit_ms_p50",
    "pool_write_ms_p50", "pool_write_dispatches_mean", "expert_pairs_mean",
    "experts_hit_mean")
#: and those that only a device or its trace's host spans give: listed
#: for the cell, silent here
DEVICE_ONLY = ("block_gap_ms_p50", "hbm_peak_pct.backlog", "idle_admit_pct",
               "idle_pool_write_pct")


def toy_trace(*_):
    """One decode program of 8 ms that ran two micro-steps of the two
    layers (the block read 1 ms, each of a layer's three grouped products
    0.1 ms) and one prefill of 4 ms (the forward kernel 1 ms)."""
    ops, t = [], 0

    def op(name, ns):
        nonlocal t
        ops.append((t, t + ns, f"{name}.{len(ops)}"))
        t += ns

    for _step in range(2):
        for _layer in range(LAYERS):
            op("attn_block_decode", MS)
            for name in ("moe_gate", "moe_up", "moe_down"):
                op(name, MS // 10)
    decode_end = 8 * MS
    t = decode_end
    op("attn_block_prefill", MS)
    return trace_reduce.Trace(
        {0: ops},
        {0: [(0, decode_end, "jit_decode_block(1)"),
             (decode_end, decode_end + 4 * MS, "jit__prefill(2)")]}, [])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE run of the tiny cell, traced (the trace a toy): the result,
    the state the readers saw and the samples the comparison took."""
    root = str(tmp_path_factory.mktemp("perfbench_sdar"))
    manifest = sdar.build(root)
    seen = {}
    evaluate, served_gaps = run.evaluate, check.served_gaps

    def spy_evaluate(entries, state, root):
        seen.update(state=state)
        return evaluate(entries, state, root)

    def spy_gaps(ref, sz, seed, samples, length, mode="f32"):
        seen.update(ref=ref, samples=samples, length=length, seed=seed)
        return served_gaps(ref, sz, seed, samples, length, mode)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(run, "evaluate", spy_evaluate)
        patch.setattr(check, "served_gaps", spy_gaps)
        patch.setattr(trace_reduce, "load", toy_trace)
        result, division = tiny.run_cell(root, manifest, sdar.CELL,
                                         seed=4, trace=True)
    return result, division, seen, root


def test_the_tiny_cell_is_correct_and_reports_the_cells_metrics(traced):
    result, division, seen, root = traced
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["served_gap"]["value"] <= 0.06
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["details"]["tokens_compared"] > 30
    assert "weights_s" in division["setup_division"]
    fam = family.resolve(
        run.cell_files(run.load_json(root, "BENCHMARK.json"), sdar.CELL,
                       root)["config"], "backlog", sdar.CONTROL, root)
    for module in (fam.reference, fam.counts, fam.adapter):
        assert module.__file__.startswith(root), module.__file__
    assert fam.builder == "hybrid_lm" and fam.sz["block_len"] == L
    assert fam.sz["held"] == (0, 8) and fam.sz["denoise_steps"] == 2
    got = result["metrics"]
    assert set(REPORTED) <= set(got), set(REPORTED) - set(got)
    listed = {m["name"] for m in run.load_json(root, "BENCHMARK.json")[
        "per_layer"] if sdar.CELL in m.get("workloads", ())}
    assert set(REPORTED + DEVICE_ONLY) == listed
    # the accepted metrics that read one token a micro-step stay silent
    assert not {"decode_step_ms", "decode_step_ms.state",
                "decode_hbm_roofline.state", "attn_full_decode_roofline",
                "first_token_wait_ms_p50"} & set(got)
    assert got["pool_write_dispatches_mean"]["value"] == 1.0
    assert got["decode_step_ms.block"]["value"] == pytest.approx(4.0)
    for name in sdar.COUNTED:
        assert 0 < got[name]["value"] <= 100.0, (name, got[name])


def test_a_micro_steps_counts_by_hand(traced):
    result, _, seen, _ = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state = seen["state"]
    steps = list(micro_steps(state))
    live = sum(len(s) for s in steps) / len(steps)
    read = sum(sum(s) for s in steps) / len(steps)
    rows = live * L
    # the block read: each live slot's rows once for its 4 rows and all 8
    # query heads of a KV head; 4 queries in and 4 outputs out a slot
    flops = 2 * 8 * 2 * 16 * L * read
    nbytes = read * 2 * 32 * 2 + live * L * 2 * 128 * 2
    least = max(flops / tiny.PEAK["flops_per_s"],
                nbytes / tiny.PEAK["hbm_bytes_per_s"])
    assert got["attn_block_decode_roofline"] == pytest.approx(
        100.0 * least / 1e-3)
    routed = counters(state)
    assert got["expert_pairs_mean"] == pytest.approx(routed["expert_pairs"])
    # all 8 experts held: every live row's 2 pairs land here
    assert routed["expert_pairs"] == pytest.approx(2 * rows)
    expert = 3 * 64 * 16
    # what every row shares: q, k, v, the output and the router of both
    # layers; the head in two of a block's three micro-steps
    shared = LAYERS * (2 * 64 * 128 + 2 * 64 * 32 + 64 * 8)
    head = 2 / 3 * 64 * 96
    small = 2 * LAYERS * (64 + 16) + 64
    nbytes = ((shared + small + head) * 2
              + LAYERS * routed["experts_hit"] * expert * 2
              + LAYERS * (read + rows) * 2 * 32 * 2)
    assert got["decode_hbm_roofline.block"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 4e-3)
    flops = (2 * rows * (shared + head)
             + LAYERS * 2 * routed["expert_pairs"] * expert
             + LAYERS * 2 * 8 * 2 * 16 * L * read)
    assert got["decode_step_mfu_pct.block"] == pytest.approx(
        100.0 * flops / tiny.PEAK["flops_per_s"] / 4e-3)


@pytest.mark.parametrize("lens", [[4], [8, 900, 4096], [2000] * 64],
                         ids=["one-block", "mixed", "the-cells"])
def test_no_layers_count_passes_the_whole_steps_at_made_up_lengths(lens):
    """At the chip's configuration: what every layer's block read and the
    experts that were hit count, added up, stays under the whole step's
    count, in operations and in bytes, so no share of a step that takes
    at least its own roofline's time can pass 100."""
    cfg = run.load_json(tiny.REPO, "benchmark", "configs",
                        "sdar-30b-a3b-chat.json")
    fam = family.resolve(cfg, "backlog", "fp8")
    sz, counts = fam.sz, fam.counts
    pairs = 8.0 * L * len(lens)
    spec = {"expert_pairs": pairs, "experts_hit": min(128.0, pairs)}
    for of in ("flops", "bytes"):
        step = getattr(counts, f"decode_step_{of}")(sz, lens, spec)
        parts = (6 * getattr(counts, f"attn_decode_{of}")(sz, lens)
                 + 6 * getattr(counts, f"moe_decode_{of}")(
                     sz, spec["expert_pairs"], spec["experts_hit"]))
        assert 0 < parts < step
    # a prompt counts its whole blocks, and a block's rows see each other
    assert counts.attn_prefill_flops(sz, 7) == counts.attn_prefill_flops(
        sz, 4) == 2.0 * 32 * 2 * 128 * 16


@pytest.mark.parametrize("mode", [sdar.CONTROL, "causal_block",
                                  "dirty_close", "no_topk_norm",
                                  "no_qk_norm"])
def test_the_control_and_each_planted_fault_read_not_correct(traced, mode):
    """Over 12 seeded sequences of 48 tokens: the token that the control's
    precision, or the reference with one piece of the mathematics left
    out, puts first at the step that commits it lies further below the
    reference's best than the cell's limit allows."""
    _, _, seen, _ = traced
    state = seen["state"]
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 95, 16).astype(np.int32),
                rng.integers(0, 95, 48).astype(np.int32)) for _ in range(12)]
    numbers = check.served_gaps(seen["ref"], state["sz"], seen["seed"],
                                samples, seen["length"], mode)
    assert numbers["control_gap"] > 1.5 * sdar.LIMITS["served_gap"], mode
    ok, _ = check.verdict({"served_gap": numbers["control_gap"],
                           "unanswered": 0}, sdar.LIMITS)
    assert not ok


def test_the_chips_configuration_is_the_catalogs_row_cut_in_depth_only():
    """Every key of the published config is in the file under the same
    name with the same value, but the depth, whose published value it
    states; the sizes and the parameter count follow from the published
    config (4,361 M at 6 layers with the embedding and the untied head, all
    128 experts held; a pool of 3.22 GB). The catalog row is compared
    where ``ARCHITECTURE_CATALOG`` names a JSON-lines file of them."""
    cfg = run.load_json(tiny.REPO, "benchmark", "configs",
                        "sdar-30b-a3b-chat.json")
    entry = run.find(run.load_json(tiny.REPO, "BENCHMARK.json")["configs"],
                     "sdar-30b-a3b-chat", "config")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    published = dict(cfg, **cfg["published"])
    row = None
    try:
        with open(os.environ.get("ARCHITECTURE_CATALOG", "")) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
    except OSError:
        pass                      # no catalog named
    if row is not None:
        assert cfg["source"] == entry["source"] == row["source_url"]
        assert {k: published[k] for k in row["config"]} == row["config"]
    fam = family.resolve(cfg, "backlog", "fp8")
    sz = fam.sz
    assert (sz["d"], sz["heads"], sz["hk"], sz["dk"], sz["v"]) == (
        2048, 32, 4, 128, 151936)
    assert (sz["experts"], sz["held"], sz["top_k"], sz["ef"]) == (
        128, (0, 128), 8, 768)
    assert (sz["block_len"], sz["denoise_steps"], sz["layers"]) == (4, 2, 6)
    model = cfg["program"]["model"]
    assert model["router"] == "softmax" and model["qk_norm"] is True
    assert model["attention"] == ["full"] * 6
    import jax

    shapes = jax.eval_shape(lambda: fam.reference.init_params(
        family.seed_key(0), sz))
    total = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert round(total / 1e6) == 4361
    engine = cfg["program"]["engine"]
    kv = engine["slots"] * engine["cache_len"] * 6 * 2 * sz["kvd"] * 2
    assert round(kv / 1e9, 2) == 3.22
