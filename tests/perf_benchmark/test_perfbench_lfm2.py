"""The ``lfm2_moe`` family's tiny cell through the real harness on the CPU
(``perfbench_lfm2.py``): one traced run, sound, reporting every metric the
chip's cell reports; its counts checked by hand (a convolution layer's
state read and written for the live slots alone, whatever their lengths;
experts as hit) and held under the whole step's at made-up lengths; the
control and the planted faults reading not ``correct``; the chip's
configuration file against the catalog's row and the issue's arithmetic.

Readings at this size, on the CPU (matrices N(0, 0.12): at a width of 64
products of 0.02s are nought to every comparison; logits of about 3). The
program's served tokens, three seeds of 62 to 74 tokens: 0.0, 0.0, 0.033.
Over 12 seeded sequences of 48 tokens the float8 control reads 1.47,
``state_zero`` 5.07, ``taps_reversed`` 5.88, ``no_b_gate`` 5.90,
``no_qk_norm`` 0.75, ``no_bias`` 0.58 (int8 0.53, bfloat16 0.10). The
cell's limit is 0.12: 3.6 times the largest sound reading, a fifth of the
smallest fault's.
"""

import json

import numpy as np
import pytest

from benchmark import check, family, run, trace_reduce
from benchmark.readers import decode_blocks
from benchmark.readers.decode_share import micro_steps
from benchmark.readers.routed import counters

import perfbench_lfm2 as lfm2
import perfbench_tiny as tiny

MS = 1_000_000
CONV, FULL, ROUTED = 3, 1, 2
#: the metrics ISSUE 35's point 7 gives the chip's cell, all of them
REPORTED = lfm2.COUNTED + (
    "decode_step_ms.state", "pool_state_bytes_pct", "decode_block_len_mean",
    "tpot_ms_p50", "compiles_in_window.backlog", "device_idle_pct.backlog",
    "admit_ms_p50", "first_token_wait_ms_p50", "pool_write_ms_p50",
    "pool_write_dispatches_mean", "expert_pairs_mean", "experts_hit_mean")
#: and the four that only a device gives: listed for the cell, silent here
DEVICE_ONLY = ("block_gap_ms_p50", "hbm_peak_pct.backlog", "idle_admit_pct",
               "idle_pool_write_pct")


def toy_trace(*_):
    """One decode program of 8 ms that ran two micro-steps of the four
    layers (a state step 0.05 ms, the K/V read 1 ms, each of a routed
    layer's three grouped products 0.1 ms) and one prefill of 4 ms (the
    forward kernel 1 ms)."""
    ops, t = [], 0

    def op(name, ns):
        nonlocal t
        ops.append((t, t + ns, f"{name}.{len(ops)}"))
        t += ns

    for _step in range(2):
        for kind in lfm2.KINDS:
            op("conv_decode" if kind == "conv" else "attn_full_decode",
               MS // 20 if kind == "conv" else MS)
        for _layer in range(ROUTED):
            for name in ("moe_gate", "moe_up", "moe_down"):
                op(name, MS // 10)
    decode_end = 8 * MS
    t = decode_end
    op("attn_full_prefill", MS)
    return trace_reduce.Trace(
        {0: ops},
        {0: [(0, decode_end, "jit_decode_block(1)"),
             (decode_end, decode_end + 4 * MS, "jit__prefill(2)")]}, [])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE run of the tiny cell, traced (the trace a toy): the result,
    the state the readers saw and the samples the comparison took."""
    root = str(tmp_path_factory.mktemp("perfbench_lfm2"))
    manifest = lfm2.build(root)
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
        result, division = tiny.run_cell(root, manifest, lfm2.CELL,
                                         seed=4, trace=True)
    return result, division, seen, root


def test_the_tiny_cell_is_correct_and_reports_the_cells_metrics(traced):
    result, division, seen, root = traced
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["served_gap"]["value"] <= 0.06
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["details"]["tokens_compared"] > 40
    assert "weights_s" in division["setup_division"]
    # the family's three modules came from the tree they were added to
    fam = family.resolve(
        run.cell_files(run.load_json(root, "BENCHMARK.json"), lfm2.CELL,
                       root)["config"], "backlog", lfm2.CONTROL, root)
    for module in (fam.reference, fam.counts, fam.adapter):
        assert module.__file__.startswith(root), module.__file__
    assert fam.builder == "hybrid_lm" and fam.sz["kinds"] == lfm2.KINDS
    assert fam.sz["held"] == (0, 8) and fam.sz["K"] == 3
    got = result["metrics"]
    assert set(REPORTED) <= set(got), set(REPORTED) - set(got)
    listed = {m["name"] for m in run.load_json(root, "BENCHMARK.json")[
        "per_layer"] if lfm2.CELL in m.get("workloads", ())}
    assert set(REPORTED + DEVICE_ONLY) == listed
    # the accepted metrics that look for other kernels stay silent
    assert not {"decode_step_ms", "attn_decode_roofline",
                "decode_step_ms.mixed", "decode_step_ms.latent",
                "attn_swa_decode_roofline", "attn_mla_decode_roofline",
                "attn_full_decode_roofline", "attn_full_prefill_roofline",
                "pool_ring_bytes_pct", "pool_latent_bytes_pct"} & set(got)
    assert got["pool_write_dispatches_mean"]["value"] == 1.0
    assert got["decode_step_ms.state"]["value"] == pytest.approx(4.0)
    for name in lfm2.COUNTED:
        assert 0 < got[name]["value"] <= 100.0, (name, got[name])
    # an admission writes 3 layers' two rows of 64 and its prompt's K/V
    events = [e["attrs"] for e in seen["state"]["events"]
              if e["name"] == "serve.pool_write"]
    assert events and all(a["bytes_state"] == CONV * 2 * 64 * 2
                          and a["bytes"] == a["bytes_state"] + a["bytes_full"]
                          for a in events)
    assert 0 < got["pool_state_bytes_pct"]["value"] < 100.0


def test_the_state_steps_and_the_whole_steps_counts_by_hand(traced):
    result, _, seen, _ = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state, sz = seen["state"], seen["state"]["sz"]
    steps = list(micro_steps(decode_blocks(state)))
    live = sum(len(s) for s in steps) / len(steps)
    rows = sum(sum(s) for s in steps) / len(steps)
    # the 3 x 64 taps in float32 are all that has to cross HBM (a slot's
    # rows come from one product and go to the next, its state is carried
    # on the chip); two gates and three multiply-adds a channel a live
    # slot: nothing of a slot's LENGTH
    nbytes = 3 * 64 * 4
    flops = live * 64 * (2 + 2 * 3)
    least = max(nbytes / tiny.PEAK["hbm_bytes_per_s"],
                flops / tiny.PEAK["flops_per_s"])
    assert got["conv_decode_roofline"] == pytest.approx(
        100.0 * least / 0.05e-3)
    # the attention layer: K and V of 2 heads x 8, once a live row
    kv = rows * 2 * 16 * 2 + live * 2 * 64 * 2
    assert got["attn_full_decode_roofline.state"] == pytest.approx(
        100.0 * kv / tiny.PEAK["hbm_bytes_per_s"] / 1e-3)
    routed = counters(state)
    assert got["expert_pairs_mean"] == pytest.approx(routed["expert_pairs"])
    # all 8 experts held: every live token's 2 pairs land here
    assert routed["expert_pairs"] == pytest.approx(2 * live)
    assert 1.0 <= routed["experts_hit"] <= min(8.0, routed["expert_pairs"])
    expert = 3 * 64 * 16
    # what every token shares: the operators' matrices, the dense FFNs,
    # the routers and the head; then gains, head norms, taps and biases
    shared = (CONV * (64 * 192 + 64 * 64) + FULL * (2 * 64 * 64 + 2 * 64 * 16)
              + 2 * 3 * 64 * 96 + ROUTED * 64 * 8 + 64 * 96)
    small = 2 * 4 * 64 + 64 + FULL * 2 * 8 + CONV * 3 * 64 + ROUTED * 8
    cache = (FULL * (rows + live) * 2 * 16 * 2
             + CONV * live * 2 * 2 * 64 * 2)
    nbytes = ((shared + small) * 2
              + ROUTED * routed["experts_hit"] * expert * 2 + cache)
    assert got["decode_hbm_roofline.state"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 4e-3)
    flops = (2 * live * shared + ROUTED * 2 * routed["expert_pairs"] * expert
             + FULL * rows * 8 * 2 * 16 + CONV * live * 64 * 8)
    assert got["decode_step_mfu_pct.state"] == pytest.approx(
        100.0 * flops / tiny.PEAK["flops_per_s"] / 4e-3)


@pytest.mark.parametrize("lens", [[1], [5, 900, 4096], [1500] * 128],
                         ids=["one-token", "mixed", "the-cells"])
def test_no_layers_count_passes_the_whole_steps_at_made_up_lengths(lens):
    """At the chip's configuration: what the kernels of all layers and the
    experts that were hit count, added up, stays under the whole step's
    count, in operations and in bytes, so no share of a step that takes at
    least its own roofline's time can pass 100. A dead slot is no length
    in the list and counts nothing; a convolution layer counts the same
    whatever the lengths."""
    cfg = run.load_json(tiny.REPO, "benchmark", "configs", "lfm2-8b-a1b.json")
    fam = family.resolve(cfg, "backlog", "fp8")
    sz, counts = fam.sz, fam.counts
    spec = {"expert_pairs": 4.0 * len(lens),
            "experts_hit": min(32.0, 4.0 * len(lens))}
    for of in ("flops", "bytes"):
        step = getattr(counts, f"decode_step_{of}")(sz, lens, spec)
        kernel = getattr(counts, f"attn_decode_{of}")
        parts = (9 * kernel(sz, lens, {"kind": "conv"})
                 + 3 * kernel(sz, lens, {"kind": "full"})
                 + 10 * getattr(counts, f"moe_decode_{of}")(
                     sz, spec["expert_pairs"], spec["experts_hit"]))
        assert 0 < parts < step
    conv = {"kind": "conv"}
    assert counts.attn_decode_flops(sz, lens, conv) == \
        counts.attn_decode_flops(sz, [1] * len(lens), conv)
    assert counts.attn_decode_flops(sz, lens + [7], conv) > \
        counts.attn_decode_flops(sz, lens, conv)
    assert counts.attn_decode_bytes(sz, lens, conv) == 3 * 2048 * 4


@pytest.mark.parametrize("mode", [lfm2.CONTROL, "state_zero",
                                  "taps_reversed", "no_b_gate",
                                  "no_qk_norm", "no_bias"])
def test_the_control_and_each_planted_fault_read_not_correct(traced, mode):
    """Over 12 seeded sequences of 48 tokens: the token that the control's
    precision, or the reference with one piece of the mathematics left
    out, puts first lies further below the reference's best than the
    cell's limit allows."""
    _, _, seen, _ = traced
    state = seen["state"]
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 96, 16).astype(np.int32),
                rng.integers(0, 96, 48).astype(np.int32)) for _ in range(12)]
    numbers = check.served_gaps(seen["ref"], state["sz"], seen["seed"],
                                samples, seen["length"], mode)
    assert numbers["control_gap"] > 1.5 * lfm2.LIMITS["served_gap"], mode
    ok, _ = check.verdict({"served_gap": numbers["control_gap"],
                           "unanswered": 0}, lfm2.LIMITS)
    assert not ok


def test_the_chips_configuration_is_the_catalogs_row_cut_in_depth_only():
    """Every key of the published config is in the file under the same
    name with the same value, but the two it lists as reduced, whose
    published values it states; the sizes and the parameter count are the
    issue's arithmetic (4,063 M at 12 layers, 9 conv : 3 attention, all 32
    experts held)."""
    cfg = run.load_json(tiny.REPO, "benchmark", "configs", "lfm2-8b-a1b.json")
    entry = run.find(run.load_json(tiny.REPO, "BENCHMARK.json")["configs"],
                     "lfm2-8b-a1b", "config")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "layer_types"]
    published = dict(cfg, **cfg["published"])
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
    except OSError:
        pass                      # no catalog beside this checkout
    if row is not None:
        assert cfg["source"] == entry["source"] == row["source_url"]
        assert {k: published[k] for k in row["config"]} == row["config"]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:12]
    fam = family.resolve(cfg, "backlog", "fp8")
    sz = fam.sz
    assert (sz["d"], sz["heads"], sz["hk"], sz["dk"], sz["K"]) == (
        2048, 32, 8, 64, 3)
    assert sz["kinds"].count("conv") == 9 and sz["kinds"].count("full") == 3
    assert sz["ffns"] == ("dense",) * 2 + ("routed",) * 10
    assert (sz["experts"], sz["held"], sz["top_k"], sz["ef"], sz["f"]) == (
        32, (0, 32), 4, 1792, 7168)
    model = cfg["program"]["model"]
    assert model["attention"] == list(sz["kinds"])
    assert model["ffn"] == list(sz["ffns"])
    assert model["held_experts"] == [0, 32] and model["qk_norm"] is True
    import jax

    shapes = jax.eval_shape(lambda: fam.reference.init_params(
        family.seed_key(0), sz))
    total = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert round(total / 1e6) == 4063
    engine = cfg["program"]["engine"]
    kv = engine["slots"] * engine["cache_len"] * 3 * 2 * sz["kvd"] * 2
    state = engine["slots"] * 9 * 2 * sz["d"] * 2
    assert round(kv / 1e9, 2) == 3.22 and round(state / 1e6, 1) == 9.4
