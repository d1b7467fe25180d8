"""The ``deepseek_v3`` family's tiny cell through the real harness on the
CPU (``perfbench_kanana.py``): one traced run, sound, reporting every
metric the chip's cell reports; its counts checked by hand (a latent row
read ONCE, at its own width; experts as hit); the control and the five
planted faults reading not ``correct``; the near-tie rule by hand.

Readings at this size, on the CPU (matrices N(0, 0.16): at a width of 32
products of 0.02s are nought to every comparison; logits of about 3). The
program's served tokens of the one run: every one the reference's own best
(0.0). Over 12 seeded sequences of 48 tokens the float8 control reads 0.49,
``no_rope_key`` 1.82, ``no_latent_norm`` 1.17, ``scale_128`` 0.72,
``no_shared`` 1.08, ``unscaled_route`` 0.58 (int8 0.05: at a width of 32 it
is no coarser than bfloat16). The cell's limit is 0.05, under every one of
them by a factor of 9.7 at least; as in ``test_perfbench_mimo.py`` a sound
run is held to the bulk of its tokens, since one token at a near-tie of two
router scores may read an expert's whole part.
"""

import numpy as np
import pytest

from benchmark import check, family, run, trace_reduce
from benchmark.readers import decode_blocks
from benchmark.readers.decode_share import micro_steps
from benchmark.readers.routed import counters

import perfbench_kanana as kanana
import perfbench_tiny as tiny

MS = 1_000_000
LAYERS, ROUTED = kanana.LAYERS, kanana.LAYERS - 1
#: the metrics ISSUE 33's point 7 gives the chip's cell, all of them
REPORTED = kanana.COUNTED + (
    "decode_step_ms.latent", "pool_latent_bytes_pct",
    "decode_block_len_mean", "tpot_ms_p50", "compiles_in_window.backlog",
    "device_idle_pct.backlog", "admit_ms_p50", "first_token_wait_ms_p50",
    "pool_write_ms_p50", "pool_write_dispatches_mean", "expert_pairs_mean",
    "experts_hit_mean")
#: and the four that only a device gives (its memory, gaps between its
#: programs, idle gaps under a region): listed for the cell, silent here
DEVICE_ONLY = ("block_gap_ms_p50", "hbm_peak_pct.backlog", "idle_admit_pct",
               "idle_pool_write_pct")


def toy_trace(*_):
    """One decode program of 8 ms that ran two micro-steps of the two
    layers (a latent read 1 ms, each of a routed layer's three grouped
    products 0.1 ms) and one prefill of 4 ms (a forward kernel 1 ms a
    layer)."""
    ops, t = [], 0

    def op(name, ns):
        nonlocal t
        ops.append((t, t + ns, f"{name}.{len(ops)}"))
        t += ns

    for _step in range(2):
        for _layer in range(LAYERS):
            op("attn_mla_decode", MS)
        for _layer in range(ROUTED):
            for name in ("moe_gate", "moe_up", "moe_down"):
                op(name, MS // 10)
    decode_end = 8 * MS
    t = decode_end
    for _layer in range(LAYERS):
        op("attn_mla_prefill", MS)
    return trace_reduce.Trace(
        {0: ops},
        {0: [(0, decode_end, "jit_decode_block(1)"),
             (decode_end, decode_end + 4 * MS, "jit__prefill(2)")]}, [])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE run of the tiny cell, traced (the trace a toy): the result,
    the state the readers saw and the samples the comparison took."""
    root = str(tmp_path_factory.mktemp("perfbench_kanana"))
    manifest = kanana.build(root)
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
        result, division = tiny.run_cell(root, manifest, kanana.CELL,
                                         seed=4, trace=True)
    return result, division, seen, root


def token_gaps(seen) -> np.ndarray:
    """Every compared token's gap below the reference's best."""
    state = seen["state"]
    fn = seen["ref"].served_gaps_fn(state["sz"],
                                    family.seed_key(seen["seed"]), "f32")
    out = []
    for prompt, served in seen["samples"]:
        seq = np.zeros((1, seen["length"]), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + len(served)] = served
        out.append(np.asarray(fn(seq, len(prompt), len(served))[0])[
            :len(served)])
    return np.concatenate(out)


def test_the_tiny_cell_is_correct_and_reports_the_cells_metrics(traced):
    result, division, seen, root = traced
    limit = kanana.LIMITS["served_gap"]
    gaps = token_gaps(seen)
    assert gaps.max() == pytest.approx(
        result["compared"]["served_gap"]["value"], abs=1e-6)
    assert (gaps > limit).mean() <= 0.02, np.sort(gaps)[-5:]
    assert np.quantile(gaps, 0.9) <= 0.01
    assert result["correct"] is bool(gaps.max() <= limit), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["details"]["tokens_compared"] > 40
    assert "weights_s" in division["setup_division"]
    # the family's three modules came from the tree they were added to
    fam = family.resolve(
        run.cell_files(run.load_json(root, "BENCHMARK.json"), kanana.CELL,
                       root)["config"], "backlog", kanana.CONTROL, root)
    for module in (fam.reference, fam.counts, fam.adapter):
        assert module.__file__.startswith(root), module.__file__
    assert fam.builder == "hybrid_lm" and fam.sz["ad"] == 136
    got = result["metrics"]
    assert set(REPORTED) <= set(got), set(REPORTED) - set(got)
    listed = {m["name"] for m in run.load_json(root, "BENCHMARK.json")[
        "per_layer"] if kanana.CELL in m.get("workloads", ())}
    assert set(REPORTED + DEVICE_ONLY) == listed
    assert all(got[name]["value"] is not None for name in REPORTED)
    # the accepted metrics that look for other kernels stay silent
    assert not {"decode_step_ms", "attn_decode_roofline",
                "decode_step_ms.mixed", "attn_full_decode_roofline",
                "pool_ring_bytes_pct"} & set(got)
    assert got["pool_write_dispatches_mean"]["value"] == 1.0
    # admissions write latent rows and nothing else
    assert got["pool_latent_bytes_pct"]["value"] == 100.0
    assert got["decode_step_ms.latent"]["value"] == pytest.approx(4.0)
    for name in kanana.COUNTED:
        assert 0 < got[name]["value"]


def test_the_latent_reads_counts_by_hand(traced):
    result, _, seen, _ = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state = seen["state"]
    steps = list(micro_steps(decode_blocks(state)))
    live = sum(len(s) for s in steps) / len(steps)
    rows = sum(sum(s) for s in steps) / len(steps)
    # a live row ONCE, its own 136 numbers (the 256 lanes the pool holds
    # them in are no part of the need), bfloat16, for the scores and the
    # weighted sum both; 8 absorbed queries of 136 in and 8 latent
    # outputs of 128 out a slot
    nbytes = rows * 136 * 2 + live * 8 * (136 + 128) * 2
    flops = rows * 8 * 2 * (136 + 128)
    assert nbytes / tiny.PEAK["hbm_bytes_per_s"] > \
        flops / tiny.PEAK["flops_per_s"]            # bytes bind it
    assert got["attn_mla_decode_roofline"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 1e-3)
    # read as K and again as V it would count nearly twice that
    twice = rows * (136 + 128) * 2
    assert twice > 1.9 * rows * 136 * 2
    # a prompt of p tokens, expanded: p (p + 1) / 2 pairs of 8 heads,
    # keys 24 wide and values 16; Q, K, V read and the output written
    lens = [len(r["prompt"]) for r in state["requests"]
            if state["t_open"] < r.get("first_token", 0.0)
            <= state["t_close"]]
    assert lens
    flops = np.mean([p * (p + 1) / 2 for p in lens]) * 8 * 2 * (24 + 16)
    nbytes = np.mean(lens) * (2 * 192 + 2 * 128) * 2
    least = max(flops / tiny.PEAK["flops_per_s"],
                nbytes / tiny.PEAK["hbm_bytes_per_s"])
    assert got["attn_mla_prefill_roofline"] == pytest.approx(
        100.0 * least / 1e-3)


def test_the_whole_steps_and_the_expert_layers_counts_by_hand(traced):
    result, _, seen, _ = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state, sz = seen["state"], seen["state"]["sz"]
    routed = counters(state)
    assert got["expert_pairs_mean"] == pytest.approx(routed["expert_pairs"])
    assert got["experts_hit_mean"] == pytest.approx(routed["experts_hit"])
    # the counters are per routed layer: 4 slots x 2 of 8 experts, 4 held
    assert 0.5 < routed["expert_pairs"] < 8.0
    assert 0.5 < routed["experts_hit"] <= min(4.0, routed["expert_pairs"])
    expert = 3 * 32 * 16
    nbytes = (routed["experts_hit"] * expert * 2
              + 2 * routed["expert_pairs"] * 32 * 2)
    assert got["moe_decode_roofline"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 0.3e-3)
    steps = list(micro_steps(decode_blocks(state)))
    live = sum(len(s) for s in steps) / len(steps)
    rows = sum(sum(s) for s in steps) / len(steps)
    # what every token shares: q, the joint down-projection, W_kvb (the
    # absorbed products go through it once a token) and the output of
    # every layer; the dense FFN; router and shared expert of the routed
    # layers; the head
    attn = 32 * 192 + 32 * 136 + 128 * 256 + 128 * 32
    shared = (LAYERS * attn + 3 * 32 * 64
              + ROUTED * (32 * 8 + 3 * 32 * 32) + 32 * 96)
    small = LAYERS * (2 * 32 + 128) + 32 + ROUTED * 8
    latent = LAYERS * (rows + live) * 136 * 2     # read once, one written
    nbytes = ((shared + small) * 2
              + ROUTED * routed["experts_hit"] * expert * 2 + latent)
    assert got["decode_hbm_roofline.latent"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 4e-3)
    flops = (2 * live * shared + ROUTED * 2 * routed["expert_pairs"] * expert
             + LAYERS * rows * 8 * 2 * (136 + 128))
    assert got["decode_step_mfu_pct.latent"] == pytest.approx(
        100.0 * flops / tiny.PEAK["flops_per_s"] / 4e-3)
    assert sz["param_bytes"] == sz["kv_bytes"] == 2 and "row" not in sz


@pytest.mark.parametrize("mode", [kanana.CONTROL, "no_rope_key",
                                  "no_latent_norm", "scale_128", "no_shared",
                                  "unscaled_route"])
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
    assert numbers["control_gap"] > 1.5 * kanana.LIMITS["served_gap"], mode
    ok, _ = check.verdict({"served_gap": numbers["control_gap"],
                           "unanswered": 0}, kanana.LIMITS)
    assert not ok


@pytest.mark.parametrize("which", ["the cell's file", "the tiny preset"])
def test_the_stated_row_width_is_the_width_the_pool_holds(which):
    """``program.stored.latent_width`` is written in a configuration's
    file and computed by the program (``ops/kv_cache.latent_width``):
    nothing else holds the two equal. The counts use neither: a row is
    counted at its own width."""
    from mmlspark_tpu.ops.kv_cache import latent_width

    cfg = kanana.CONFIG if which == "the tiny preset" else run.load_json(
        tiny.REPO, "benchmark", "configs", "kanana-2-30b-a3b.json")
    own = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    assert cfg["program"]["stored"]["latent_width"] == latent_width(own)
    assert family.load(tiny.REPO, "references", "deepseek_v3").sizes(
        cfg)["ad"] == own


def test_a_near_tie_is_any_held_experts_not_the_boundary_pairs_alone():
    """Top 2 of 8, experts 0-3 held, width 0.006. The reference leaves a
    position out where a HELD expert lies within the width of the other
    side of the choice: chosen and that close to the first one left out,
    or left out and that close to the last one chosen. Row 0: the held
    expert is FIRST of the two chosen, 0.004 over the one left out, and
    the boundary pair (experts 5 and 6, 0.002 apart) holds none: a rule
    that looks at the pair alone is blind to it at any width. Row 1: the
    same scores, the held expert 0.02 clear. Row 2: a held expert left
    out 0.003 under the last one chosen. Row 3: the pair within 0.002 and
    no held expert near: nothing that this chip holds can change."""
    import jax.numpy as jnp

    ref = family.load(tiny.REPO, "references", "deepseek_v3")
    sz = dict(ref.sizes(kanana.CONFIG), tie=0.006)
    assert sz["held"] == (0, 4) and sz["top_k"] == 2 and ref.TIE == 0.006
    scores = np.full((4, 8), 0.2, np.float32)
    scores[0, [0, 5, 6]] = 0.506, 0.504, 0.502
    scores[1, [0, 5, 6]] = 0.522, 0.504, 0.502
    scores[2, [5, 6, 1]] = 0.540, 0.504, 0.501
    scores[3, [5, 6, 7]] = 0.504, 0.502, 0.600
    # sigmoid(h W) = scores with W the identity and h their logits
    h = jnp.log(scores / (1 - scores))[None]
    p = {"router_w": jnp.eye(8, dtype=jnp.float32),
         "select_bias": jnp.zeros(8, jnp.float32)}
    experts, weights, near = ref.route(h, p, sz, "f32")
    assert np.asarray(near[0]).tolist() == [True, False, True, False]
    assert sorted(np.asarray(experts[0, 0]).tolist()) == [0, 5]
    np.testing.assert_allclose(np.asarray(weights[0]).sum(-1), 2.448,
                               rtol=1e-6)
