"""The ``mimo_v2_flash`` family's tiny cell through the real harness on the
CPU (``perfbench_mimo.py``): one traced run, sound; its counts checked by
hand for both attention kinds and for the expert layer; the control and
the four planted faults reading not ``correct``.

Readings at this size, on the CPU (3 seeds of weights, logits of about 1.5).
The program's served tokens, 240 a seed: widest gaps of 0.0003, 0.005 and
0.10, the last ONE token where program (bfloat16 products) and reference
(float32) chose another second expert: a near-tie of two router scores,
which moves a token by an expert's whole part. Over 24 seeded sequences of
48 tokens the float8 control reads 0.31 to 0.49 (int8 0.09 to 0.35: at a
width of 32 it is hardly coarser than bfloat16), a fault of the attention
0.48 to 1.88, the bias left out of the choice 0.21 to 0.35. The
cell's limit is 0.05: under every control and fault, over the bulk of the
sound tokens, and UNDER what one such token reads, since at this size (top 2
of 8 experts, half of them held) that is what the bias left out reads too.
So the sound run is held to the bulk: at most one served token in fifty over
the limit, and ``correct`` exactly when none is. The chip's cell has room
for a limit over such a token (PERF.md section 2).
"""

import numpy as np
import pytest

from benchmark import check, family, run, trace_reduce
from benchmark.readers import decode_blocks
from benchmark.readers.decode_share import micro_steps
from benchmark.readers.routed import counters

import perfbench_mimo as mimo
import perfbench_tiny as tiny

MS = 1_000_000


def toy_trace(*_):
    """One decode program of 8 ms that ran two micro-steps of the four
    layers (a full kernel 1 ms, a window kernel 0.5 ms, each of a routed
    layer's three grouped products 0.1 ms) and one prefill of 4 ms (a
    full forward kernel 1 ms, a window one 0.25 ms)."""
    ops, t = [], 0

    def op(name, ns):
        nonlocal t
        ops.append((t, t + ns, f"{name}.{len(ops)}"))
        t += ns

    for _step in range(2):
        for kind, ns in (("full", MS), ("swa", MS // 2), ("swa", MS // 2),
                         ("full", MS)):
            op(f"attn_{kind}_decode", ns)
        for _layer in range(3):
            for name in ("moe_gate", "moe_up", "moe_down"):
                op(name, MS // 10)
    decode_end = 8 * MS
    t = decode_end
    for kind, ns in (("full", MS), ("swa", MS // 4), ("swa", MS // 4),
                     ("full", MS)):
        op(f"attn_{kind}_prefill", ns)
    return trace_reduce.Trace(
        {0: ops},
        {0: [(0, decode_end, "jit_decode_block(1)"),
             (decode_end, decode_end + 4 * MS, "jit__prefill(2)")]}, [])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE run of the tiny cell, traced (the trace a toy): the result,
    the state the readers saw and the samples the comparison took."""
    root = str(tmp_path_factory.mktemp("perfbench_mimo"))
    manifest = mimo.build(root)
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
        result, division = tiny.run_cell(root, manifest, mimo.CELL, seed=4,
                                         trace=True)
    return result, division, seen


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
    result, division, seen = traced
    limit = mimo.LIMITS["served_gap"]
    gaps = token_gaps(seen)
    assert gaps.max() == pytest.approx(
        result["compared"]["served_gap"]["value"], abs=1e-6)
    # the bulk of the tokens is the reference's own choice; one token at
    # a near-tie of two router scores may not be (the module's docstring)
    assert (gaps > limit).mean() <= 0.02, np.sort(gaps)[-5:]
    assert np.quantile(gaps, 0.9) <= 0.01
    assert result["correct"] is bool(gaps.max() <= limit), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["served_gap"]["limit"] == limit == 0.05
    assert result["details"]["tokens_compared"] > 40
    assert "weights_s" in division["setup_division"]
    got = set(result["metrics"])
    assert set(mimo.COUNTED) <= got
    assert {"decode_step_ms.mixed", "expert_pairs_mean", "experts_hit_mean",
            "pool_ring_bytes_pct", "pool_write_dispatches_mean"} <= got
    # the accepted metrics that count micro-steps as calls of ``attn.``
    # find no such kernel here and stay silent
    assert not {"decode_step_ms", "attn_decode_roofline"} & got
    assert result["metrics"]["pool_write_dispatches_mean"]["value"] == 1.0
    assert result["metrics"]["decode_step_ms.mixed"]["value"] == \
        pytest.approx(4.0)
    for name in mimo.COUNTED:
        assert 0 < result["metrics"][name]["value"]


def test_the_attention_counts_of_both_kinds_by_hand(traced):
    result, _, seen = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state = seen["state"]
    steps = list(micro_steps(decode_blocks(state)))
    live = sum(len(s) for s in steps) / len(steps)
    rows = {"full": sum(sum(s) for s in steps) / len(steps),
            "swa": sum(sum(min(n, 8) for n in s) for s in steps) / len(steps)}
    assert rows["full"] > 2 * rows["swa"]   # the ring caps what is read
    # bytes bound both: K rows 24 wide and V rows 16 wide in bfloat16 of
    # 1 (full) or 2 (window) KV heads, 4 queries of 24 in, 4 x 16 out
    for kind, hk, ms in (("full", 1, 1.0), ("swa", 2, 0.5)):
        nbytes = rows[kind] * hk * (24 + 16) * 2 + live * (96 + 64) * 2
        flops = rows[kind] * 4 * 2 * (24 + 16)
        assert nbytes / tiny.PEAK["hbm_bytes_per_s"] > \
            flops / tiny.PEAK["flops_per_s"]
        assert got[f"attn_{kind}_decode_roofline"] == pytest.approx(
            100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / (ms * 1e-3))
    # a prompt of p tokens: p (p + 1) / 2 pairs in a full layer, a window
    # of 8 in a window layer; compute binds the full kind at this peak
    lens = [len(r["prompt"]) for r in state["requests"]
            if state["t_open"] < r.get("first_token", 0.0)
            <= state["t_close"]]
    assert lens
    pairs = {"full": np.mean([p * (p + 1) / 2 for p in lens]),
             "swa": np.mean([36 + (p - 8) * 8 for p in lens])}
    for kind, hk, ms in (("full", 1, 1.0), ("swa", 2, 0.25)):
        flops = pairs[kind] * 4 * 2 * (24 + 16)
        nbytes = np.mean(lens) * (96 + 64 + hk * 40) * 2
        least = max(flops / tiny.PEAK["flops_per_s"],
                    nbytes / tiny.PEAK["hbm_bytes_per_s"])
        assert got[f"attn_{kind}_prefill_roofline"] == pytest.approx(
            100.0 * least / (ms * 1e-3))


def test_the_expert_layers_counts_by_hand(traced):
    result, _, seen = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state, sz = seen["state"], seen["state"]["sz"]
    routed = counters(state)
    assert got["expert_pairs_mean"] == pytest.approx(routed["expert_pairs"])
    assert got["experts_hit_mean"] == pytest.approx(routed["experts_hit"])
    # 4 slots x 2 of 8 experts, 4 held: at most 8 pairs a micro-step
    assert 0.5 < routed["expert_pairs"] < 8.0
    assert 0.5 < routed["experts_hit"] <= min(4.0, routed["expert_pairs"])
    # one layer: the experts HIT, three 32 x 16 matrices each in
    # bfloat16, read once, and every pair's 32 numbers in and out
    expert = 3 * 32 * 16
    nbytes = (routed["experts_hit"] * expert * 2
              + 2 * routed["expert_pairs"] * 32 * 2)
    assert got["moe_decode_roofline"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 0.3e-3)
    # the whole micro-step: what every token shares once, the experts
    # that were hit (never all that are held), rows read and written
    steps = list(micro_steps(decode_blocks(state)))
    live = sum(len(s) for s in steps) / len(steps)
    full = sum(sum(s) for s in steps) / len(steps)
    swa = sum(sum(min(n, 8) for n in s) for s in steps) / len(steps)
    attn = {"full": 32 * 96 + 32 * 24 + 32 * 16 + 64 * 32,
            "swa": 32 * 96 + 32 * 48 + 32 * 32 + 64 * 32}
    shared = (2 * attn["full"] + 2 * attn["swa"] + 3 * 32 * 64
              + 3 * 32 * 8 + 32 * 96)
    small = 2 * 4 * 32 + 32 + 2 * 4 + 3 * 8
    kv = 2 * (full + live) * 1 * 40 * 2 + 2 * (swa + live) * 2 * 40 * 2
    nbytes = ((shared + small) * 2 + 3 * routed["experts_hit"] * expert * 2
              + kv)
    assert got["decode_hbm_roofline.mixed"] == pytest.approx(
        100.0 * nbytes / tiny.PEAK["hbm_bytes_per_s"] / 4e-3)
    counted_all = nbytes + 3 * (4 - routed["experts_hit"]) * expert * 2
    assert counted_all > nbytes
    flops = (2 * live * shared + 3 * 2 * routed["expert_pairs"] * expert
             + 2 * full * 4 * 2 * 40 + 2 * swa * 4 * 2 * 40)
    assert got["decode_step_mfu_pct.mixed"] == pytest.approx(
        100.0 * flops / tiny.PEAK["flops_per_s"] / 4e-3)
    assert sz["param_bytes"] == sz["kv_bytes"] == 2
    # a prompt's rows: 13 .. 24 of them in two full blocks, 8 in each of
    # the two rings
    assert 25.0 < got["pool_ring_bytes_pct"] < 60.0


@pytest.mark.parametrize("mode", [mimo.CONTROL, "no_sink", "full_window",
                                  "v_unscaled", "no_bias"])
def test_the_control_and_each_planted_fault_read_not_correct(traced, mode):
    """Over 24 seeded sequences of 48 tokens: the token that the control's
    precision, or the reference with one piece of the mathematics left
    out, puts first lies further below the reference's best than the
    cell's limit allows."""
    _, _, seen = traced
    state = seen["state"]
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 96, 16).astype(np.int32),
                rng.integers(0, 96, 48).astype(np.int32)) for _ in range(24)]
    numbers = check.served_gaps(seen["ref"], state["sz"], seen["seed"],
                                samples, seen["length"], mode)
    assert numbers["control_gap"] > 1.5 * mimo.LIMITS["served_gap"]
    ok, _ = check.verdict({"served_gap": numbers["control_gap"],
                           "unanswered": 0}, mimo.LIMITS)
    assert not ok
