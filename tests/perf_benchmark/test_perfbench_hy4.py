"""The ``hy_v4`` family's tiny cell through the real harness on the CPU
(``perfbench_hy4.py``): one traced run, sound, reporting every metric the
chip's cell reports; its counts checked by hand (an index key read once a
query block, a chosen row once, the whole step's traffic with the float32
head); the control and the five planted faults reading not ``correct``.

Readings at this size, on the CPU (matrices N(0, 0.1), logits of 2 to 3,
8 rows chosen of at most 48): the program's served tokens of the run read
0.09 at the widest under seed 4 (this test's) and 0.33 under seed 5, whose
control reads 0.81, ``dense_read`` 2.39, ``no_sink`` 2.95 over the same
tokens. Over 8 seeded sequences of 40 tokens the float8 control reads
2.15, ``dense_read`` 2.80, ``first_index`` 2.03, ``plain_residual`` 0.57,
``no_gate`` 2.71, ``no_sink`` 3.61. A query's choice flips where two index
scores lie within the program's rounding, and at 8 rows a flip moves the
read by an eighth: the widest gap of a sound run at this size is that, not
the bulk of its tokens, so the limit, 0.3, holds this seed's run and is no
reading of the chip's cell.
"""

import numpy as np
import pytest

from benchmark import check, run, trace_reduce
from benchmark.readers import decode_blocks, in_window
from benchmark.readers.decode_share import micro_steps

import perfbench_hy4 as hy4
import perfbench_tiny as tiny

MS = 1_000_000
LAYERS, ROUTED, OWN = hy4.LAYERS, hy4.LAYERS - 1, 2
#: the metrics of the chip's cell that a CPU run can read
REPORTED = hy4.COUNTED + (
    "decode_step_ms.sparse", "dsa_rows_read_pct", "hc_mix_kernel_pct",
    "topk_sort_pct",
    "pool_latent_bytes_pct", "decode_block_len_mean", "tpot_ms_p50",
    "compiles_in_window.backlog", "device_idle_pct.backlog",
    "admit_ms_p50", "first_token_wait_ms_p50", "pool_write_ms_p50",
    "pool_write_dispatches_mean", "expert_pairs_mean", "experts_hit_mean")
#: and the two that only a device gives: listed for the cell, silent here
DEVICE_ONLY = ("hbm_peak_pct.backlog", "idle_admit_pct")


def toy_trace(*_):
    """One decode program of 20 ms that ran two micro-steps (an index
    score 0.5 ms a full layer and its top-k's sort 0.25 ms, a read 1 ms a
    layer, each of a routed layer's three grouped products 0.1 ms, two
    stream mixes 0.2 ms a layer), and one chunk program of 4 ms (an index
    score 0.5 ms and a sort 0.25 ms a full layer, a read 0.25 ms a
    layer)."""
    ops, t = [], 0

    def op(name, ns):
        nonlocal t
        ops.append((t, t + ns, f"{name}.{len(ops)}"))
        t += ns

    for _step in range(2):
        for layer in range(LAYERS):
            if layer < OWN:
                op("index_decode", MS // 2)
                op("sort", MS // 4)
            op("attn_dsa_decode", MS)
            op("hc_mix", MS // 5)
            op("hc_mix", MS // 5)
        for _layer in range(ROUTED):
            for name in ("moe_gate", "moe_up", "moe_down"):
                op(name, MS // 10)
    decode_end = 20 * MS
    t = decode_end
    for layer in range(LAYERS):
        if layer < OWN:
            op("index_prefill", MS // 2)
            op("sort", MS // 4)
        op("attn_dsa_prefill", MS // 4)
    return trace_reduce.Trace(
        {0: ops},
        {0: [(0, decode_end, "jit_decode_block(1)"),
             (decode_end, decode_end + 4 * MS, "jit__resume(2)")]}, [])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE run of the tiny cell, traced (the trace a toy): the result,
    the state the readers saw and the samples the comparison took."""
    root = str(tmp_path_factory.mktemp("perfbench_hy4"))
    manifest = hy4.build(root)
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
        result, division = tiny.run_cell(root, manifest, hy4.CELL,
                                         seed=4, trace=True)
    return result, division, seen, root


@pytest.mark.limit(420)
def test_the_tiny_cell_is_correct_and_reports_the_cells_metrics(traced):
    result, division, seen, root = traced
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["details"]["tokens_compared"] > 20
    got = result["metrics"]
    assert set(REPORTED) <= set(got), set(REPORTED) - set(got)
    listed = {m["name"] for m in run.load_json(root, "BENCHMARK.json")[
        "per_layer"] if hy4.CELL in m.get("workloads", ())}
    assert set(REPORTED + DEVICE_ONLY) == listed
    assert all(got[name]["value"] is not None for name in REPORTED)
    # the accepted metrics that look for other kernels stay silent
    assert not {"decode_step_ms", "attn_decode_roofline",
                "decode_step_ms.latent", "attn_mla_decode_roofline",
                "pool_ring_bytes_pct"} & set(got)
    assert got["decode_step_ms.sparse"]["value"] == pytest.approx(10.0)
    # two stream mixes a layer a micro-step, 4 ms of 22.15 busy; a sort
    # after every index score, 1.5 ms
    assert got["hc_mix_kernel_pct"]["value"] == pytest.approx(
        100 * 4.0 / 22.15)
    assert got["topk_sort_pct"]["value"] == pytest.approx(100 * 1.5 / 22.15)
    for name in hy4.COUNTED:
        assert 0 < got[name]["value"]
    # latent rows beside index keys: 256 lanes a row of 5 layers, 32
    # numbers a key of 2
    assert got["pool_latent_bytes_pct"]["value"] == pytest.approx(
        100 * 5 * 256 / (5 * 256 + 2 * 32))


def test_the_rows_read_are_the_chosen_ones_of_the_rows_live(traced):
    result, _, seen, _ = traced
    events = [e["attrs"] for e in in_window(seen["state"])
              if e["name"] == "dispatch" and "dsa_rows_read" in e["attrs"]]
    assert events
    read = sum(a["dsa_rows_read"] for a in events)
    live = sum(a["dsa_rows_live"] for a in events)
    # 8 chosen of 16 to 48 live rows a slot
    assert 100 * read / live == pytest.approx(
        result["metrics"]["dsa_rows_read_pct"]["value"])
    assert 10 < 100 * read / live < 60


def test_the_sparse_reads_counts_by_hand(traced):
    result, _, seen, _ = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    state = seen["state"]
    steps = list(micro_steps(decode_blocks(state)))
    live = sum(len(s) for s in steps) / len(steps)
    keys = sum(sum(s) for s in steps) / len(steps)
    rows = sum(sum(min(n, 8) for n in s) for s in steps) / len(steps)
    peak = tiny.PEAK
    # an index score: 4 heads of 32, a ReLU and the weighted sum, each live
    # key read once (32 bfloat16 numbers) and its score written (float32)
    flops = keys * 4 * (2 * 32 + 2)
    nbytes = keys * (32 * 2 + 4) + live * 128 * 2
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    assert got["index_decode_roofline"] == pytest.approx(100 * least / 0.5e-3)
    # a chosen row once at its own 144 numbers for 8 heads' scores and
    # sums; the rows, queries and outputs lie in VMEM, so no HBM bytes
    flops = rows * 8 * 2 * (144 + 128)
    least = flops / peak["flops_per_s"]
    assert got["attn_dsa_decode_roofline"] == pytest.approx(100 * least / 1e-3)


@pytest.mark.parametrize("mode", [hy4.CONTROL, "dense_read", "first_index",
                                  "plain_residual", "no_gate", "no_sink"])
def test_the_control_and_each_planted_fault_read_not_correct(traced, mode):
    """Over 8 seeded sequences of 40 tokens: the token that the control's
    precision, or the reference with one piece of the mathematics changed,
    puts first lies further below the reference's best than the cell's
    limit allows."""
    _, _, seen, _ = traced
    state = seen["state"]
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 96, 16).astype(np.int32),
                rng.integers(0, 96, 24).astype(np.int32)) for _ in range(8)]
    numbers = check.served_gaps(seen["ref"], state["sz"], seen["seed"],
                                samples, seen["length"], mode)
    assert numbers["control_gap"] > 1.5 * hy4.LIMITS["served_gap"], mode
    ok, _ = check.verdict({"served_gap": numbers["control_gap"],
                           "unanswered": 0}, hy4.LIMITS)
    assert not ok


def test_a_prefill_count_is_a_calls_mean_work():
    """A prompt of 40 tokens in chunks of 16 (16, 16, then 8 padded to 8):
    three calls of each prefill kernel a layer, the work of the prompt
    over them."""
    from benchmark import family

    counts = family.load(tiny.REPO, "counts", "hy_v4")
    ref = family.load(tiny.REPO, "references", "hy_v4")
    sz = counts.sizes(hy4.CONFIG, ref.sizes(hy4.CONFIG))
    assert counts._chunks(sz, 40) == [16, 16, 8]
    rows = sum(min(p + 1, 8) for p in range(40))
    assert counts.attn_prefill_flops(sz, 40, {"kind": "dsa"}) == \
        pytest.approx(8 * 2 * (144 + 128) * rows / 3)
    assert counts.attn_prefill_flops(sz, 40, {"kind": "index"}) == \
        pytest.approx(4 * (2 * 32 + 2) * 40 * 41 / 2 / 3)
