"""The reduction from a trace to numbers, on hand-made intervals and on a
small trace recorded on the chip (``data/``), and the ``gpt2`` family's
counts (``counts/gpt2.py``) against hand counts. No topology is described
and no chip is needed."""

import gzip
import json
import os

import pytest

from benchmark import family, trace_reduce
from benchmark.flops import roofline_share
from benchmark.readers import decode_share, module_gap, module_ms

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "decode_ticks.xplane.pb.gz")


def sizes_of(config: str) -> dict:
    with open(os.path.join(family.ROOT, "benchmark", "configs",
                           f"{config}.json")) as f:
        return family.resolve(json.load(f), "backlog").sz


flops = family.load(family.ROOT, "counts", "gpt2")
LARGE = sizes_of("gpt2-large")
with open(os.path.join(HERE, "..", "..", "benchmark", "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]


def toy_trace():
    ms = 1_000_000
    ops = {0: [(0, 3 * ms, "while.2"),                # holds the next three
               (0, 2 * ms, "fusion.1"), (2 * ms, 3 * ms, "attn.7"),
               (1 * ms, 3 * ms, "copy.2"),            # overlaps the two
               (5 * ms, 6 * ms, "fusion.9"), (9 * ms, 10 * ms, "attn.8")]}
    modules = {0: [(0, 3 * ms, "jit_decode_block(1)"),
                   (5 * ms, 6 * ms, "jit__prefill(2)"),
                   (9 * ms, 10 * ms, "jit_decode_block(1)")]}
    spans = [(0, 4 * ms, "bench.engine_step"),
             (4 * ms, 10 * ms, "bench.engine_step"),
             (int(4.1 * ms), int(8.5 * ms), "serve.admit"),
             (int(4.5 * ms), int(6.5 * ms), "serve.prefill")]
    return trace_reduce.Trace(ops, modules, spans)


def test_busy_is_the_union_and_idle_the_rest():
    t = toy_trace()
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_s == pytest.approx(0.005)       # 3 + 1 + 1 ms, overlap once
    assert t.idle_share() == pytest.approx(0.5)
    assert trace_reduce.union_ns([(0, 5), (1, 2), (7, 9), (9, 10)]) == 8
    assert trace_reduce.gaps_ns([(0, 5), (1, 2), (7, 9)]) == [(5, 7)]


def test_device_time_by_name_and_inside_a_program():
    t = toy_trace()
    line = "%attn.232 = bf16[320,1,64]{2,1,0:T(2,128)(2,1)} custom-call(s32[16]"
    assert trace_reduce.op_name(line) == "attn.232"
    assert trace_reduce.op_family(line) == "attn"
    assert trace_reduce.op_family("%fusion.12.3") == "fusion"
    top = dict(t.top_ops())
    assert "while" not in top        # counted by what runs inside it
    assert top["fusion"] == pytest.approx(0.003)
    assert top["attn"] == pytest.approx(0.002)
    seconds, calls = t.op_seconds(r"^attn\.", "decode_block")
    assert (seconds, calls) == (pytest.approx(0.002), 2)
    assert t.op_seconds("^fusion", "_prefill") == (pytest.approx(0.001), 1)
    assert t.op_seconds("^attn", "_prefill") == (0.0, 0)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    t = toy_trace()
    gaps = t.idle_gaps()
    assert [round(s * 1e3, 3) for s, _ in gaps] == [2.0, 3.0]
    assert gaps[0][1] == "bench.engine_step"      # straddles two steps
    assert gaps[1][1] == "bench.engine_step/serve.admit"
    listed = dict(t.breakdown()["idle_gaps"])
    assert listed["total:bench.engine_step/serve.admit"] == pytest.approx(3e-3)


def test_readers_of_programs_read_gaps_and_micro_steps():
    t = toy_trace()
    events = [{"t": 1.0 + i, "name": "decode", "span_name": "request",
               "span": 1, "tick": i,
               "attrs": {"block": 4, "pos": 100, "tokens": 4}}
              for i in range(2)]
    one_layer = dict(LARGE, layers=1)   # the toy runs the kernel once a step
    state = {"trace": t, "events": events, "t_open": 0.0, "t_close": 9.0,
             "sz": one_layer, "counts": flops, "peak": V5E}
    spec = {"module": "decode_block", "op": r"^attn\.", "per": "micro_step"}
    assert module_gap.read(state, spec) == pytest.approx(6.0)
    # two programs, 4 ms together, two kernel calls: two micro-steps
    assert module_ms.read(state, spec) == pytest.approx(2.0)
    share = decode_share.read(state, dict(spec, of="bytes"))
    lens = [101 + i for i in range(4)]
    want = sum(flops.decode_step_bytes(one_layer, [n]) for n in lens) / 4
    assert share == pytest.approx(100 * want / 819e9 / 0.002)
    assert decode_share.read(dict(state, events=[]),
                             dict(spec, of="bytes")) is None
    assert module_ms.read(state, dict(spec, module="nothing")) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in data/")
def test_a_trace_recorded_on_the_chip(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    # a quarter of a second of the tiny throwaway cell (2 layers x 256, 4
    # slots) on a TPU v5 lite, my chip run, PR 25
    t = trace_reduce.read_xplane(str(path), chips=1)
    assert t.window_s == pytest.approx(0.212253753)
    assert t.busy_s == pytest.approx(0.001163934)
    assert t.idle_share() == pytest.approx(1 - 0.001163934 / 0.212253753)
    blocks = t.module_events("decode_block")
    assert len(blocks) == 12 and all(e > s for s, e, _ in blocks)
    seconds, calls = t.op_seconds(r"^attn\.", "decode_block")
    assert calls == 46 and seconds == pytest.approx(0.00040794)
    assert calls % 2 == 0            # the kernel runs once a layer a step
    assert dict(t.top_ops())["attn"] >= seconds
    names = {span.rsplit("/", 1)[-1] for _, span in t.idle_gaps()}
    assert "serve.admit" in names
    listed = t.breakdown()
    assert len(listed["device_ops"]) <= 10 and len(listed["idle_gaps"]) <= 10


# -- operations and bytes, by hand --------------------------------------------

BLOCK = 3 * 1280 * 1280 + 1280 * 1280 + 2 * 1280 * 5120   # 19,660,800
HEAD = 1280 * 50257                                       # 64,328,960


def test_gpt2_large_sizes():
    assert (LARGE["d"], LARGE["f"], LARGE["layers"], LARGE["heads"]) == (
        1280, 5120, 36, 20)
    assert flops.layer_matmul_params(LARGE) == BLOCK == 19_660_800
    assert flops.matmul_params(LARGE) == 36 * BLOCK + HEAD == 772_117_760


def test_one_decode_micro_step_by_hand():
    lens = [100, 612, 1000]          # three live slots, the rest dead
    attn = 36 * sum(4 * n * 1280 for n in lens)
    assert flops.decode_step_flops(LARGE, lens) == 2 * 3 * 772_117_760 + attn
    small = 36 * (3 * 1280 + 1280 + 5120 + 1280 + 4 * 1280) + 2 * 1280 + 50257
    params = 4 * (772_117_760 + small)
    kv = 36 * 2 * 1280 * 2 * (sum(lens) + len(lens))
    assert flops.decode_step_bytes(LARGE, lens) == params + kv
    # dead slots and rows past the frontier cost nothing: under 24 x 1,024
    full = flops.decode_step_bytes(LARGE, [1024] * 24)
    assert flops.decode_step_bytes(LARGE, lens) < full
    # at the chip's peaks a step that took its byte floor reads 100, not more
    floor = flops.decode_step_bytes(LARGE, lens) / V5E["hbm_bytes_per_s"]
    share, side = roofline_share(
        flops.decode_step_flops(LARGE, lens),
        flops.decode_step_bytes(LARGE, lens), floor, V5E)
    assert side == "memory" and share == pytest.approx(100.0)


def test_one_prefill_of_640_tokens_by_hand():
    attn = 36 * 4 * 1280 * (640 * 641 // 2)
    want = 2 * 640 * 36 * BLOCK + attn + 2 * HEAD
    assert flops.prefill_flops(LARGE, 640) == want
    # the TRUE length, not the bucket of 1,024
    assert flops.prefill_flops(LARGE, 640) < 0.7 * flops.prefill_flops(
        LARGE, 1024)
    assert flops.attn_prefill_bytes(LARGE, 640) == 4 * 640 * 1280 * 2
    assert roofline_share(1.0, 1.0, 0.0, V5E) is None


def test_training_counts_forward_and_backward_once():
    medium = sizes_of("gpt2-medium")
    weights = 24 * 12 * 1024 * 1024 + 1024 * 50257
    assert flops.matmul_params(medium) == weights
    attn = 24 * 3 * (4 * 1024 * (1024 * 1025 // 2)) / 1024
    assert flops.train_flops_per_token(medium, 1024) == 6 * weights + attn
