"""The ``lfm2_moe`` family at a size the CPU holds, added to the throwaway
tree of ``perfbench_tiny`` as a configuration and a cell: the same pattern
as the benchmark's cut of LFM2-8B-A1B (``[conv, conv, full, conv]`` over
``[dense, dense, routed, routed]``, 8 query and 2 KV heads of 8 over a
stream of 64, 3 taps, all 8 experts held, top 2), through the family's
real reference, counts and adapter, which the tree copies with the rest
of ``benchmark/``."""

from __future__ import annotations

import json
import os

import perfbench_tiny as tiny

CONFIG_NAME, CELL = "tiny-lfm2", "tiny-lfm2.tiny-backlog"
#: the new cell's metrics whose numbers come from the family's counts
COUNTED = ("decode_step_mfu_pct.state", "decode_hbm_roofline.state",
           "conv_decode_roofline", "attn_full_decode_roofline.state",
           "attn_full_prefill_roofline.state", "moe_decode_roofline")
LIKE = "lfm2-8b-a1b.synth-backlog"
KINDS = ("conv", "conv", "full", "conv")
FFNS = ("dense", "dense", "routed", "routed")
CONFIG = {
    "hidden_size": 64, "vocab_size": 96, "num_hidden_layers": 4,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 8,
    "num_key_value_heads": 2, "rope_theta": 1000000, "num_dense_layers": 2,
    "intermediate_size": 96, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "norm_eps": 1e-05,
    "initializer_range": 0.12,
    "source": "https://huggingface.co/LiquidAI/LFM2-8B-A1B",
    "assumed": {"everything": "a test's throwaway at a size the CPU holds"},
    "program": {
        "reference": "lfm2_moe", "adapter": "lfm2_moe",
        "builder": "hybrid_lm",
        "stored": {"param_bytes": 2, "kv_bytes": 2},
        "model": {
            "vocab_size": 96, "d_model": 64, "heads": 8, "head_dim": 8,
            "kv_heads": 2, "attention": list(KINDS), "ffn": list(FFNS),
            "rope_base": 1000000.0, "qk_norm": True, "conv_kernel": 3,
            "d_ff": 96, "n_experts": 8, "top_k": 2, "expert_d_ff": 16,
            "held_experts": [0, 8], "norm_eps": 1e-05, "max_len": 64,
            "param_dtype": "bfloat16"},
        "engine": {"slots": 4, "cache_len": 64, "decode_block": 4},
        "trainer": None,
    },
}
#: read at this size on the CPU (test_perfbench_lfm2.py has the readings)
LIMITS = {"served_gap": 0.12, "unanswered": 0}
#: at a width of 64 int8 is no coarser than bfloat16 (test_perfbench_check.py)
CONTROL = "fp8"


def build(root: str) -> dict:
    """``perfbench_tiny``'s tree under ``root`` with the tiny cut of the
    family added as new files and entries; returns the manifest."""
    manifest = tiny.build(root)
    before = tiny._listing(root)
    for rel, obj in ((f"configs/{CONFIG_NAME}.json", CONFIG),
                     (f"cells/{CELL}.json",
                      {"limits": LIMITS, "control_mode": CONTROL})):
        path = os.path.join(root, "benchmark", rel)
        assert not os.path.exists(path), f"{rel} is already there"
        with open(path, "w") as f:
            json.dump(obj, f)
    manifest["configs"].append({
        "name": CONFIG_NAME, "source": CONFIG["source"],
        "file": f"benchmark/configs/{CONFIG_NAME}.json", "reduced": [],
        "why": "a test's throwaway: the short-convolution family, tiny"})
    manifest["workloads"].append({
        "name": CELL, "config": CONFIG_NAME, "traffic": "tiny-backlog",
        "chips": 1, "why": "a test's throwaway"})
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if LIKE in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    after = tiny._listing(root)
    assert all(after[p] == h for p, h in before.items()), \
        "a file the benchmark already had was changed"
    return manifest
