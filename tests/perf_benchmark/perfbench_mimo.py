"""The ``mimo_v2_flash`` family at a size the CPU holds, added to the
throwaway tree of ``perfbench_tiny`` as a configuration and a cell: the
same pattern as the benchmark's cut (``[full+dense, swa, swa, full]``,
8 experts of which 4 are held, a window of 8, q/k heads of 24 and v heads
of 16 with 8 rotary dimensions), through the family's real reference,
counts and adapter, which the tree copies with the rest of ``benchmark/``."""

from __future__ import annotations

import json
import os

import perfbench_tiny as tiny

CONFIG_NAME, CELL = "tiny-mimo", "tiny-mimo.tiny-backlog"
#: the new cell's metrics whose numbers come from the family's counts
COUNTED = ("decode_step_mfu_pct.mixed", "decode_hbm_roofline.mixed",
           "attn_full_decode_roofline", "attn_swa_decode_roofline",
           "moe_decode_roofline", "attn_full_prefill_roofline",
           "attn_swa_prefill_roofline")
LIKE = "mimo-v2-flash.reason-backlog"
PATTERN = {"attention": ["full", "swa", "swa", "full"],
           "ffn": ["dense", "routed", "routed", "routed"]}
CONFIG = {
    "hidden_size": 32, "vocab_size": 96, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "num_attention_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "rope_theta": 5000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.334, "sliding_window": 8,
    "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "layernorm_epsilon": 1e-05, "initializer_range": 0.08,
    "published": {"n_routed_experts": 8},
    "source": "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash",
    "assumed": {"everything": "a test's throwaway at a size the CPU holds"},
    "program": {
        "reference": "mimo_v2_flash", "adapter": "mimo_v2_flash",
        "builder": "hybrid_lm",
        "stored": {"param_bytes": 2, "kv_bytes": 2},
        "model": {
            "vocab_size": 96, "d_model": 32, "heads": 4, "head_dim": 24,
            "v_head_dim": 16, **PATTERN, "kv_heads": 1, "swa_kv_heads": 2,
            "window": 8, "rope_base": 5000000.0, "swa_rope_base": 10000.0,
            "rotary_dim": 8, "value_scale": 0.707, "swa_sink": True,
            "d_ff": 64, "n_experts": 8, "top_k": 2, "expert_d_ff": 16,
            "held_experts": [0, 4], "max_len": 64,
            "param_dtype": "bfloat16"},
        "engine": {"slots": 4, "cache_len": 64, "decode_block": 4},
        "trainer": None,
    },
}
#: read at this size on the CPU (test_perfbench_mimo.py has the readings)
LIMITS = {"served_gap": 0.05, "unanswered": 0}
#: at a width of 32 int8 is no coarser than bfloat16 (test_perfbench_check.py)
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
        "why": "a test's throwaway: the hybrid family at a tiny size"})
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
