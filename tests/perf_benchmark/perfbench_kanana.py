"""The ``deepseek_v3`` family at a size the CPU holds, added to the
throwaway tree of ``perfbench_tiny`` as a configuration and a cell: the
same pattern as the benchmark's cut of Kanana-2-30B-A3B (``[mla+dense,
mla+routed]``, 8 heads of 16 + 8 and 16 over a latent of 128,
8 experts of which 4 are held, top 2 scaled by 2.448, a shared expert),
through the family's real reference, counts and adapter, which the tree
copies with the rest of ``benchmark/``."""

from __future__ import annotations

import json
import os

import perfbench_tiny as tiny

CONFIG_NAME, CELL = "tiny-kanana", "tiny-kanana.tiny-backlog"
#: the new cell's metrics whose numbers come from the family's counts
COUNTED = ("decode_step_mfu_pct.latent", "decode_hbm_roofline.latent",
           "attn_mla_decode_roofline", "attn_mla_prefill_roofline",
           "moe_decode_roofline")
LIKE = "kanana-2-30b-a3b.report-backlog"
LAYERS = 2
CONFIG = {
    "hidden_size": 32, "vocab_size": 96, "num_hidden_layers": LAYERS,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "num_attention_heads": 8, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
    "kv_lora_rank": 128, "q_lora_rank": None, "rope_theta": 10000,
    "rope_interleave": True, "rope_scaling": None,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_shared_experts": 2, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-06, "initializer_range": 0.16,
    "published": {"n_routed_experts": 8},
    "source": "https://huggingface.co/kakaocorp/"
              "kanana-2-30b-a3b-instruct-2601",
    "assumed": {"everything": "a test's throwaway at a size the CPU holds"},
    "program": {
        "reference": "deepseek_v3", "adapter": "deepseek_v3",
        "builder": "hybrid_lm",
        # rows of 128 + 8 numbers are held in 256 lanes
        "stored": {"param_bytes": 2, "kv_bytes": 2, "latent_width": 256},
        "model": {
            "vocab_size": 96, "d_model": 32, "heads": 8, "head_dim": 24,
            "v_head_dim": 16, "attention": ["mla"] * LAYERS,
            "ffn": ["dense"] + ["routed"] * (LAYERS - 1),
            "rope_base": 10000.0, "rope_interleave": True,
            "kv_lora_rank": 128, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "d_ff": 64, "n_experts": 8, "top_k": 2,
            "expert_d_ff": 16, "held_experts": [0, 4], "shared_d_ff": 32,
            "routed_scale": 2.448, "norm_eps": 1e-06, "max_len": 64,
            "param_dtype": "bfloat16"},
        "engine": {"slots": 4, "cache_len": 64, "decode_block": 4},
        "trainer": None,
    },
}
#: read at this size on the CPU (test_perfbench_kanana.py has the readings)
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
        "why": "a test's throwaway: the latent family at a tiny size"})
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
