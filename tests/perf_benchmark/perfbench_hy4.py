"""The ``hy_v4`` family at a size the CPU holds, added to the throwaway
tree of ``perfbench_tiny`` as a configuration and a cell: the pattern of
the benchmark's cut of Hy4-preview (a dense layer and four routed ones,
the first two with their own indexer and the last three reading the
second's choice; 8 heads of 32 + 16 and 32 over a latent of 128, a query
rank of 32, a gate and a sink; an indexer of 4 heads of 32 choosing 8
rows; 4 residual streams; 8 experts of which 4 are held, top 2 scaled by
2.827, a shared expert; every SwiGLU clamped), chunked prefill, through
the family's real reference, counts and adapter, which the tree copies
with the rest of ``benchmark/``."""

from __future__ import annotations

import json
import os

import perfbench_tiny as tiny

CONFIG_NAME, CELL = "tiny-hy4", "tiny-hy4.tiny-backlog"
#: the new cell's metrics whose numbers come from the family's counts
COUNTED = ("decode_step_mfu_pct.sparse", "decode_hbm_roofline.sparse",
           "index_decode_roofline", "attn_dsa_decode_roofline",
           "index_prefill_roofline", "attn_dsa_prefill_roofline",
           "moe_decode_roofline")
LIKE = "hy4-preview.longdoc-backlog"
LAYERS = 5
INDEXER = ["full", "full", "shared", "shared", "shared"]
CONFIG = {
    "hidden_size": 64, "vocab_size": 96, "num_hidden_layers": LAYERS,
    "n_group": 1, "topk_group": 1, "use_mla": True, "use_dsa": True,
    "num_attention_heads": 8, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "qk_head_dim": 48, "v_head_dim": 32,
    "kv_lora_rank": 128, "q_lora_rank": 32, "index_n_heads": 4,
    "index_head_dim": 32, "index_topk": 8, "indexer_types": INDEXER,
    "mlp_layer_types": ["dense"] + ["sparse"] * (LAYERS - 1),
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "hc_mult": 4, "hc_magnitude": 2, "hc_eps": 1e-06,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.827, "norm_topk_prob": True,
    "swiglu_limit": 10, "rms_norm_eps": 1e-05, "initializer_range": 0.1,
    "published": {"n_routed_experts": 8},
    "source": "https://huggingface.co/tencent/Hy4-preview",
    "assumed": {"everything": "a test's throwaway at a size the CPU holds"},
    "program": {
        "reference": "hy_v4", "adapter": "hy_v4", "builder": "hybrid_lm",
        # rows of 128 + 16 numbers are held in 256 lanes
        "stored": {"param_bytes": 2, "kv_bytes": 2, "latent_width": 256,
                   "head_bytes": 4},
        "model": {
            "vocab_size": 96, "d_model": 64, "heads": 8, "head_dim": 48,
            "v_head_dim": 32, "attention": ["mla"] * LAYERS,
            "ffn": ["dense"] + ["routed"] * (LAYERS - 1),
            "rope_base": 10000.0, "kv_lora_rank": 128,
            "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "d_ff": 96,
            "n_experts": 8, "top_k": 2, "expert_d_ff": 32,
            "held_experts": [0, 4], "shared_d_ff": 32,
            "routed_scale": 2.827, "norm_eps": 1e-05, "max_len": 64,
            "param_dtype": "bfloat16", "q_lora_rank": 32, "attn_gate": True,
            "latent_sink": True, "index_topk": 8, "index_heads": 4,
            "index_dim": 32, "indexer": INDEXER, "hc_streams": 4,
            "hc_magnitude": 2.0, "hc_eps": 1e-06, "swiglu_limit": 10.0,
            "head_fp32": True},
        "engine": {"slots": 4, "cache_len": 64, "decode_block": 4,
                   "prefill_chunk": 16, "max_fills": 2},
        "trainer": None,
    },
}
#: read at this size on the CPU (test_perfbench_hy4.py has the readings)
LIMITS = {"served_gap": 0.3, "unanswered": 0}
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
        "why": "a test's throwaway: the sparse latent family at a tiny size"})
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
