"""The ``sdar_moe`` family at a size the CPU holds, added to the throwaway
tree of ``perfbench_tiny`` as a configuration and a cell: the shape of the
benchmark's cut of SDAR-30B-A3B-Chat (every layer full attention,
block-causal over blocks of 4 with 2 denoising steps, a softmax top-k
router over all experts held, q/k norms, heads wider than the stream's
share), through the family's real reference, counts and adapter, which
the tree copies with the rest of ``benchmark/``."""

from __future__ import annotations

import json
import os

import perfbench_tiny as tiny

CONFIG_NAME, CELL = "tiny-sdar", "tiny-sdar.tiny-blockgen"
MIX = "tiny-blockgen"
#: the new cell's metrics whose numbers come from the family's counts
COUNTED = ("decode_step_mfu_pct.block", "decode_hbm_roofline.block",
           "attn_block_decode_roofline", "attn_block_prefill_roofline",
           "moe_decode_roofline")
LIKE = "sdar-30b-a3b-chat.blockgen-backlog"
LAYERS, BLOCK, STEPS, MASK = 2, 4, 2, 95
CONFIG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "max_position_embeddings": 64, "max_window_layers": LAYERS,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 16, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": LAYERS, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 96,
    "initializer_range": 0.12,
    "source": "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat",
    "assumed": {"everything": "a test's throwaway at a size the CPU holds"},
    "program": {
        "reference": "sdar_moe", "adapter": "sdar_moe",
        "builder": "hybrid_lm",
        "stored": {"param_bytes": 2, "kv_bytes": 2},
        "model": {
            "vocab_size": 96, "d_model": 64, "heads": 8, "head_dim": 16,
            "kv_heads": 2, "attention": ["full"] * LAYERS,
            "ffn": ["routed"] * LAYERS, "rope_base": 1000000.0,
            "qk_norm": True, "n_experts": 8, "top_k": 2, "expert_d_ff": 16,
            "held_experts": [0, 8], "norm_eps": 1e-06, "max_len": 64,
            "param_dtype": "bfloat16", "router": "softmax", "block": BLOCK,
            "denoise_steps": STEPS, "mask_id": MASK},
        "engine": {"slots": 4, "cache_len": 64, "decode_block": 8},
        "trainer": None,
    },
}
#: prompts that end inside blocks as well as on their edges, answers whose
#: budgets end inside blocks
BLOCKGEN = {
    "kind": "backlog", "count": 16, "group": 4, "order_seed": 1,
    "prompt_len": {"dist": "loguniform", "lo": 5, "hi": 22},
    "output_len": {"dist": "loguniform", "lo": 6, "hi": 22},
    "queued": 2, "fill_s": 0.3, "stagger_first_slotful": True,
    "check_requests": 4}
#: read at this size on the CPU (test_perfbench_sdar.py has the readings)
LIMITS = {"served_gap": 0.12, "unanswered": 0}
CONTROL = "fp8"


def build(root: str) -> dict:
    """``perfbench_tiny``'s tree under ``root`` with the tiny cut of the
    family and its mix added as new files and entries; returns the
    manifest."""
    manifest = tiny.build(root)
    before = tiny._listing(root)
    for rel, obj in ((f"configs/{CONFIG_NAME}.json", CONFIG),
                     (f"traffic/{MIX}.json", BLOCKGEN),
                     (f"cells/{CELL}.json",
                      {"limits": LIMITS, "control_mode": CONTROL})):
        path = os.path.join(root, "benchmark", rel)
        assert not os.path.exists(path), f"{rel} is already there"
        with open(path, "w") as f:
            json.dump(obj, f)
    manifest["configs"].append({
        "name": CONFIG_NAME, "source": CONFIG["source"],
        "file": f"benchmark/configs/{CONFIG_NAME}.json", "reduced": [],
        "why": "a test's throwaway: the block-diffusion family, tiny"})
    manifest["workloads"].append({
        "name": CELL, "config": CONFIG_NAME, "traffic": MIX,
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
