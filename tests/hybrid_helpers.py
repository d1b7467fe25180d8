"""What the two ``hybrid_lm`` test files share (test_hybrid_lm.py: the model
through pool and engine; test_hybrid_kernels.py: its kernels and expert
layer): the tiny cut of the family's published config."""

WINDOW, VOCAB = 8, 96
CFG = {
    "hidden_size": 32, "vocab_size": VOCAB, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "num_attention_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "rope_theta": 5e6, "swa_rope_theta": 1e4,
    "partial_rotary_factor": 0.334, "sliding_window": WINDOW,
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "layernorm_epsilon": 1e-5,
    "initializer_range": 0.16, "published": {"n_routed_experts": 8},
}
