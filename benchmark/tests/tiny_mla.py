"""A tiny latent-attention MoE configuration, cell and traffic for the CPU
tests of the runner, the reference and the controls (float32 program):
three documents of four 8-position blocks, a question of 1 to 28 tokens."""

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "q_lora_rank": 48, "kv_lora_rank": 128, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 32, "v_head_dim": 16, "n_routed_experts": 2,
        "n_experts_routed": 8, "ep_size": 4, "ep_rank": 1,
        "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "rope_theta": 10000.0,
        "rope_interleave": True, "rope_scaling": None,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "dtype": "float32"}

TINY_MIX = {
    "loop": "backlog", "backlog_requests_per_s": 400, "ramp_allow_s": 1,
    "window_opens": {"after_retired": 6},
    "tenants": 3, "tenant_zipf_a": 0.6, "shared_prefix_len": 32,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.2, "min": 33,
               "max": 60},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 2,
               "max": 40}}


def tiny_cell(**engine):
    return {"runner": "serve_mla",
            "engine": {"num_slots": 4, "max_length": 128, "paged": True,
                       "chunked": True, "prefill_chunk": 8, "block_len": 8,
                       "num_blocks": 65, "prefix_cache": True, **engine},
            "expect_paths": [],
            "allow_fallbacks": {"rms_norm": None, "decode_attention": None,
                                "flash_attention": None,
                                "chunked_prefill": None,
                                "moe_experts": None},
            "check": {"sample": 6,
                      "limits": {"served_gap_max": 2e-4,
                                 "served_gap_mean": 2e-5}}}
