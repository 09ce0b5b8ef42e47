"""A tiny SDAR-MoE configuration, cell and traffic for the CPU tests of the
runner, the reference and the controls (float32 program)."""

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 4, "num_experts_routed": 8, "ep_size": 2,
        "ep_rank": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "rope_theta": 1000000, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "dtype": "float32",
        # unit gain at this width (1/sqrt(64)): see weights_sdar.py
        "initializer_range": 0.125,
        "block_length": 4, "mask_token_id": 255, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_dynamic",
        "confidence_threshold": 0.9}

TINY_MIX = {
    "loop": "backlog", "backlog_requests_per_s": 400, "ramp_allow_s": 1,
    "window_opens": {"after_retired": 4},
    "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8, "min": 4,
               "max": 60},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 2,
               "max": 40}}


def tiny_cell(**engine):
    return {"runner": "serve_sdar",
            "engine": {"num_slots": 4, "max_length": 128, "paged": True,
                       "chunked": True, "prefill_chunk": 8, "block_len": 8,
                       "num_blocks": 65, "prefix_cache": False, **engine},
            "expect_paths": [],
            "allow_fallbacks": {"rms_norm": None, "decode_attention": None,
                                "flash_attention": None,
                                "chunked_prefill": None,
                                "moe_experts": None},
            "check": {"sample": 40, "gap_tail": 1e-4,
                      "limits": {"served_gap_max": 1e-4,
                                 "served_gap_mean": 1e-5,
                                 "served_gap_over_pct": 0.0,
                                 "served_pick_gap_mean": 1e-5}}}
