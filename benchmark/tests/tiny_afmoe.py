"""A tiny AFMoE configuration, cell and traffic for the CPU tests of the
runner, the reference and the controls (float32 program)."""

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "num_dense_layers": 1, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 2,
        "num_experts_routed": 8, "ep_size": 4, "ep_rank": 1,
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
        "n_group": 1, "topk_group": 1, "sliding_window": 16,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
        "rope_theta": 10000.0, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-5, "mup_enabled": True,
        "tie_word_embeddings": False, "dtype": "float32"}

TINY_MIX = {
    "loop": "backlog", "backlog_requests_per_s": 400, "ramp_allow_s": 1,
    "window_opens": {"after_retired": 4},
    "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8, "min": 4,
               "max": 60},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 2,
               "max": 40}}


def tiny_cell(**engine):
    return {"runner": "serve_afmoe",
            "engine": {"num_slots": 4, "max_length": 128, "paged": True,
                       "chunked": True, "prefill_chunk": 8, "block_len": 8,
                       "num_blocks": 65, **engine},
            "expect_paths": [],
            "allow_fallbacks": {"rms_norm": None, "decode_attention": None,
                                "flash_attention": None,
                                "chunked_prefill": None,
                                "moe_experts": None},
            "check": {"sample": 40,
                      "limits": {"served_gap_max": 1e-4,
                                 "served_gap_mean": 1e-5}}}
