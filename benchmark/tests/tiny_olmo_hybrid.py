"""A tiny Olmo-Hybrid configuration, cell and traffic for the CPU tests of
the runner, the reference and the controls (float32 program)."""

LINEAR, FULL = "linear_attention", "full_attention"

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 4, "max_position_embeddings": 256,
        "attention_bias": False, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False,
        "layer_types": [LINEAR, LINEAR, LINEAR, FULL, LINEAR],
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 32,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "dtype": "float32",
        # unit gain at this width (1/sqrt(64)): see weights_lfm2.py
        "initializer_range": 0.125}

TINY_MIX = {
    "loop": "backlog", "backlog_requests_per_s": 400, "ramp_allow_s": 1,
    "window_opens": {"after_retired": 4},
    "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8, "min": 4,
               "max": 60},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 2,
               "max": 40}}


def tiny_cell(**engine):
    return {"runner": "serve_olmo_hybrid",
            "engine": {"num_slots": 4, "max_length": 128, "paged": True,
                       "chunked": True, "prefill_chunk": 8, "block_len": 8,
                       "num_blocks": 65, "prefix_cache": False, **engine},
            "expect_paths": [],
            "allow_fallbacks": {"rms_norm": None, "decode_attention": None,
                                "flash_attention": None,
                                "chunked_prefill": None,
                                "gated_delta_step": None,
                                "gated_delta_chunk": None},
            "check": {"sample": 40,
                      "limits": {"served_gap_max": 1e-4,
                                 "served_gap_mean": 1e-5}}}
