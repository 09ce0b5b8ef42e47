"""Tests of the benchmark's own yardstick; run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are outside ``tests/``, so tier-1's count does not move with them.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": False, "dtype": "float32"}

TINY_MIX = {
    "loop": "backlog", "backlog_requests_per_s": 400, "ramp_allow_s": 1,
    "window_opens": {"after_retired": 4},
    "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.6, "min": 4,
               "max": 24},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 2,
               "max": 40}}


def tiny_cell(**engine):
    return {"runner": "serve",
            "engine": {"num_slots": 4, "max_length": 128, **engine},
            "expect_paths": [],
            "allow_fallbacks": {"rms_norm": None, "decode_attention": None,
                                "flash_attention": None,
                                "chunked_prefill": None},
            "check": {"sample": 6,
                      "limits": {"served_gap_max": 1e-4,
                                 "served_gap_mean": 1e-5}}}
