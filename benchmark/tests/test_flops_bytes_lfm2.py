"""Hand counts at LFM2-8B-A1B's published widths."""

import pytest

from benchmark.harness import flops_bytes as fb
from benchmark.harness import flops_bytes_lfm2 as fl

LFM2 = {"hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "dtype": "bfloat16",
        "layer_types": ["full_attention" if i in (2, 6, 10, 14, 18, 21)
                        else "conv" for i in range(24)]}


def test_six_layers_of_24_hold_kv():
    assert fl.kv_layers(LFM2) == 6
    # 6 layers x (K, V) x 8 heads x 64 x 2 B
    assert fl.kv_bytes_per_position(LFM2) == 12288
    # 18 layers x 2 carried inputs x 2048 channels x 2 B
    assert fl.state_bytes_per_slot(LFM2) == 147456


def test_one_tick_of_decode_rows_by_hand():
    # 250 rows whose depths sum to 200,000: each K/V layer reads 8 heads x
    # 64 of K and of V a position, and q in and the output out a row
    flops, nbytes = fl.decode_rows_attention(LFM2, 250, 200000)
    assert flops == 6 * 2 * 2 * 32 * 64 * 200000
    assert nbytes == 6 * (2 * 200000 * 512 * 2 + 2 * 250 * 2048 * 2)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    least, bound = fb.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)
    # a quarter of what a reader that counts every layer would ask for
    every = 24 * fb.cached_attention(
        dict(LFM2, intermediate_size=0, vocab_size=0), 200000, 200000,
        250)[1]
    assert nbytes * 4 == every
