"""Hand counts at both configurations' widths (Mistral-7B-v0.3 and
Yi-1.5-9B as published)."""

import pytest

from benchmark.harness import flops_bytes as fb

MISTRAL = {"hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 8, "intermediate_size": 14336,
           "vocab_size": 32768, "num_hidden_layers": 16}
YI = {"hidden_size": 4096, "num_attention_heads": 32,
      "num_key_value_heads": 4, "intermediate_size": 11008,
      "vocab_size": 64000, "num_hidden_layers": 8}


def test_parameters_by_hand():
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    assert fb.layer_matmul_params(MISTRAL) == (
        16777216 + 2 * 4194304 + 16777216 + 3 * 58720256) == 218103808
    assert fb.matmul_params(MISTRAL) == 16 * 218103808 + 4096 * 32768
    # q, o 4096x4096; k, v 4096x512; three 4096x11008
    assert fb.layer_matmul_params(YI) == (
        2 * 16777216 + 2 * 2097152 + 3 * 45088768) == 173015040
    assert fb.matmul_params(MISTRAL) == 3623878656


@pytest.mark.parametrize("cfg,kv_row", [(MISTRAL, 8 * 128), (YI, 4 * 128)])
def test_one_decode_call_by_hand(cfg, kv_row):
    # 24 rows whose depths sum to 12000: each query does 32 heads x 128 x
    # depth multiply-adds for QK^T and again for PV
    flops, nbytes = fb.cached_attention(cfg, 12000, 12000, 24)
    assert flops == 2 * 2 * 32 * 128 * 12000
    assert nbytes == 2 * 12000 * kv_row * 2 + 2 * 24 * 4096 * 2
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    least, bound = fb.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)


def test_a_prefill_chunk_counts_its_causal_pairs():
    c, m = 256, 1000
    flops, nbytes = fb.cached_attention(
        MISTRAL, c * m + c * (c + 1) // 2, m + c, c)
    assert flops == 4 * 32 * 128 * (256 * 1000 + 32896)
    assert nbytes == 2 * 1256 * 1024 * 2 + 2 * 256 * 4096 * 2


@pytest.mark.parametrize("cfg,seq", [(MISTRAL, 2048), (YI, 4096)])
def test_one_train_token_by_hand(cfg, seq):
    matmul = 6 * fb.matmul_params(cfg)
    attn = cfg["num_hidden_layers"] * 3 * (2 * seq * 4096)
    assert fb.train_token_flops(cfg, seq) == matmul + attn
    if cfg is YI:       # 8 layers: 6 x 1.646 B + 8 x 100.7 M = 10.68 GFLOP
        assert fb.train_token_flops(cfg, seq) == pytest.approx(10.68e9,
                                                               rel=1e-3)
