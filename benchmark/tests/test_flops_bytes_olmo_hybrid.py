"""Hand counts at Olmo-Hybrid-7B's published widths, 16 layers held."""

import json
import os

import pytest

from benchmark.harness import flops_bytes as fb
from benchmark.harness import flops_bytes_lfm2 as fl
from benchmark.harness import flops_bytes_olmo_hybrid as fo
from benchmark.harness import manifest as mf
from benchmark.harness import weights_olmo_hybrid

with open(os.path.join(mf.BENCH, "configs", "olmo-hybrid-7b.json")) as f:
    CFG = json.load(f)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def test_the_cut_is_four_whole_periods():
    assert CFG["num_hidden_layers"] == 16 == len(CFG["layer_types"])
    assert CFG["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"] + CFG["layer_types"][4:]
    assert CFG["layer_types"][:4] * 4 == CFG["layer_types"]
    assert fo.linear_layers(CFG) == 12 and fl.kv_layers(CFG) == 4


def test_the_files_arithmetic():
    """The parameter count the configuration's file states, from the
    shapes the weights are made in."""
    n = 0
    for group in weight_shapes().values():
        for shape in group.values():
            size = 1
            for d in shape:
                size *= d
            n += size
    assert round(n / 1e6, 1) == 4100.8
    shapes = weight_shapes()
    lin = sum(_size(s) for s in shapes["layers.0"].values())
    full = sum(_size(s) for s in shapes["layers.3"].values())
    assert round(lin / 1e6, 2) == 215.57 and round(full / 1e6, 2) == 185.81


def weight_shapes():
    return weights_olmo_hybrid.weight_shapes(CFG)


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_a_request_holds_60_kib_a_position_and_27_mb_of_state():
    # 4 layers x (K, V) x 30 heads x 128 x 2 B
    assert fl.kv_bytes_per_position(CFG) == 61440
    # a layer: 30 heads x 96 x 192 float32
    assert fo.delta_state_bytes(CFG) == 2211840
    # 12 layers x (S + 3 carried inputs of 11,520 channels in bf16)
    assert fo.state_bytes_per_slot(CFG) == 12 * (2211840 + 3 * 11520 * 2)
    assert fo.state_bytes_per_slot(CFG) == 27371520


def test_one_tick_of_the_step_by_hand():
    # 57 occupied rows: each of 12 layers reads and writes 2.2 MB of S a
    # row, and q, k (96 a head), v, o (192) and two gates, float32
    flops, nbytes = fo.gated_delta_step(CFG, 57)
    assert flops == 7 * 30 * 96 * 192 * 57 * 12
    assert nbytes == 12 * 57 * (2 * 2211840
                                + 4 * 30 * (96 + 96 + 192 + 192 + 2))
    least, bound = fb.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)
    assert least == pytest.approx(3.74e-3, rel=0.01)      # 3.7 ms a tick


def test_one_chunk_of_the_walk_by_hand():
    # 256 tokens: four sub-chunks of 64 a head and layer; three products of
    # 64 x 96 x 192 and one of 64 x 64 x 192
    flops, nbytes = fo.gated_delta_chunk(CFG, 256)
    one = 4 * 30 * (3 * 2 * 64 * 96 * 192 + 2 * 64 * 64 * 192)
    assert flops == 12 * one
    assert nbytes == 12 * (2 * 2211840 + 4 * 4 * 30 * 64 * (
        3 * 96 + 64 + 2 * 192))
    # a chunk cut short walks whole sub-chunks: 65 tokens are two
    assert fo.gated_delta_chunk(CFG, 65)[0] == flops / 2
    least, bound = fb.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "memory"


def test_kv_layers_reader_counts_four_layers_of_sixteen():
    # the LFM2 cell's reader counts from layer_types, heads and hidden size
    # alone: at a query group of ONE, K and V are 2 x 30 x 128 a position
    flops, nbytes = fl.decode_rows_attention(CFG, 57, 57 * 650)
    assert flops == 4 * 4 * 3840 * 57 * 650
    assert nbytes == 61440 * 57 * 650 + 4 * 2 * 3840 * 2 * 57
