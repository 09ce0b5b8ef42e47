"""The SDAR-MoE reference's own pieces: what a request's forwards were fed
(rebuilt from the tokens and their unmask order), the third control's
sequence, the unmasking rule, and the weights' names against the program's.
The engine against the reference is tier-1's (``tests/test_sdar.py``)."""

import json
import os

import numpy as np

from benchmark.harness import weights_sdar
from benchmark.reference import sdar_arch
from tiny_sdar import TINY

M = TINY["mask_token_id"]


def test_forwards_fed_rebuilds_every_forwards_input():
    # a prompt block, a block given two positions by the prompt that took
    # two forwards, and a block that took four
    seq = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12])
    when = np.array([0, 0, 0, 0, 0, 0, 2, 1, 3, 1, 4, 2])
    fed, f, pos = sdar_arch.forwards_fed(seq, when, TINY)
    assert fed.shape == (4, 12)
    assert fed[0].tolist() == [1, 2, 3, 4, 5, 6, M, M, M, M, M, M]
    assert fed[1].tolist() == [1, 2, 3, 4, 5, 6, M, 8, M, 10, M, M]
    # the second block had two forwards: clean at the third and fourth
    assert fed[2].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, M, 10, M, 12]
    assert fed[3].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, M, 12]
    reads = sorted(zip(f.tolist(), pos.tolist()))
    assert reads == [(1, 6), (1, 7), (1, 8), (1, 9), (1, 10), (1, 11),
                     (2, 6), (2, 8), (2, 10), (2, 11), (3, 8), (3, 10),
                     (4, 10)]
    last = sdar_arch.as_last_fed(seq, when, TINY)
    assert last.tolist() == [1, 2, 3, 4, 5, 6, M, 8, 9, 10, M, 12]


def test_unmask_rule():
    logp = np.log(np.array([0.95, 0.5, 0.97, 0.2]))
    masked = np.array([True, True, True, False])
    assert sdar_arch.unmask(logp, masked, sdar_arch.DYNAMIC, 0.9,
                            1).tolist() == [True, False, True, False]
    assert sdar_arch.unmask(logp, masked, sdar_arch.DYNAMIC, 0.99,
                            1).tolist() == [False, False, True, False]
    assert sdar_arch.unmask(logp, masked, sdar_arch.STATIC, 0.0,
                            2).tolist() == [True, False, True, False]


def test_every_weight_has_a_name_in_the_program():
    from paddle_tpu import nn
    from paddle_tpu.models.sdar import SdarMoeForCausalLM
    from benchmark.harness import serve_sdar
    with nn.abstract_parameters():
        model = SdarMoeForCausalLM(serve_sdar.program_config(TINY, 128))
    have = {n: tuple(p.shape)
            for n, p in model.named_parameters(include_buffers=True)
            if not p.is_buffer}
    groups = weights_sdar.weight_shapes(TINY)
    made = {weights_sdar.program_name(("" if g == "top" else g + ".") + n):
            shape for g, shapes in groups.items()
            for n, shape in shapes.items()}
    assert made == have
    assert len(weights_sdar.reference_names(TINY)) == len(have)


def test_the_configuration_file_keeps_the_catalogs_numbers():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cfg = json.load(open(os.path.join(
        root, "benchmark", "configs", "sdar-30b-a3b-ep8.json")))
    catalog = {"attention_bias": False, "decoder_sparse_step": 1,
               "head_dim": 128, "hidden_size": 2048,
               "intermediate_size": 6144, "max_position_embeddings": 32768,
               "max_window_layers": 48, "moe_intermediate_size": 768,
               "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts": 128, "num_experts_per_tok": 8,
               "num_hidden_layers": 48, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-06, "rope_theta": 1000000,
               "tie_word_embeddings": False, "use_sliding_window": False,
               "vocab_size": 151936}
    differ = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differ == {"num_experts"} == set(cfg["reduced"])
    assert cfg["num_experts"] * cfg["ep_size"] == cfg["num_experts_routed"]
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 + 2 * 2048
             + 2 * 128 + 16 * 3 * 2048 * 768)
    assert 48 * layer + 2 * 151936 * 2048 + 2048 == 5_164_972_032  # 10.33 GB
