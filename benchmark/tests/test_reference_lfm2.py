"""The plain LFM2-MoE reference: a convolution layer with experts and an
attention layer written out by hand in numpy (token by token, tap by tap,
expert by expert: an independent whole-sequence formulation), against the
program's ``Lfm2MoeForCausalLM`` at a tiny size, and its two controls; CPU,
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights_lfm2
from benchmark.reference import lfm2_arch
from tiny_lfm2 import TINY


@pytest.fixture(scope="module")
def pair():
    from benchmark.harness.serve_lfm2 import build_model
    return build_model(TINY, 2**31 + 11, TINY["max_position_embeddings"])


def _np(w):
    return {k: np.asarray(v, np.float64) for k, v in w.items()}


def _norm(x, w, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _experts_by_hand(z, w):
    """Rank 1 of 2 holds experts 4..7 of 8; top 2 by score + bias."""
    s = 1.0 / (1.0 + np.exp(-(z @ w["router"])))
    m = np.zeros_like(z)
    for i in range(len(z)):
        chosen = np.argsort(-(s[i] + w["router_bias"]))[:2]
        for e in chosen:
            if 4 <= e < 8:
                wt = s[i, e] / (s[i, chosen].sum() + 1e-6)
                g = _silu(z[i] @ w["experts_gate"][e - 4]) * (
                    z[i] @ w["experts_up"][e - 4])
                m[i] += wt * (g @ w["experts_down"][e - 4])
    return m


def _layer(made, i, x, kind):
    t = x.shape[0]
    cos, sin = lfm2_arch.rope_tables(t, 16, 1e6)
    with jax.default_matmul_precision("highest"):
        return np.asarray(lfm2_arch.decoder_layer(
            jnp.asarray(x, jnp.float32), lfm2_arch.layer_weights(made, i),
            cos, sin, cfg=lfm2_arch._static(TINY), kind=kind, dense=False))


def test_a_convolution_layer_with_experts_by_hand(pair):
    """Layer 3 of the tiny model on 20 tokens in float64 loops: the
    convolution as a sum over taps a token, zeros before the sequence."""
    _, made = pair
    w = _np(lfm2_arch.layer_weights(made, 3))
    t, h = 20, 64
    x = np.random.default_rng(0).normal(size=(t, h))
    y = _norm(x, w["op_norm"])
    proj = y @ w["conv_in"]
    b, c, xx = proj[:, :h], proj[:, h:2 * h], proj[:, 2 * h:]
    u = b * xx
    conv = np.zeros((t, h))
    for i in range(t):
        for j in range(3):              # tap j weighs u_{i-2+j}
            if i - 2 + j >= 0:
                conv[i] += w["conv_filter"][j] * u[i - 2 + j]
    hres = x + (c * conv) @ w["conv_out"]
    want = hres + _experts_by_hand(_norm(hres, w["ffn_norm"]), w)
    np.testing.assert_allclose(_layer(made, 3, x, "conv"), want,
                               atol=2e-4, rtol=2e-4)


def test_an_attention_layer_by_hand(pair):
    """Layer 2 (attention, experts): per-head q/k norms before RoPE at
    theta 1e6, causal softmax at 1/sqrt(16), no gate, no window."""
    _, made = pair
    w = _np(lfm2_arch.layer_weights(made, 2))
    t, nh, nkv, hd = 12, 4, 2, 16
    x = np.random.default_rng(1).normal(size=(t, 64))
    y = _norm(x, w["op_norm"])
    q = _norm((y @ w["q"]).reshape(t, nh, hd), w["q_norm"])
    k = _norm((y @ w["k"]).reshape(t, nkv, hd), w["k_norm"])
    v = (y @ w["v"]).reshape(t, nkv, hd)
    inv = 1.0 / 1e6 ** (np.arange(0, hd, 2) / hd)

    def rot(a, pos):
        cs, sn = np.cos(pos * inv), np.sin(pos * inv)
        a1, a2 = a[..., :hd // 2], a[..., hd // 2:]
        return np.concatenate([a1 * cs - a2 * sn, a2 * cs + a1 * sn], -1)

    attn = np.zeros((t, nh, hd))
    for i in range(t):
        for head in range(nh):
            kv = head // (nh // nkv)
            sc = np.array([rot(q[i, head], i) @ rot(k[j, kv], j)
                           for j in range(i + 1)]) / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            attn[i, head] = (p / p.sum()) @ v[:i + 1, kv]
    hres = x + attn.reshape(t, -1) @ w["o"]
    want = hres + _experts_by_hand(_norm(hres, w["ffn_norm"]), w)
    np.testing.assert_allclose(_layer(made, 2, x, "full_attention"), want,
                               atol=2e-4, rtol=2e-4)


def test_weights_are_a_function_of_the_seed(pair):
    _, made = pair
    again = weights_lfm2.make_weights(TINY, 2**31 + 11, "float32")
    other = weights_lfm2.make_weights(TINY, 12, "float32")
    assert all(np.array_equal(made[k], again[k]) for k in made)
    assert not np.array_equal(made["embed"], other["embed"])
    assert "head" not in made                            # tied
    assert made["layers.0.conv_in"].shape == (64, 192)
    assert made["layers.0.conv_filter"].shape == (3, 64)
    assert made["layers.2.q"].shape == (64, 64)
    assert made["layers.2.k"].shape == (64, 2 * 16)
    assert made["layers.3.experts_gate"].shape == (4, 64, 32)
    assert made["layers.3.router"].shape == (64, 8)
    assert float(jnp.abs(made["layers.3.router_bias"]).max()) > 0
    # every tap of the filter counts: unit gain, a third each
    assert 0.3 < float(jnp.std(made["layers.0.conv_filter"])) < 0.9
    assert set(made) == set(weights_lfm2.reference_names(TINY))


def test_logits_agree_with_the_program(pair):
    model, made = pair
    ids = np.random.default_rng(0).integers(1, 256, 50).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)[None])[0])
    want = np.asarray(lfm2_arch.logits(made, TINY, ids))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


def test_the_controls_move_the_logits(pair):
    _, made = pair
    ids = np.arange(1, 41, dtype=np.int32)
    a = np.asarray(lfm2_arch.logits(made, TINY, ids))
    b = np.asarray(lfm2_arch.logits(made, TINY, ids, weight_bits=8))
    c = np.asarray(lfm2_arch.logits(made, TINY, ids, history=False))
    assert 1e-4 < np.abs(a - b).max() < 1.0
    # without history every position moves, the first among them only
    # through attention's view of later layers... it has no predecessor:
    # position 0 is the one a zeroed state computes right
    assert np.abs(a[0] - c[0]).max() < 1e-5
    assert np.abs(a[1:] - c[1:]).max() > 1e-2
