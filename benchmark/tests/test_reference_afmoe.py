"""The plain AFMoE reference: against a single expert layer written out by
hand in numpy (token by token, expert by expert), against the program's
``AfmoeForCausalLM`` at a tiny size, and its two controls; CPU, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights_afmoe
from benchmark.reference import afmoe_arch
from tiny_afmoe import TINY


@pytest.fixture(scope="module")
def pair():
    from benchmark.harness.serve_afmoe import build_model
    return build_model(TINY, 2**31 + 11, TINY["max_position_embeddings"])


def _np(w):
    return {k: np.asarray(v, np.float64) for k, v in w.items()}


def _norm(x, w, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_one_expert_layer_by_hand(pair):
    """Layer 1 of the tiny model (a window layer with experts; rank 1 of 4
    holds experts 2 and 3 of 8) on 24 tokens, window 16, in float64 loops:
    every rule of the layer once, none of the reference's code."""
    _, made = pair
    w = _np(afmoe_arch.layer_weights(made, 1))
    t, h, nh, nkv, hd, win = 24, 64, 4, 2, 16, 16
    x = np.random.default_rng(0).normal(size=(t, h))
    y = _norm(x, w["in_norm"])
    q = _norm((y @ w["q"]).reshape(t, nh, hd), w["q_norm"])
    k = _norm((y @ w["k"]).reshape(t, nkv, hd), w["k_norm"])
    v = (y @ w["v"]).reshape(t, nkv, hd)
    inv = 1.0 / 10000.0 ** (np.arange(0, hd, 2) / hd)

    def rot(a, pos):
        c, s = np.cos(pos * inv), np.sin(pos * inv)
        a1, a2 = a[..., :hd // 2], a[..., hd // 2:]
        return np.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)

    attn = np.zeros((t, nh, hd))
    for i in range(t):
        for head in range(nh):
            kv = head // (nh // nkv)
            js = [j for j in range(t) if 0 <= i - j < win]
            sc = np.array([rot(q[i, head], i) @ rot(k[j, kv], j)
                           for j in js]) / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            attn[i, head] = (p / p.sum()) @ v[js, kv]
    gate = 1.0 / (1.0 + np.exp(-(y @ w["attn_gate"])))
    hres = x + _norm((attn.reshape(t, -1) * gate) @ w["o"],
                     w["post_attn_norm"])
    z = _norm(hres, w["pre_mlp_norm"])
    s = 1.0 / (1.0 + np.exp(-(z @ w["router"])))
    m = (_silu(z @ w["shared_gate"]) * (z @ w["shared_up"])) \
        @ w["shared_down"]
    for i in range(t):
        chosen = np.argsort(-(s[i] + w["router_bias"]))[:2]
        for e in chosen:
            if 2 <= e < 4:                       # held by rank 1 of 4
                wt = s[i, e] / (s[i, chosen].sum() + 1e-20) * 2.448
                g = _silu(z[i] @ w["experts_gate"][e - 2]) * (
                    z[i] @ w["experts_up"][e - 2])
                m[i] += wt * (g @ w["experts_down"][e - 2])
    want = hres + _norm(m, w["post_mlp_norm"])

    cos, sin = afmoe_arch.rope_tables(t, hd, 10000.0)
    with jax.default_matmul_precision("highest"):
        got = afmoe_arch.decoder_layer(
            jnp.asarray(x, jnp.float32), afmoe_arch.layer_weights(made, 1),
            cos, sin, cfg=afmoe_arch._static(TINY), dense=False,
            sliding=True, window=win)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


def test_weights_are_a_function_of_the_seed(pair):
    _, made = pair
    again = weights_afmoe.make_weights(TINY, 2**31 + 11, "float32")
    other = weights_afmoe.make_weights(TINY, 12, "float32")
    assert all(np.array_equal(made[k], again[k]) for k in made)
    assert not np.array_equal(made["head"], other["head"])
    assert made["layers.1.q"].shape == (64, 4 * 16)      # head_dim, not H/nh
    assert made["layers.1.experts_gate"].shape == (2, 64, 32)
    assert made["layers.1.router"].shape == (64, 8)
    assert float(jnp.abs(made["layers.1.router_bias"]).max()) > 0
    assert set(made) == set(weights_afmoe.reference_names(TINY))


def test_logits_agree_with_the_program(pair):
    model, made = pair
    ids = np.random.default_rng(0).integers(1, 256, 50).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)[None])[0])
    want = np.asarray(afmoe_arch.logits(made, TINY, ids))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


def test_the_controls_move_the_logits(pair):
    _, made = pair
    ids = np.arange(1, 41, dtype=np.int32)
    a = np.asarray(afmoe_arch.logits(made, TINY, ids))
    b = np.asarray(afmoe_arch.logits(made, TINY, ids, weight_bits=8))
    c = np.asarray(afmoe_arch.logits(made, TINY, ids, window=False))
    assert 1e-4 < np.abs(a - b).max() < 1.0
    # without the window nothing changes before position 16, all after
    assert np.abs(a[:16] - c[:16]).max() < 1e-5
    assert np.abs(a[16:] - c[16:]).max() > 1e-2
