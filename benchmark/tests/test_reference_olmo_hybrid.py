"""The plain Olmo-Hybrid reference: a Gated DeltaNet layer and an attention
layer written out by hand in numpy (token by token, tap by tap, head by
head, in float64: an independent formulation), against the program's
``OlmoHybridForCausalLM`` at a tiny size, and its two controls; CPU,
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights_olmo_hybrid
from benchmark.reference import olmo_hybrid_arch as arch
from tiny_olmo_hybrid import TINY


@pytest.fixture(scope="module")
def pair():
    from benchmark.harness.serve_olmo_hybrid import build_model
    return build_model(TINY, 2**31 + 11, TINY["max_position_embeddings"])


def _np(w):
    return {k: np.asarray(v, np.float64) for k, v in w.items()}


def _norm(x, w, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _mlp_by_hand(h, w):
    fed = (_silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    return h + _norm(fed, w["mlp_norm"])


def _layer(made, i, x, kind):
    with jax.default_matmul_precision("highest"):
        return np.asarray(arch.decoder_layer(
            jnp.asarray(x, jnp.float32), arch.layer_weights(made, i),
            cfg=arch._static(TINY), kind=kind))


def test_a_gated_deltanet_layer_by_hand(pair):
    """Layer 1 of the tiny model on 20 tokens in float64 loops: the
    convolution as a sum over taps a token, the state a matrix a head,
    decayed, corrected by the delta rule and read, a token at a time."""
    _, made = pair
    w = _np(arch.layer_weights(made, 1))
    t, hl, dk, dv = 20, 4, 16, 32
    x = np.random.default_rng(0).normal(size=(t, 64))
    u = x @ w["in"]
    c = np.zeros_like(u)
    for i in range(t):
        for j in range(4):              # tap j weighs u_{i-3+j}
            if i - 3 + j >= 0:
                c[i] += w["conv"][j] * u[i - 3 + j]
    c = _silu(c)
    q = c[:, :hl * dk].reshape(t, hl, dk)
    k = c[:, hl * dk:2 * hl * dk].reshape(t, hl, dk)
    v = c[:, 2 * hl * dk:].reshape(t, hl, dv)
    beta = 2.0 / (1.0 + np.exp(-(x @ w["b"])))
    g = -np.exp(w["A_log"]) * np.log1p(np.exp(x @ w["a"] + w["dt_bias"]))
    gate = _silu(x @ w["g"]).reshape(t, hl, dv)
    out = np.zeros((t, hl, dv))
    for head in range(hl):
        s = np.zeros((dk, dv))
        for i in range(t):
            qi = q[i, head] / np.sqrt((q[i, head] ** 2).sum() + 1e-6) / 4.0
            ki = k[i, head] / np.sqrt((k[i, head] ** 2).sum() + 1e-6)
            s = np.exp(g[i, head]) * s
            s = s + beta[i, head] * np.outer(ki, v[i, head] - s.T @ ki)
            out[i, head] = _norm(s.T @ qi, w["o_norm"]) * gate[i, head]
    h = x + _norm(out.reshape(t, -1) @ w["out"], w["mixer_norm"])
    np.testing.assert_allclose(_layer(made, 1, x, "linear_attention"),
                               _mlp_by_hand(h, w), atol=2e-4, rtol=2e-4)
    # the seeded decays: heads both forget and remember
    assert np.exp(g).min() < 0.5 < 0.99 < np.exp(g).max() < 1.0


def test_an_attention_layer_by_hand(pair):
    """Layer 3 (attention): q/k norms over the WHOLE projection, no rotary
    embedding, causal softmax at 1/sqrt(16), the norm after the mixer."""
    _, made = pair
    w = _np(arch.layer_weights(made, 3))
    t, nh, hd = 12, 4, 16
    x = np.random.default_rng(1).normal(size=(t, 64))
    q = _norm(x @ w["q"], w["q_norm"]).reshape(t, nh, hd)
    k = _norm(x @ w["k"], w["k_norm"]).reshape(t, nh, hd)
    v = (x @ w["v"]).reshape(t, nh, hd)
    attn = np.zeros((t, nh, hd))
    for i in range(t):
        for head in range(nh):
            sc = np.array([q[i, head] @ k[j, head]
                           for j in range(i + 1)]) / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            attn[i, head] = (p / p.sum()) @ v[:i + 1, head]
    h = x + _norm(attn.reshape(t, -1) @ w["o"], w["mixer_norm"])
    np.testing.assert_allclose(_layer(made, 3, x, "full_attention"),
                               _mlp_by_hand(h, w), atol=2e-4, rtol=2e-4)


def test_weights_are_a_function_of_the_seed(pair):
    _, made = pair
    again = weights_olmo_hybrid.make_weights(TINY, 2**31 + 11, "float32")
    other = weights_olmo_hybrid.make_weights(TINY, 12, "float32")
    assert all(np.array_equal(made[k], again[k]) for k in made)
    assert not np.array_equal(made["embed"], other["embed"])
    assert made["head"].shape == (64, 256)                  # untied
    assert made["layers.0.in"].shape == (64, 4 * (16 + 16 + 32))
    assert made["layers.0.conv"].shape == (4, 256)
    assert made["layers.0.g"].shape == (64, 128)
    assert made["layers.0.o_norm"].shape == (32,)
    assert made["layers.3.q_norm"].shape == (64,)
    a = np.exp(np.asarray(made["layers.0.A_log"]))
    dt = np.log1p(np.exp(np.asarray(made["layers.0.dt_bias"])))
    assert made["layers.0.A_log"].dtype == jnp.float32
    assert (1.0 <= a).all() and (a <= 16.0).all()
    assert (0.00099 <= dt).all() and (dt <= 0.1001).all()
    # every tap of the filter counts: unit gain, a quarter each
    assert 0.35 < float(jnp.std(made["layers.0.conv"])) < 0.65
    assert set(made) == set(weights_olmo_hybrid.reference_names(TINY))


def test_logits_agree_with_the_program(pair):
    model, made = pair
    ids = np.random.default_rng(0).integers(1, 256, 50).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)[None])[0])
    want = np.asarray(arch.logits(made, TINY, ids))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    # the head of the served rows alone is those rows of the whole table
    rows = np.asarray(arch.logits(made, TINY, ids, rows=slice(7, 23)))
    np.testing.assert_array_equal(rows, want[7:23])


def test_the_controls_move_the_logits(pair):
    _, made = pair
    ids = np.arange(1, 41, dtype=np.int32)
    a = np.asarray(arch.logits(made, TINY, ids))
    b = np.asarray(arch.logits(made, TINY, ids, weight_bits=8))
    c = np.asarray(arch.logits(made, TINY, ids, history=False))
    assert 1e-4 < np.abs(a - b).max() < 2.0
    # position 0 has no predecessor: the one a zeroed state computes right
    # (up to attention, which the control leaves its history)
    assert np.abs(a[0] - c[0]).max() < 1e-5
    assert np.abs(a[1:] - c[1:]).max() > 1e-2
