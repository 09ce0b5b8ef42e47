"""The plain latent-attention reference: one attention layer written out by
hand in numpy (token by token, head by head, nothing absorbed), against the
program's ``LatentMoeForCausalLM`` at a tiny size, and its int8 control;
CPU, float32."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_arch
from tiny_mla import TINY


@pytest.fixture(scope="module")
def pair():
    from benchmark.harness.serve_mla import build_model
    return build_model(TINY, 2**31 + 11, TINY["max_position_embeddings"])


def _norm(x, w, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def test_one_attention_layer_by_hand(pair):
    """Layer 1 of the tiny model on 19 tokens in float64 loops: the two
    low-rank paths, the norms on the latents, RoPE over interleaved pairs
    on the RoPE parts alone, ONE rotated key for all heads, the scale of
    the whole query head (nope + rope)."""
    _, made = pair
    w = {k: np.asarray(v, np.float64)
         for k, v in mla_arch.layer_weights(made, 1).items()}
    t, nh, n, r, c, dv = 19, 4, 16, 32, 128, 16
    y = np.random.default_rng(0).normal(size=(t, 64))
    q = (_norm(y @ w["q_a"], w["q_a_norm"]) @ w["q_b"]).reshape(t, nh, n + r)
    kv = y @ w["kv_a"]
    lat, k_r = _norm(kv[:, :c], w["kv_a_norm"]), kv[:, c:]
    up = (lat @ w["kv_b"]).reshape(t, nh, n + dv)
    inv = 1.0 / 10000.0 ** (np.arange(0, r, 2) / r)

    def rot(a, pos):
        out = np.empty_like(a)
        cos, sin = np.cos(pos * inv), np.sin(pos * inv)
        out[0::2] = a[0::2] * cos - a[1::2] * sin
        out[1::2] = a[1::2] * cos + a[0::2] * sin
        return out

    attn = np.zeros((t, nh, dv))
    for i in range(t):
        for h in range(nh):
            sc = np.array([
                q[i, h, :n] @ up[j, h, :n]
                + rot(q[i, h, n:], i) @ rot(k_r[j], j)
                for j in range(i + 1)]) / np.sqrt(n + r)
            p = np.exp(sc - sc.max())
            attn[i, h] = (p / p.sum()) @ up[:i + 1, h, n:]
    want = attn.reshape(t, -1) @ w["o"]
    cos, sin = mla_arch.rope_tables(t, r, 10000.0)
    got = mla_arch.attention(
        jnp.asarray(y, jnp.float32), mla_arch.layer_weights(made, 1), cos,
        sin, TINY)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_reference_matches_the_program_and_the_control_does_not(pair):
    model, made = pair
    ids = np.random.default_rng(1).integers(1, 256, 41).astype(np.int32)
    want = np.asarray(mla_arch.logits(made, TINY, ids))
    got = np.asarray(model(jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() < 2e-4
    low = np.asarray(mla_arch.logits(made, TINY, ids, weight_bits=8))
    assert np.abs(low - want).max() > 1e-3
    # the rows are cut before the head
    rows = slice(30, 40)
    np.testing.assert_allclose(
        np.asarray(mla_arch.logits(made, TINY, ids, rows=rows)), want[rows],
        atol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(mla_arch))
    names = {a.name for node in ast.walk(tree)
             if isinstance(node, ast.Import) for a in node.names}
    names |= {node.module for node in ast.walk(tree)
              if isinstance(node, ast.ImportFrom)}
    assert not any(n and n.startswith(("paddle_tpu", "benchmark"))
                   for n in names), names
