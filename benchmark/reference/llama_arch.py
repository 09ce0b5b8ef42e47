"""The plain reference of the llama architecture: RMSNorm, rotary embedding
(half rotation, at the config's theta), grouped-query attention under a
causal mask, SwiGLU, an untied head, and the causal-LM loss — in
``jax.numpy``, float32, matmuls at precision "highest", no kernel, no cache,
no batching.  It imports nothing of the program and follows the published
description (Mistral-7B / Yi-1.5 ``modeling_llama``-style decoder).

Weights are a flat dict under the benchmark's own names
(``benchmark/harness/weights.py`` makes them from the seed):

    embed (V, H); layers.<i>.{in_norm, post_norm} (H,);
    layers.<i>.{q (H, nh*hd), k (H, nkv*hd), v (H, nkv*hd), o (nh*hd, H)};
    layers.<i>.{gate (H, F), up (H, F), down (F, H)}; norm (H,); head (H, V)

every matrix in (in, out) layout.  Each layer is one jitted call, so bf16
weights are upcast one layer at a time and a 16-layer, 4096-wide model fits
beside nothing else on a 16 GB chip.

``weight_bits=8`` turns the reference into the CONTROL: every matrix is
rounded to symmetric int8 with one scale per output channel before use (the
weight-only int8 step a later PR would be tempted by).  It exists so that
``correct`` can be shown to fail; no run of the benchmark uses it.

Departures from the published model: none in the mathematics; the rotary
table is computed in float32 from theta for the positions 0..T-1 of the one
sequence given.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _fake_quant(w, bits):
    """Symmetric per-output-channel rounding of an (in, out) matrix."""
    if bits is None:
        return w
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


def _mat(w, bits):
    return _fake_quant(w.astype(jnp.float32), bits)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope_tables(t, head_dim, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)   # (T, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """x (T, heads, hd): rotate the two halves of each head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v):
    """Causal grouped-query attention, q (T, nkv, g, hd), k/v (T, nkv, hd),
    one block of QUERY_BLOCK query rows at a time against every key (the
    mask removes the future), so the scores never exceed
    (heads, QUERY_BLOCK, T) and one block's program serves them all."""
    t, nkv, g, hd = q.shape
    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, blk, nkv, g, hd)
    rows = jnp.arange(t + pad).reshape(-1, blk)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        s = jnp.einsum("tkgd,skd->kgts", qi, k) / (hd ** 0.5)
        s = jnp.where((ri[:, None] >= cols[None, :])[None, None], s,
                      -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (qb, rows))
    return out.reshape(t + pad, nkv, g, hd)[:t]


def decoder_layer(x, w, cos, sin, *, n_heads, n_kv, eps, weight_bits=None):
    """One decoder block on x (T, H) float32; ``w`` holds this layer's nine
    arrays under their short names."""
    t, _ = x.shape
    h = rms_norm(x, w["in_norm"], eps)
    q = (h @ _mat(w["q"], weight_bits)).reshape(t, n_heads, -1)
    k = (h @ _mat(w["k"], weight_bits)).reshape(t, n_kv, -1)
    v = (h @ _mat(w["v"], weight_bits)).reshape(t, n_kv, -1)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    a = _attention(q.reshape(t, n_kv, n_heads // n_kv, -1), k, v)
    x = x + a.reshape(t, -1) @ _mat(w["o"], weight_bits)
    h = rms_norm(x, w["post_norm"], eps)
    gate = h @ _mat(w["gate"], weight_bits)
    up = h @ _mat(w["up"], weight_bits)
    return x + (jax.nn.silu(gate) * up) @ _mat(w["down"], weight_bits)


def _head(x, norm_w, head_w, *, eps, weight_bits=None):
    return rms_norm(x, norm_w, eps) @ _mat(head_w, weight_bits)


def layer_weights(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


@functools.lru_cache(maxsize=None)
def _jitted(n_heads, n_kv, eps, weight_bits):
    """The two jitted pieces for one architecture (cached so that every
    layer and every sequence of one length shares one compilation)."""
    layer = jax.jit(functools.partial(
        decoder_layer, n_heads=n_heads, n_kv=n_kv, eps=eps,
        weight_bits=weight_bits))
    head = jax.jit(functools.partial(_head, eps=eps,
                                     weight_bits=weight_bits))
    return layer, head


def hidden_states(weights, cfg, ids, *, weight_bits=None):
    """Final-layer residual stream (T, H) float32 of one sequence ``ids``
    (T,), before the last norm; one jitted call per layer."""
    with jax.default_matmul_precision("highest"):
        layer, _ = _jitted(cfg["num_attention_heads"],
                           cfg["num_key_value_heads"],
                           float(cfg["rms_norm_eps"]), weight_bits)
        ids = jnp.asarray(ids, jnp.int32)
        hd = cfg["hidden_size"] // cfg["num_attention_heads"]
        cos, sin = rope_tables(ids.shape[0], hd, float(cfg["rope_theta"]))
        x = weights["embed"][ids].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, layer_weights(weights, i), cos, sin)
        return x


def logits(weights, cfg, ids, *, weight_bits=None):
    """Float32 logits (T, V) of one sequence: the full causal forward
    pass."""
    x = hidden_states(weights, cfg, ids, weight_bits=weight_bits)
    with jax.default_matmul_precision("highest"):
        _, head = _jitted(cfg["num_attention_heads"],
                          cfg["num_key_value_heads"],
                          float(cfg["rms_norm_eps"]), weight_bits)
        return head(x, weights["norm"], weights["head"])


def causal_lm_loss(weights, cfg, ids, labels):
    """Mean next-token cross entropy over positions with ``labels >= 0``;
    differentiable in ``weights`` (used at small sizes: the whole pass is
    traced at once)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        hd = cfg["hidden_size"] // cfg["num_attention_heads"]
        cos, sin = rope_tables(ids.shape[0], hd, float(cfg["rope_theta"]))
        x = weights["embed"][ids].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = decoder_layer(x, layer_weights(weights, i), cos, sin,
                              n_heads=cfg["num_attention_heads"],
                              n_kv=cfg["num_key_value_heads"],
                              eps=float(cfg["rms_norm_eps"]))
        lg = _head(x, weights["norm"], weights["head"],
                   eps=float(cfg["rms_norm_eps"]))
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(
            lg, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
        valid = (labels >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid) / jnp.maximum(valid.sum(), 1.0)
