"""The plain reference of the LFM2-MoE architecture (``model_type:
lfm2_moe``, LFM2-8B-A1B): two RMS norms a layer (pre-norm), a token mixer
that is a gated short convolution on ``conv`` layers and grouped-query
attention with per-head q/k RMS norms and rotary embedding (half rotation)
on ``full_attention`` layers, a SwiGLU feed-forward in the leading dense
layers and in the others routed experts (sigmoid scores, a selection bias,
the best ``num_experts_per_tok`` of all experts, weights renormalised), a
final norm and the embedding transposed as the head — in ``jax.numpy``,
float32, matmuls at precision "highest", no kernel, no cache, no batching.
It imports nothing of the program.  The rules the published config's keys do
not state are listed under ``assumed`` in the configuration's file.

With ``y`` the normed block input (T, H) and ``L = conv_L_cache``::

    conv:  [B, C, X] = split_3(y W_in);  u = B * X
           c_t = sum_{j=0..L-1} k_j * u_{t-(L-1)+j}     (u_s = 0 for s < 0)
           Op  = (C * c) W_out

The convolution is computed over the whole sequence by shifted sums: no
state is carried, which is what the program's cache form has to agree with.

Weights are a flat dict under the benchmark's own names
(``benchmark/harness/weights_lfm2.py`` makes them from the seed), every
matrix in (in, out) layout:

    embed (V, H); norm (H,)
    layers.<i>.{op_norm, ffn_norm} (H,)
    conv layers:      layers.<i>.conv_in (H, 3H); conv_filter (L, H);
                      conv_out (H, H)
    attention layers: layers.<i>.q (H, nh*hd); {k, v} (H, nkv*hd);
                      o (nh*hd, H); {q_norm, k_norm} (hd,)
    dense layers:     layers.<i>.{gate, up} (H, F); down (F, H)
    expert layers:    layers.<i>.router (H, E_routed); router_bias (E_routed,)
                      layers.<i>.experts_{gate, up} (E_held, H, Fm);
                      experts_down (E_held, Fm, H)

**One chip's share of an expert-parallel deployment.**  The router scores
all ``num_experts_routed`` experts; the stacked expert weights hold the
experts ``[ep_rank, ep_rank + 1) * num_experts`` (``num_experts`` being the
number held).  The layer's result is the weighted sum over the CHOSEN
experts that are HELD; what the absent ones would add is left out, and that
partial result goes on to the next layer.  With every expert held this is
the published layer.

Computed in blocks so that a 4k-token sequence fits beside 9 GB of resident
weights: attention walks the queries in blocks of QUERY_BLOCK; the held
experts are applied one at a time (a scan over the stack, so a bf16 expert
is upcast alone), each to every token, weighted by the (mostly zero)
routing weight.

Two CONTROLS, which exist so that ``correct`` can be shown to fail; no run
of the benchmark uses them.  ``weight_bits=8``: every matrix is rounded to
symmetric int8 with one scale per output channel before use.
``history=False``: the convolution sees no earlier input, ``c_t = k_{L-1} *
u_t`` — what a program computes that lost or zeroed its per-request state.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
CONV, FULL = "conv", "full_attention"
ROUTE_EPS = 1e-6


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _fake_quant(w, bits):
    """Symmetric per-output-channel rounding of an (..., in, out) matrix."""
    if bits is None:
        return w
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


def _mat(w, bits):
    return _fake_quant(w.astype(jnp.float32), bits)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope_tables(t, hd, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)   # (T, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """x (T, heads, hd): rotate the two halves of each head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def short_conv(y, w, history=True, weight_bits=None):
    """The gated short convolution of the normed block input y (T, H), by
    shifted sums over the whole sequence.  ``history=False`` is the
    control: the filter's last tap alone."""
    t = y.shape[0]
    b, c, x = jnp.split(y @ _mat(w["conv_in"], weight_bits), 3, axis=-1)
    u = b * x
    k = w["conv_filter"].astype(jnp.float32)                 # (L, H)
    taps = k.shape[0]
    out = k[taps - 1] * u
    if history:
        for back in range(1, taps):     # tap L-1-back weighs u_{t-back}
            shifted = jnp.pad(u, ((back, 0), (0, 0)))[:t]
            out = out + k[taps - 1 - back] * shifted
    return (c * out) @ _mat(w["conv_out"], weight_bits)


def _attention(q, k, v):
    """Causal grouped-query attention, q (T, nkv, g, hd), k/v (T, nkv, hd),
    one block of QUERY_BLOCK query rows at a time against every key."""
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


def attention(y, w, cos, sin, cfg, weight_bits=None):
    """Attention of the normed block input y (T, H): returns (T, H)."""
    t = y.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = float(cfg["norm_eps"])
    q = (y @ _mat(w["q"], weight_bits)).reshape(t, nh, -1)
    k = (y @ _mat(w["k"], weight_bits)).reshape(t, nkv, -1)
    v = (y @ _mat(w["v"], weight_bits)).reshape(t, nkv, -1)
    q = _rotate(rms_norm(q, w["q_norm"], eps), cos, sin)
    k = _rotate(rms_norm(k, w["k_norm"], eps), cos, sin)
    a = _attention(q.reshape(t, nkv, nh // nkv, -1), k, v)
    return a.reshape(t, -1) @ _mat(w["o"], weight_bits)


def swiglu(x, gate, up, down, weight_bits=None):
    g = x @ _mat(gate, weight_bits)
    u = x @ _mat(up, weight_bits)
    return (jax.nn.silu(g) * u) @ _mat(down, weight_bits)


def route(y, router, bias, cfg, weight_bits=None):
    """The router over ALL routed experts: (indices (T, k) int32 of the
    chosen experts, their weights (T, k) float32).  Chosen by score plus
    bias; weighed by the score alone."""
    s = jax.nn.sigmoid(y @ _mat(router, weight_bits))        # (T, E)
    pick = s + bias.astype(jnp.float32) if cfg["use_expert_bias"] else s
    idx = jax.lax.top_k(pick, cfg["num_experts_per_tok"])[1]
    wgt = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        wgt = wgt / (wgt.sum(-1, keepdims=True) + ROUTE_EPS)
    return idx, wgt * float(cfg["routed_scaling_factor"])


def expert_layer(y, w, cfg, weight_bits=None):
    """This chip's share of the routed experts on the normed block input y
    (T, H); no shared expert."""
    idx, wgt = route(y, w["router"], w["router_bias"], cfg, weight_bits)
    lo = cfg["ep_rank"] * w["experts_gate"].shape[0]
    held = lo + jnp.arange(w["experts_gate"].shape[0])       # (E_held,)
    # (T, E_held): the weight with which each held expert enters, 0 where
    # it was not chosen
    per_held = jnp.sum(
        jnp.where(idx[:, :, None] == held[None, None, :],
                  wgt[:, :, None], 0.0), axis=1)

    def one(carry, args):
        gate, up, down, col = args
        return carry + col[:, None] * swiglu(y, gate, up, down,
                                             weight_bits), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        w["experts_gate"], w["experts_up"], w["experts_down"], per_held.T))
    return out


def decoder_layer(x, w, cos, sin, *, cfg, kind, dense, history=True,
                  weight_bits=None):
    """One block on x (T, H) float32; ``w`` holds this layer's arrays under
    their short names; ``cfg`` is the hashable view ``_static`` makes."""
    cfg = dict(cfg)
    eps = float(cfg["norm_eps"])
    y = rms_norm(x, w["op_norm"], eps)
    if kind == FULL:
        h = x + attention(y, w, cos, sin, cfg, weight_bits)
    else:
        h = x + short_conv(y, w, history, weight_bits)
    y = rms_norm(h, w["ffn_norm"], eps)
    if dense:
        return h + swiglu(y, w["gate"], w["up"], w["down"], weight_bits)
    return h + expert_layer(y, w, cfg, weight_bits)


def _head(x, norm_w, embed, *, eps, weight_bits=None):
    return rms_norm(x, norm_w, eps) @ _mat(embed.T, weight_bits)


def layer_weights(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


_USED = ("num_attention_heads", "num_key_value_heads", "norm_eps",
         "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
         "use_expert_bias")


def _static(cfg):
    """The keys a layer reads, as a hashable tuple (a jit static)."""
    return tuple(sorted([(k, cfg[k]) for k in _USED]
                        + [("ep_rank", int(cfg.get("ep_rank", 0)))]))


@functools.lru_cache(maxsize=None)
def _jitted(static, kind, dense, history, weight_bits):
    """One kind of layer, jitted (cached so that every layer of a kind and
    every sequence of one length share a compilation)."""
    return jax.jit(functools.partial(
        decoder_layer, cfg=static, kind=kind, dense=dense, history=history,
        weight_bits=weight_bits))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps, weight_bits):
    return jax.jit(functools.partial(_head, eps=eps,
                                     weight_bits=weight_bits))


def hidden_states(weights, cfg, ids, *, weight_bits=None, history=True):
    """Final-layer residual stream (T, H) float32 of one sequence ``ids``
    (T,), before the last norm; one jitted call per layer."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        cos, sin = rope_tables(ids.shape[0], head_dim(cfg),
                               float(cfg["rope_theta"]))
        x = weights["embed"][ids].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            layer = _jitted(_static(cfg), cfg["layer_types"][i],
                            i < cfg["num_dense_layers"], bool(history),
                            weight_bits)
            x = layer(x, layer_weights(weights, i), cos, sin)
        return x


def logits(weights, cfg, ids, *, weight_bits=None, history=True):
    """Float32 logits (T, V) of one sequence: the full causal forward
    pass."""
    x = hidden_states(weights, cfg, ids, weight_bits=weight_bits,
                      history=history)
    with jax.default_matmul_precision("highest"):
        head = _jitted_head(float(cfg["norm_eps"]), weight_bits)
        return head(x, weights["norm"], weights["embed"])
