"""The plain reference of the latent-attention MoE architecture (the
``deepseek_v3`` key set; JoyAI-LLM-Flash): pre-norm RMS norms (two a layer,
a final one), multi-head latent attention in its PLAIN form, a SwiGLU
feed-forward in the leading dense layers and in the others routed experts
(sigmoid scores, a selection bias, the best ``num_experts_per_tok`` of all
experts, weights renormalised and scaled) beside a shared expert, an untied
head — in ``jax.numpy``, float32, matmuls at precision "highest", no
kernel, no cache, no batching.  It imports nothing of the program.

With ``h`` the normed block input of one token (H heads; n =
``qk_nope_head_dim``, r = ``qk_rope_head_dim``, v = ``v_head_dim``, c =
``kv_lora_rank``)::

    c_q             = N_q(h W_dq);       [q_nope ‖ q_r] = c_q W_uq   (H x (n + r))
    [c_kv ‖ k_r]    = h W_dkv;           c = N_kv(c_kv)
    q_rope, k_rope  = RoPE(q_r), RoPE(k_r)         one k_rope for every head
    [k_nope_h ‖ v_h] = c W_ukv                     (n + v a head)
    s_h(i, j) = (q_nope_h(i).k_nope_h(j) + q_rope_h(i).k_rope(j)) / sqrt(n + r)
    o_h = sum_j softmax_{j<=i}(s_h)(i, j) v_h(j);  out = concat_h(o_h) W_o

RoPE (theta ``rope_theta``, no scaling) turns lanes (2i, 2i+1) as a pair
(``rope_interleave``) by position x theta^(-2i/r) and leaves them in place.
Every up-projection is applied to every position: nothing is absorbed,
nothing cached.  The rules the published config's keys do not state are
listed under ``assumed`` in the configuration's file.

Weights are a flat dict under the benchmark's own names
(``benchmark/harness/weights_mla.py`` makes them from the seed), every
matrix in (in, out) layout:

    embed (V, H); norm (H,); head (H, V)
    layers.<i>.{in_norm, post_norm} (H,)
    layers.<i>.q_a (H, ql); q_a_norm (ql,); q_b (ql, nh*(n+r))
    layers.<i>.kv_a (H, c+r); kv_a_norm (c,); kv_b (c, nh*(n+v)); o (nh*v, H)
    dense layers:  layers.<i>.{gate, up} (H, F); down (F, H)
    expert layers: layers.<i>.router (H, E_routed); router_bias (E_routed,)
                   layers.<i>.experts_{gate, up} (E_held, H, Fm); experts_down (E_held, Fm, H)
                   layers.<i>.shared_{gate, up} (H, Fm*n_shared); shared_down

**One chip's share of an expert-parallel deployment**, as in
``afmoe_arch.py``: the router scores all ``n_experts_routed`` experts; the
stacked expert weights hold the experts ``[ep_rank, ep_rank + 1) *
n_routed_experts`` (``n_routed_experts`` being the number held).  The
layer's result is the shared expert plus the weighted sum over the CHOSEN
experts that are HELD; what the absent ones would add is left out.

Computed in blocks so that a 12k-token sequence fits beside 9.6 GB of
resident weights: attention walks the queries in blocks of QUERY_BLOCK; the
held experts are applied one at a time (a scan over the stack); the head is
taken of the rows that are read alone (``rows``: 12k x 129,280 float32
logits would be 6 GB).

The CONTROL ``weight_bits=8`` (every matrix rounded to symmetric int8, one
scale per output channel) exists so that ``correct`` can be shown to fail;
no run of the benchmark uses it.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


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


def rope_tables(t, width, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32)
                           / width))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)   # (T, width/2)
    return jnp.cos(ang), jnp.sin(ang)


def rotate_pairs(x, cos, sin):
    """x (T, ..., r): lanes (2i, 2i+1) turn as a pair by the angle of the
    token's position and frequency i, and stay where they are."""
    mid = (1,) * (x.ndim - 2)
    c = cos.reshape(cos.shape[0], *mid, -1)
    s = sin.reshape(sin.shape[0], *mid, -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _attention(q, k, v, scale):
    """Causal attention, q (T, nh, dk), k (T, nh, dk), v (T, nh, dv), one
    block of QUERY_BLOCK query rows at a time against every key."""
    t, nh, dk = q.shape
    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, nh, dk)
    rows = jnp.arange(t + pad).reshape(-1, blk)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        s = jnp.einsum("thd,shd->hts", qi, k) * scale
        s = jnp.where((ri[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (qb, rows))
    return out.reshape(t + pad, nh, -1)[:t]


def attention(y, w, cos, sin, cfg, weight_bits=None):
    """Latent attention, plain form, of the normed block input y (T, H)."""
    t = y.shape[0]
    nh = cfg["num_attention_heads"]
    n, r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    c = cfg["kv_lora_rank"]
    eps = float(cfg["rms_norm_eps"])
    c_q = rms_norm(y @ _mat(w["q_a"], weight_bits), w["q_a_norm"], eps)
    q = (c_q @ _mat(w["q_b"], weight_bits)).reshape(t, nh, n + r)
    kv = y @ _mat(w["kv_a"], weight_bits)
    lat = rms_norm(kv[:, :c], w["kv_a_norm"], eps)
    up = (lat @ _mat(w["kv_b"], weight_bits)).reshape(t, nh, -1)
    k_rope = rotate_pairs(kv[:, c:], cos, sin)                     # (T, r)
    q = jnp.concatenate(
        [q[..., :n], rotate_pairs(q[..., n:], cos, sin)], axis=-1)
    k = jnp.concatenate(
        [up[..., :n], jnp.broadcast_to(k_rope[:, None], (t, nh, r))],
        axis=-1)
    a = _attention(q, k, up[..., n:], (n + r) ** -0.5)
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
    idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                        cfg["num_experts_per_tok"])[1]
    wgt = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        wgt = wgt / (wgt.sum(-1, keepdims=True) + 1e-20)
    return idx, wgt * float(cfg["routed_scaling_factor"])


def routed_experts(y, w, cfg, weight_bits=None):
    """This chip's share of the routed experts on the normed block input y
    (T, H): the weighted sum over the chosen experts that are held."""
    idx, wgt = route(y, w["router"], w["router_bias"], cfg, weight_bits)
    lo = cfg["ep_rank"] * w["experts_gate"].shape[0]
    held = lo + jnp.arange(w["experts_gate"].shape[0])       # (E_held,)
    per_held = jnp.sum(
        jnp.where(idx[:, :, None] == held[None, None, :],
                  wgt[:, :, None], 0.0), axis=1)             # (T, E_held)

    def one(carry, args):
        gate, up, down, col = args
        return carry + col[:, None] * swiglu(y, gate, up, down,
                                             weight_bits), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        w["experts_gate"], w["experts_up"], w["experts_down"], per_held.T))
    return out


def expert_layer(y, w, cfg, weight_bits=None):
    """Shared expert (whole) plus this chip's share of the routed ones."""
    return routed_experts(y, w, cfg, weight_bits) + swiglu(
        y, w["shared_gate"], w["shared_up"], w["shared_down"], weight_bits)


def decoder_layer(x, w, cos, sin, *, cfg, dense, weight_bits=None):
    """One block on x (T, H) float32; ``w`` holds this layer's arrays under
    their short names; ``cfg`` is the hashable view ``_static`` makes."""
    cfg = dict(cfg)
    eps = float(cfg["rms_norm_eps"])
    h = x + attention(rms_norm(x, w["in_norm"], eps), w, cos, sin, cfg,
                      weight_bits)
    y = rms_norm(h, w["post_norm"], eps)
    if dense:
        return h + swiglu(y, w["gate"], w["up"], w["down"], weight_bits)
    return h + expert_layer(y, w, cfg, weight_bits)


def _head(x, norm_w, head_w, *, eps, weight_bits=None):
    return rms_norm(x, norm_w, eps) @ _mat(head_w, weight_bits)


def layer_weights(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


_USED = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "kv_lora_rank", "rms_norm_eps", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor")


def _static(cfg):
    """The keys a layer reads, as a hashable tuple (a jit static)."""
    return tuple(sorted([(k, cfg[k]) for k in _USED]
                        + [("ep_rank", int(cfg.get("ep_rank", 0)))]))


@functools.lru_cache(maxsize=None)
def _jitted(static, dense, weight_bits):
    """One kind of layer, jitted (cached so that every layer of a kind and
    every sequence of one length share a compilation)."""
    return jax.jit(functools.partial(decoder_layer, cfg=static, dense=dense,
                                     weight_bits=weight_bits))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps, weight_bits):
    return jax.jit(functools.partial(_head, eps=eps,
                                     weight_bits=weight_bits))


def hidden_states(weights, cfg, ids, *, weight_bits=None):
    """Final-layer residual stream (T, H) float32 of one sequence ``ids``
    (T,), before the last norm; one jitted call per layer."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        cos, sin = rope_tables(ids.shape[0], cfg["qk_rope_head_dim"],
                               float(cfg["rope_theta"]))
        x = weights["embed"][ids].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            layer = _jitted(_static(cfg), i < cfg["first_k_dense_replace"],
                            weight_bits)
            x = layer(x, layer_weights(weights, i), cos, sin)
        return x


def logits(weights, cfg, ids, *, weight_bits=None, rows=None):
    """Float32 logits of one sequence: the full causal forward pass, the
    head over ``rows`` (a slice of positions, cut from the hidden states
    BEFORE the head) or over every position."""
    x = hidden_states(weights, cfg, ids, weight_bits=weight_bits)
    if rows is not None:
        x = x[rows]
    with jax.default_matmul_precision("highest"):
        head = _jitted_head(float(cfg["rms_norm_eps"]), weight_bits)
        return head(x, weights["norm"], weights["head"])
