"""The plain reference of the Olmo-Hybrid architecture (``model_type:
olmo_hybrid``, Olmo-Hybrid-7B): three layers in four mix tokens by a Gated
DeltaNet (Yang, Kautz, Hatamizadeh, arXiv:2412.06464, as
``flash-linear-attention``'s ``GatedDeltaNet`` has it), the fourth by plain
multi-head softmax attention with q/k norms and NO rotary embedding; a
SwiGLU in every layer; the Olmo 2 / Olmo 3 "reordered norm" (the RMS norm
follows the sub-layer); a final norm and an untied head — in ``jax.numpy``,
float32, matmuls at precision "highest", THE RECURRENCE WRITTEN AS THE
RECURRENCE (a ``lax.scan`` a token), no kernel, no cache, no batching.  It
imports nothing of the program.  The rules the published config's keys do
not state are listed under ``assumed`` in the configuration's file.

With ``x`` the layer's input (T, H), ``N(.)`` an RMS norm with learned
weight::

    h  = x + N_mix(Mixer(x))                      (one line: ``_after_mixer``)
    x' = h + N_mlp(W_down(silu(W_gate h) * W_up h))   (``_after_mlp``)

    full_attention:  q = N_q(x W_q), k = N_k(x W_k) over the WHOLE
        projection, v = x W_v; heads of hidden/heads; causal softmax at
        1/sqrt(head); W_o.  No position encoding.

    linear_attention, per head, d_k and d_v wide:
        [q~ | k~ | v~] = x W_in;  c_t = silu(sum_{j<L} w_j * u_{t-(L-1)+j})
        q_t = c^q/|c^q| * d_k^-1/2;  k_t = c^k/|c^k|    (1e-6 under the root)
        beta_t = 2 sigmoid(x_t W_b)        (linear_allow_neg_eigval: the 2)
        g_t = -exp(A_log) * softplus(x_t W_a + dt_bias)
        S~ = exp(g_t) S_{t-1};  r_t = v_t - S~^T k_t
        S_t = S~ + beta_t k_t r_t^T;  o_t = S_t^T q_t        (S_0 = 0)
        Mixer = [N_o(o_t) * silu(x_t W_g)]_heads W_o

Weights are a flat dict under the benchmark's own names
(``benchmark/harness/weights_olmo_hybrid.py`` makes them from the seed),
every matrix in (in, out) layout:

    embed (V, H); norm (H,); head (H, V)
    layers.<i>.{mixer_norm, mlp_norm} (H,); {gate, up} (H, F); down (F, H)
    attention layers: layers.<i>.{q, k, v} (H, heads*hd); o (heads*hd, H);
                      {q_norm, k_norm} (heads*hd,)
    linear layers:    layers.<i>.in (H, C), C = Hl*(2 d_k + d_v);
                      conv (L, C); g (H, Hl*d_v); {a, b} (H, Hl);
                      {A_log, dt_bias} (Hl,) float32; o_norm (d_v,);
                      out (Hl*d_v, H)

Attention walks the queries in blocks of QUERY_BLOCK so that a 4k-token
sequence fits beside 8 GB of resident weights; ``logits(..., rows=)`` takes
the head of the served rows alone (a 4k x 100k logit table is 1.6 GB).

Three CONTROLS, which exist so that ``correct`` can be shown to fail; no
run of the benchmark uses them.  ``weight_bits=8``: every matrix is rounded to
symmetric int8 with one scale per output channel before use.
``history=False``: every token sees ``S = 0`` and an empty convolution
window, ``c_t = silu(w_{L-1} * u_t)``, ``o_t = beta_t (k_t . q_t) v_t`` —
what a program computes that lost or zeroed its per-request state.
``state_dtype="bfloat16"``: ``S`` is rounded to that type after every
token, which is what a program computes that keeps a narrower state.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


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


def delta_rule(q, k, v, g, beta, state_dtype=None):
    """The gated delta rule, a token at a time from ``S_0 = 0``: q, k (T,
    Hl, d_k), v (T, Hl, d_v), g, beta (T, Hl) -> o (T, Hl, d_v).
    ``state_dtype`` (a control): the type ``S`` is kept in between tokens."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        r = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + b_t[:, None, None] * k_t[:, :, None] * r[:, None, :]
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def gated_delta_net(x, w, cfg, history=True, weight_bits=None,
                    state_dtype=None):
    """The linear-attention mixer of the layer input x (T, H) -> (T, H)."""
    t = x.shape[0]
    hl, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    u = x @ _mat(w["in"], weight_bits)                       # (T, C)
    taps = w["conv"].astype(jnp.float32)                     # (L, C)
    n = taps.shape[0]
    c = taps[n - 1] * u
    if history:
        for back in range(1, n):        # tap L-1-back weighs u_{t-back}
            c = c + taps[n - 1 - back] * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    c = jax.nn.silu(c)
    q, k, v = jnp.split(c, [hl * dk, 2 * hl * dk], axis=-1)

    def unit(z):
        z = z.reshape(t, hl, dk)
        return z * jax.lax.rsqrt(jnp.sum(z * z, -1, keepdims=True) + L2_EPS)
    q, k, v = unit(q) * dk ** -0.5, unit(k), v.reshape(t, hl, dv)
    beta = jax.nn.sigmoid(x @ _mat(w["b"], weight_bits))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        x @ _mat(w["a"], weight_bits) + w["dt_bias"].astype(jnp.float32))
    if history:
        o = delta_rule(q, k, v, g, beta, state_dtype)
    else:
        # S_{t-1} = 0: S_t = beta k v^T, o = S_t^T q
        o = (beta * jnp.sum(k * q, axis=-1))[:, :, None] * v
    o = rms_norm(o, w["o_norm"], float(cfg["rms_norm_eps"]))
    gate = jax.nn.silu(x @ _mat(w["g"], weight_bits))
    return (o.reshape(t, hl * dv) * gate) @ _mat(w["out"], weight_bits)


def _attention(q, k, v):
    """Causal multi-head attention, q, k, v (T, heads, hd), one block of
    QUERY_BLOCK query rows at a time against every key."""
    t, nh, hd = q.shape
    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, nh, hd)
    rows = jnp.arange(t + pad).reshape(-1, blk)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        s = jnp.einsum("thd,shd->hts", qi, k) / (hd ** 0.5)
        s = jnp.where((ri[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, (qb, rows)).reshape(t + pad, nh, hd)[:t]


def attention(x, w, cfg, weight_bits=None):
    """Attention of the layer input x (T, H): returns (T, H).  K/V heads
    fewer than the query heads are repeated (the published model has as
    many)."""
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = float(cfg["rms_norm_eps"])
    q = rms_norm(x @ _mat(w["q"], weight_bits), w["q_norm"], eps)
    k = rms_norm(x @ _mat(w["k"], weight_bits), w["k_norm"], eps)
    v = x @ _mat(w["v"], weight_bits)
    k = jnp.repeat(k.reshape(t, nkv, -1), nh // nkv, axis=1)
    v = jnp.repeat(v.reshape(t, nkv, -1), nh // nkv, axis=1)
    a = _attention(q.reshape(t, nh, -1), k, v)
    return a.reshape(t, -1) @ _mat(w["o"], weight_bits)


def swiglu(x, gate, up, down, weight_bits=None):
    g = x @ _mat(gate, weight_bits)
    u = x @ _mat(up, weight_bits)
    return (jax.nn.silu(g) * u) @ _mat(down, weight_bits)


# the norms' placement, one line a sub-layer (assumed: the reordered norm)
def _after_mixer(x, mixed, w, eps):
    return x + rms_norm(mixed, w["mixer_norm"], eps)


def _after_mlp(h, fed, w, eps):
    return h + rms_norm(fed, w["mlp_norm"], eps)


def decoder_layer(x, w, *, cfg, kind, history=True, weight_bits=None,
                  state_dtype=None):
    """One block on x (T, H) float32; ``w`` holds this layer's arrays under
    their short names; ``cfg`` is the hashable view ``_static`` makes."""
    cfg = dict(cfg)
    eps = float(cfg["rms_norm_eps"])
    mixed = (attention(x, w, cfg, weight_bits) if kind == FULL
             else gated_delta_net(x, w, cfg, history, weight_bits,
                                  state_dtype))
    h = _after_mixer(x, mixed, w, eps)
    return _after_mlp(h, swiglu(h, w["gate"], w["up"], w["down"],
                                weight_bits), w, eps)


def _head(x, norm_w, head, *, eps, weight_bits=None):
    return rms_norm(x, norm_w, eps) @ _mat(head, weight_bits)


def layer_weights(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


_USED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "rms_norm_eps", "linear_num_key_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_allow_neg_eigval")


def _static(cfg):
    """The keys a layer reads, as a hashable tuple (a jit static)."""
    return tuple(sorted((k, cfg[k]) for k in _USED))


@functools.lru_cache(maxsize=None)
def _jitted(static, kind, history, weight_bits, state_dtype=None):
    """One kind of layer, jitted (cached so that every layer of a kind and
    every sequence of one length share a compilation)."""
    return jax.jit(functools.partial(
        decoder_layer, cfg=static, kind=kind, history=history,
        weight_bits=weight_bits, state_dtype=state_dtype))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps, weight_bits):
    return jax.jit(functools.partial(_head, eps=eps,
                                     weight_bits=weight_bits))


def hidden_states(weights, cfg, ids, *, weight_bits=None, history=True,
                  state_dtype=None):
    """Final-layer residual stream (T, H) float32 of one sequence ``ids``
    (T,), before the last norm; one jitted call per layer."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = weights["embed"][ids].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            layer = _jitted(_static(cfg), cfg["layer_types"][i],
                            bool(history), weight_bits, state_dtype)
            x = layer(x, layer_weights(weights, i))
        return x


def logits(weights, cfg, ids, *, rows=None, weight_bits=None, history=True,
           state_dtype=None):
    """Float32 logits of one sequence, the full causal forward pass: (T, V),
    or with ``rows`` (a slice) of those rows alone — the layers see the
    whole sequence, the head only what is read."""
    x = hidden_states(weights, cfg, ids, weight_bits=weight_bits,
                      history=history, state_dtype=state_dtype)
    if rows is not None:
        x = x[rows]
    with jax.default_matmul_precision("highest"):
        head = _jitted_head(float(cfg["rms_norm_eps"]), weight_bits)
        return head(x, weights["norm"], weights["head"])
