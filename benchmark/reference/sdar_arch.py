"""The plain reference of the SDAR-MoE architecture (``model_type:
sdar_moe``, SDAR-30B-A3B-Chat): a pre-norm decoder whose every layer is
grouped-query attention with per-head q/k RMS norms before rotary embedding
(half rotation) followed by routed experts (softmax over all experts, the
best ``num_experts_per_tok``, their probabilities renormalised), a final
norm and an untied head — under the BLOCK-CAUSAL mask of a block-diffusion
language model — in ``jax.numpy``, float32, matmuls at precision
"highest", no kernel, no cache, no batching.  It imports nothing of the
program.  The rules the published config's keys do not state are listed
under ``assumed`` in the configuration's file.

With ``B = block_length``: key ``j`` is visible to the query at position
``i`` iff ``j // B <= i // B``, and the logits at position ``i`` predict the
token AT ``i`` (no shift).  Generation (:func:`generate`) is autoregressive
over blocks and masked diffusion inside one: a new block is ``B`` copies of
``mask_token_id``; a denoising forward runs the committed tokens and the
block under the mask, takes at every still-masked position the best token
other than the mask token and its softmax probability, and unmasks by
confidence (:func:`unmask`); a block that is mask-free is committed and the
next one opened.

**All of a sequence's forwards at once** (:func:`hidden_states` with
``noised``): the clean sequence and noised copies of it side by side.  A
copy's block attends the CLEAN blocks before it and ITSELF as the copy has
it, which is what the denoising forward of that block saw when the copy
holds what it was fed.  Copy ``f`` holding every block as its ``f``-th
forward was fed gives all those forwards' logits in one pass; no cache is
needed, and the clean half is the committed sequence.

Weights are a flat dict under the benchmark's own names
(``benchmark/harness/weights_sdar.py`` makes them from the seed), every
matrix in (in, out) layout:

    embed (V, H); norm (H,); head (H, V)
    layers.<i>.{in_norm, post_norm} (H,); {q_norm, k_norm} (hd,)
    layers.<i>.q (H, nh*hd); {k, v} (H, nkv*hd); o (nh*hd, H)
    layers.<i>.router (H, E_routed)
    layers.<i>.experts_{gate, up} (E_held, H, Fm); experts_down (E_held, Fm, H)

**One chip's share of an expert-parallel deployment**, as the other expert
references have it: the router scores all ``num_experts_routed`` experts;
the stacked expert weights hold the experts ``[ep_rank, ep_rank + 1) *
num_experts`` (``num_experts`` being the number held); the layer's result
is the weighted sum over the CHOSEN experts that are HELD, and that partial
result goes on to the next layer.  With every expert held this is the
published layer.

Three CONTROLS, which exist so that ``correct`` can be shown to fail; no run
of the benchmark uses them.  ``weight_bits=8``: every matrix is rounded to
symmetric int8 with one scale per output channel before use.
``mask="causal"``: key ``j`` visible iff ``j <= i`` — what a program
computes that kept a causal (speculative-window) mask.  The third needs no
switch: handing :func:`hidden_states` as ``clean`` the sequence whose every
block is as its LAST DENOISING forward was fed gives later blocks the K/V
that forward left — what a program computes that skipped the commit
forward (``as_last_fed``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128           # 5 copies x 32 heads x 128 x 4096 keys: 0.3 GB
HEAD_ROWS = 1024            # rows the head is taken of at once
DYNAMIC, STATIC = "low_confidence_dynamic", "low_confidence_static"


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
    """x (..., T, heads, hd): rotate the two halves of each head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, block, causal, length):
    """Attention of C side-by-side copies of one sequence, copy 0 the clean
    one: q (C, T, nkv, g, hd), k/v (C, T, nkv, hd), T a multiple of the
    query block.  A clean query sees the clean keys of its own and earlier
    blocks; a copy's query the clean keys of EARLIER blocks and its own
    block's keys as the copy has them.  ``causal``: a key inside the
    query's own block is seen only if it is not later than the query (the
    control).  Keys at ``length`` and beyond are padding and seen by
    nobody.  One block of query rows at a time."""
    c, t, nkv, g, hd = q.shape
    rb = min(QUERY_BLOCK, t)
    rows = jnp.arange(t).reshape(-1, rb)
    cols = jnp.arange(t)
    scale = hd ** -0.5

    def own(ri, rj):
        """Keys ``rj`` of the query's own block that query ``ri`` sees."""
        same = ((ri[:, None] // block == rj[None, :] // block)
                & (rj[None, :] < length))
        return same & (rj[None, :] <= ri[:, None]) if causal else same

    def one_block(args):
        qi, ri, ki, vi = args          # (C, rb, ...), (rb,), (C, rb, ...)
        before = cols[None, :] // block < ri[:, None] // block   # (rb, T)
        s_clean = jnp.einsum("ctkgd,skd->ckgts", qi, k[0]) * scale
        s_own = jnp.einsum("ctkgd,cskd->ckgts", qi, ki) * scale
        # copy 0 is the clean sequence: its own block IS among the clean
        # keys, so its own-block scores are the clean keys' at those rows
        see_clean = jnp.broadcast_to(before, (c, rb, t)).at[0].set(
            before | own(ri, cols))
        see_own = jnp.broadcast_to(own(ri, ri), (c, rb, rb)).at[0].set(False)
        s = jnp.concatenate(
            [jnp.where(see_clean[:, None, None], s_clean, -jnp.inf),
             jnp.where(see_own[:, None, None], s_own, -jnp.inf)], axis=-1)
        w = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("ckgts,skd->ctkgd", w[..., :t], v[0])
                + jnp.einsum("ckgts,cskd->ctkgd", w[..., t:], vi))

    def blocks(x):                      # (C, T, ...) -> (T/rb, C, rb, ...)
        return jnp.moveaxis(x.reshape(c, -1, rb, *x.shape[2:]), 1, 0)

    out = jax.lax.map(one_block, (blocks(q), rows, blocks(k), blocks(v)))
    return jnp.moveaxis(out, 0, 1).reshape(c, t, nkv, g, hd)


def attention(y, w, cos, sin, cfg, length, mask="block", weight_bits=None):
    """Attention of the normed block input y (C, T, H): returns (C, T, H)."""
    c, t, _ = y.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = float(cfg["rms_norm_eps"])
    q = (y @ _mat(w["q"], weight_bits)).reshape(c, t, nh, -1)
    k = (y @ _mat(w["k"], weight_bits)).reshape(c, t, nkv, -1)
    v = (y @ _mat(w["v"], weight_bits)).reshape(c, t, nkv, -1)
    q = _rotate(rms_norm(q, w["q_norm"], eps), cos, sin)
    k = _rotate(rms_norm(k, w["k_norm"], eps), cos, sin)
    a = _attention(q.reshape(c, t, nkv, nh // nkv, -1), k, v,
                   int(cfg["block_length"]), mask == "causal", length)
    return a.reshape(c, t, -1) @ _mat(w["o"], weight_bits)


def swiglu(x, gate, up, down, weight_bits=None):
    g = x @ _mat(gate, weight_bits)
    u = x @ _mat(up, weight_bits)
    return (jax.nn.silu(g) * u) @ _mat(down, weight_bits)


def route(y, router, cfg, weight_bits=None):
    """The router over ALL routed experts: (indices (..., k) int32 of the
    chosen experts, their weights (..., k) float32): softmax, the k
    largest, renormalised over the chosen where ``norm_topk_prob``."""
    p = jax.nn.softmax(y @ _mat(router, weight_bits), axis=-1)
    wgt, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        wgt = wgt / wgt.sum(-1, keepdims=True)
    return idx, wgt


def expert_layer(y, w, cfg, weight_bits=None):
    """This chip's share of the routed experts on the normed block input y
    (..., H); no shared expert.  The held experts are applied one at a
    time, each to every token, weighted by the (mostly zero) routing
    weight."""
    idx, wgt = route(y, w["router"], cfg, weight_bits)
    lo = cfg["ep_rank"] * w["experts_gate"].shape[0]
    held = lo + jnp.arange(w["experts_gate"].shape[0])       # (E_held,)
    per_held = jnp.sum(
        jnp.where(idx[..., None] == held, wgt[..., None], 0.0), axis=-2)

    def one(carry, args):
        gate, up, down, col = args
        return carry + col[..., None] * swiglu(y, gate, up, down,
                                               weight_bits), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        w["experts_gate"], w["experts_up"], w["experts_down"],
        jnp.moveaxis(per_held, -1, 0)))
    return out


def decoder_layer(x, w, cos, sin, *, cfg, length, mask="block",
                  weight_bits=None):
    """One block on x (C, T, H) float32; ``w`` holds this layer's arrays
    under their short names; ``cfg`` is the hashable view ``_static``
    makes."""
    cfg = dict(cfg)
    eps = float(cfg["rms_norm_eps"])
    h = x + attention(rms_norm(x, w["in_norm"], eps), w, cos, sin, cfg,
                      length, mask, weight_bits)
    return h + expert_layer(rms_norm(h, w["post_norm"], eps), w, cfg,
                            weight_bits)


def _head(x, norm_w, head, *, eps, weight_bits=None):
    return rms_norm(x, norm_w, eps) @ _mat(head, weight_bits)


def layer_weights(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


_USED = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
         "num_experts_per_tok", "norm_topk_prob", "block_length")


def _static(cfg):
    """The keys a layer reads, as a hashable tuple (a jit static)."""
    return tuple(sorted([(k, cfg[k]) for k in _USED]
                        + [("ep_rank", int(cfg.get("ep_rank", 0)))]))


@functools.lru_cache(maxsize=None)
def _jitted(static, length, mask, weight_bits):
    """The layer, jitted (cached so that every layer and every sequence of
    one length share a compilation)."""
    return jax.jit(functools.partial(
        decoder_layer, cfg=static, length=length, mask=mask,
        weight_bits=weight_bits))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps, weight_bits):
    return jax.jit(functools.partial(_head, eps=eps,
                                     weight_bits=weight_bits))


def hidden_states(weights, cfg, clean, noised=None, *, weight_bits=None,
                  mask="block"):
    """Final-layer residual stream (1 + F, T, H) float32, before the last
    norm, of the sequence ``clean`` (T,) under the block mask and of its
    ``noised`` copies (F, T) beside it (module docstring); a T past the
    query block is padded to whole query blocks here and cut again.  One
    jitted call per layer."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(clean, jnp.int32)[None]
        if noised is not None:
            ids = jnp.concatenate([ids, jnp.asarray(noised, jnp.int32)])
        t = ids.shape[1]
        pad = -t % QUERY_BLOCK if t > QUERY_BLOCK else 0
        ids = jnp.pad(ids, ((0, 0), (0, pad)))
        cos, sin = rope_tables(t + pad, int(cfg["head_dim"]),
                               float(cfg["rope_theta"]))
        x = weights["embed"][ids].astype(jnp.float32)
        layer = _jitted(_static(cfg), t, mask, weight_bits)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, layer_weights(weights, i), cos, sin)
        return x[:, :t]


def head_logits(weights, cfg, hidden, *, weight_bits=None):
    """Float32 logits (R, V) of R rows of :func:`hidden_states`' result,
    ``HEAD_ROWS`` at a time: the head is taken of the rows that are read
    and of no others (a whole sequence's logits are gigabytes)."""
    with jax.default_matmul_precision("highest"):
        head = _jitted_head(float(cfg["rms_norm_eps"]), weight_bits)
        hidden = jnp.asarray(hidden)
        pad = -hidden.shape[0] % HEAD_ROWS if hidden.shape[0] > HEAD_ROWS \
            else 0
        rows = jnp.pad(hidden, ((0, pad), (0, 0)))
        out = [head(rows[at:at + HEAD_ROWS], weights["norm"],
                    weights["head"])
               for at in range(0, rows.shape[0], HEAD_ROWS)]
        return jnp.concatenate(out)[:hidden.shape[0]]


def _stats(lg, tokens, mask_token_id):
    cand = jnp.where(jnp.arange(lg.shape[-1]) == mask_token_id, -jnp.inf, lg)
    return (jnp.max(cand, -1), jnp.argmax(cand, -1).astype(jnp.int32),
            jax.nn.logsumexp(cand, -1),
            jnp.take_along_axis(lg, tokens.T, axis=-1).T)


_stats = jax.jit(_stats, static_argnums=2)


def head_stats(weights, cfg, hidden, tokens, *, weight_bits=None):
    """What a comparison reads of R rows' logits without keeping them, the
    head taken ``HEAD_ROWS`` rows at a time: per row the best candidate's
    logit and token (the mask token is never a candidate), the log of the
    candidates' summed exponentials — best minus it is the log of the
    candidate's softmax probability, its confidence — and the logits of
    ``tokens`` (K, R) int, as (K, R)."""
    mask_id = int(cfg["mask_token_id"])
    n = hidden.shape[0]
    pad = -n % HEAD_ROWS        # whole chunks: one compilation a program
    hidden = jnp.pad(jnp.asarray(hidden), ((0, pad), (0, 0)))
    tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), ((0, 0), (0, pad)))
    parts = []
    for at in range(0, hidden.shape[0], HEAD_ROWS):
        lg = head_logits(weights, cfg, hidden[at:at + HEAD_ROWS],
                         weight_bits=weight_bits)
        parts.append(_stats(lg, tokens[:, at:at + HEAD_ROWS], mask_id))
    return tuple(np.concatenate([np.asarray(p[i]) for p in parts],
                                axis=-1)[..., :n] for i in range(4))


def logits(weights, cfg, ids, *, weight_bits=None, mask="block"):
    """Float32 logits (T, V) of one sequence: the full forward pass under
    the block mask, position i's predicting the token AT i."""
    x = hidden_states(weights, cfg, ids, weight_bits=weight_bits, mask=mask)
    return head_logits(weights, cfg, x[0], weight_bits=weight_bits)


# -- generation, as the release does it --------------------------------------

def candidates(block_logits, mask_token_id):
    """Per position of one block's logits (B, V): the best token other
    than the mask token, and the log of its softmax probability."""
    lg = np.array(block_logits, np.float64)
    lg[:, mask_token_id] = -np.inf
    x0 = lg.argmax(-1)
    top = lg.max(-1)
    logp = top - (top + np.log(np.exp(lg - top[:, None]).sum(-1)))
    return x0.astype(np.int64), logp


def unmask(logp, masked, strategy, threshold, per_step):
    """The positions one denoising forward unmasks, from the candidates'
    log-probabilities (B,) and the still-masked positions (B,) bool.
    ``low_confidence_dynamic``: every masked position whose probability
    passes ``threshold``, and the single most confident one if none does;
    ``low_confidence_static``: the ``per_step`` most confident.  Ties go to
    the earlier position."""
    conf = np.where(masked, np.exp(logp), -1.0)
    order = np.argsort(-conf, kind="stable")
    take = np.zeros_like(masked)
    if strategy == STATIC:
        take[order[:per_step]] = True
    elif strategy == DYNAMIC:
        take = conf > threshold
        take[order[0]] = True
    else:
        raise ValueError(f"unknown unmasking strategy {strategy!r}")
    return take & masked


def generate(weights, cfg, prompt, max_new_tokens, *, strategy=None,
             threshold=None, weight_bits=None):
    """Greedy block-diffusion generation of one sequence: (tokens, the
    forward-in-block at which each was unmasked, the logits (B, V) of every
    denoising forward in order).  Every forward is the full pass over the
    committed tokens and the block: no cache."""
    bl, mask_id = int(cfg["block_length"]), int(cfg["mask_token_id"])
    strategy = strategy or cfg["remasking_strategy"]
    threshold = (cfg["confidence_threshold"] if threshold is None
                 else threshold)
    per_step = -(-bl // int(cfg["denoising_steps"]))
    prompt = [int(t) for t in prompt]
    at = len(prompt) - len(prompt) % bl
    seq, given = prompt[:at], prompt[at:]
    tokens, steps, seen = [], [], []
    while len(tokens) < max_new_tokens:
        block = np.array(given + [mask_id] * (bl - len(given)), np.int64)
        when = np.zeros(bl, np.int64)
        forward = 0
        while (block == mask_id).any():
            forward += 1
            lg = np.asarray(logits(weights, cfg, np.array(
                seq + block.tolist(), np.int32),
                weight_bits=weight_bits))[-bl:]
            seen.append(lg)
            x0, logp = candidates(lg, mask_id)
            take = unmask(logp, block == mask_id, strategy, threshold,
                          per_step)
            block[take], when[take] = x0[take], forward
        room = max_new_tokens - len(tokens)
        tokens += block[len(given):][:room].tolist()
        steps += when[len(given):][:room].tolist()
        seq, given = seq + block.tolist(), []
    return tokens, steps, seen


# -- what one request's forwards were fed -----------------------------------

def forwards_fed(seq, steps, cfg):
    """From a whole-block sequence ``seq`` (T,) (prompt and delivered
    tokens) and, per position, the forward-in-block at which it was
    unmasked (0: given by the prompt): the noised copies (B, T), copy
    ``f - 1`` holding every block as its forward ``f`` was fed — the mask
    token where ``steps >= f``, blocks that had fewer forwards clean — and
    the (forward, position) pairs that are READ: every position still
    masked at a forward its block had."""
    bl, mask_id = int(cfg["block_length"]), int(cfg["mask_token_id"])
    seq, steps = np.asarray(seq, np.int32), np.asarray(steps, np.int64)
    last = steps.reshape(-1, bl).max(-1).repeat(bl)       # a block's forwards
    fs = np.arange(1, bl + 1)[:, None]
    fed = np.where((steps[None] >= fs) & (fs <= last[None]), mask_id,
                   seq[None]).astype(np.int32)
    f, pos = np.nonzero((steps[None] >= fs) & (fs <= last[None]))
    return fed, f + 1, pos


def as_last_fed(seq, steps, cfg):
    """``seq`` with every block as its LAST denoising forward was fed: the
    third control's ``clean`` (module docstring)."""
    bl, mask_id = int(cfg["block_length"]), int(cfg["mask_token_id"])
    seq, steps = np.asarray(seq, np.int32), np.asarray(steps, np.int64)
    last = steps.reshape(-1, bl).max(-1).repeat(bl)
    return np.where((steps == last) & (last > 0), mask_id,
                    seq).astype(np.int32)
