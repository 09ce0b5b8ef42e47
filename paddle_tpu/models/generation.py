"""Autoregressive decoding with a pre-allocated KV cache.

TPU-native equivalent of the reference's inference decode loop (upstream
layout: paddle/fluid/inference/ + PaddleNLP's generation_utils — cache-
carrying incremental decode behind ``model.generate``).

Design — everything is shaped for XLA's static-shape compilation model:

  * the cache is ONE stacked array ``(layers, 2, batch, max_len, kv_heads,
    head_dim)`` (k at index 0, v at index 1), pre-allocated once; each step
    writes via ``lax.dynamic_update_slice`` — no concatenation, no shape
    growth, no per-step recompilation.  The stacked layout (vs a per-layer
    pytree) also makes the decode step exportable through ``jit.save`` as a
    plain positional array with a *symbolic* cache-length dimension;
  * the decode loop is a ``lax.scan`` carrying (cache, position, last token,
    done-mask) — one compiled program for the whole generation, the
    while-loop-free form XLA pipelines best;
  * attention over the cache masks key slots ``> position`` explicitly
    (the tail of the cache is uninitialised).  Incremental decode
    (q_len 1) is DMA-bound and runs the XLA math path; *prefill* passes a
    static ``pos=0`` so eligible prompt shapes route through the Pallas
    flash kernel (see llama.py ``LlamaAttention.decode``);
  * EOS handling is maskwise (``done`` flag per row, finished rows emit
    ``pad_token_id``) — no data-dependent control flow.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..nn.layer import Layer as _Layer


def init_kv_cache(config, batch_size: int, max_length: int, dtype=None,
                  quantized: bool = False):
    """Pre-allocated cache: (L, 2, B, max_len, kv_heads, head_dim).

    ``quantized=True`` returns the int8 contiguous cache instead — a
    two-leaf pytree ``{"kv": int8 payload (same shape), "scale": f32
    (L, 2, B, n_gran, kv_heads)}`` with one symmetric absmax scale per
    128-token granule per kv head (one granule spanning the whole row
    when ``max_length`` is not a multiple of 128, keeping tiny test
    shapes usable; the Pallas dequant path needs the 128 alignment, the
    reference path does not).  Same decode_step signature: llama's
    ``LlamaAttention.decode`` detects the dict and quantizes at scatter
    time."""
    dt = dtype if dtype is not None else config.dtype
    shape = (config.num_hidden_layers, 2, batch_size, max_length,
             config.num_key_value_heads, config.head_dim)
    if not quantized:
        return jnp.zeros(shape, dt)
    n_gran = max_length // 128 if max_length % 128 == 0 else 1
    return {"kv": jnp.zeros(shape, jnp.int8),
            "scale": jnp.zeros((shape[0], 2, batch_size, n_gran,
                                config.num_key_value_heads), jnp.float32)}


# canonical home is the ops layer (models depend on ops, never the
# reverse); re-exported here for the existing call sites
from ..ops.attention import cache_mask  # noqa: E402,F401


def sample_tokens(logits, key, temperature=0.0, top_k=None, top_p=None):
    """Next-token selection — ONE implementation shared by the whole-scan
    ``greedy_generate`` path and the serving engine's step function.

    Two trace-time regimes, chosen by the *type* of ``temperature``:

      * **static Python knobs** (the ``generate()`` per-call config):
        compiles the minimal graph for that setting — ``0.0`` is pure
        argmax, ``top_k`` uses the static-k ``lax.top_k``;
      * **traced per-row arrays** (the serving engine: (B,) vectors of
        per-request ``temperature`` / ``top_k`` / ``top_p``): one
        shape-generic program serves every mixture of sampling params
        without retracing.  Row conventions: ``temperature <= 0`` ⇒
        greedy, ``top_k == 0`` and ``top_p == 1.0`` ⇒ off.

    The traced program does only what its rows ask for, by two
    conditionals inside the one program (:data:`SAMPLE_PATHS` names the
    three ways through; :func:`sample_path` is the host's mirror of the
    predicates):

      * the argmax always runs; everything else runs under
        ``any(temperature > 0)`` — a tick whose rows are all greedy (idle
        slots carry temperature 0) returns the argmax and nothing more;
      * inside it, the truncation (:func:`_truncate`: ONE descending sort
        of the scaled logits, shared by top-k and top-p) runs under
        ``any(temperature > 0 and (top_k > 0 or top_p < 1))``; rows that
        only set a temperature go straight to ``categorical``.

    **A row's token never depends on which other rows share its batch**:
    the conditionals decide what is computed, never what a row gets — a
    greedy row gets its argmax, and a row whose knobs are off keeps its
    whole vocabulary, whether or not a neighbour samples or truncates.

    ``logits``: (B, vocab).  Returns int32 (B,).
    """
    logits = logits.astype(jnp.float32)
    if isinstance(temperature, (int, float)):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p is not None:
            logits = _nucleus_mask(logits, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    live = temperature > 0.0

    def sampled():
        # greedy rows ride along through a (well-defined, never-NaN)
        # clamped scale and take their argmax at the end
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        scaled = _truncate(
            scaled, live[:, None],
            None if top_k is None else top_k[:, None],
            None if top_p is None else top_p[:, None])
        samp = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(live, samp.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(live), sampled, lambda: greedy)


# the three ways through sample_tokens' traced branch, lightest first
SAMPLE_PATHS = ("greedy", "categorical", "truncated")


def sample_path(temperature, top_k, top_p) -> int:
    """Which way a call of :func:`sample_tokens` with these per-row
    vectors goes, as an index into :data:`SAMPLE_PATHS` — the device's
    two predicates, evaluated on the host's copies of the same vectors
    (numpy, the dtypes the program is handed), so a scheduler can name a
    tick's path without a readback."""
    live = temperature > 0.0
    if not live.any():
        return 0
    return 2 if (live & ((top_k > 0) | (top_p < 1.0))).any() else 1


def _truncate(scaled, live, top_k, top_p):
    """Per-row top-k and top-p truncation of temperature-scaled logits —
    the ONE copy :func:`sample_tokens` and :func:`_target_probs` share.

    ``scaled``: (..., V); ``live`` (bool: the row samples), ``top_k``
    (int, 0 ⇒ off) and ``top_p`` (float, 1.0 ⇒ off): per-row arrays
    broadcastable against ``scaled[..., :1]``; a knob given as None is
    off for every row.
    Returns ``scaled`` with every token outside a row's kept set at -inf.

    Guarded: the work runs under ``any(live and (top_k > 0 or top_p <
    1))`` and is otherwise skipped whole (``scaled`` comes back as it
    is).  When it runs it sorts ONCE, descending: top-k reads its k-th
    value off the sorted row, and the sorted *masked* row that top-p
    needs is that same sort with the values under the k-th at -inf (same
    values, same order).  Both thresholds are applied row-wise, to the
    rows the guard counts and no others — a row that does not sample, or
    whose ``top_k == 0`` (``k = V``) and ``top_p == 1.0`` (no
    threshold), keeps its whole vocabulary whether or not the work ran
    for a neighbour: a row's result is independent of its batch."""
    if top_k is None and top_p is None:
        return scaled
    vocab = scaled.shape[-1]
    top_k = 0 if top_k is None else top_k
    top_p = 1.0 if top_p is None else top_p
    by_k, by_p = live & (top_k > 0), live & (top_p < 1.0)

    def truncated():
        srt = jnp.sort(scaled, axis=-1)[..., ::-1]               # desc
        # per-row dynamic k: the k-th largest, read off the sort (no
        # static k for lax.top_k to use); an untruncated row keeps V
        k_eff = jnp.where(by_k, jnp.clip(top_k, 1, vocab), vocab)
        floor = jnp.take_along_axis(srt, k_eff - 1, axis=-1)
        srt = jnp.where(srt < floor, -jnp.inf, srt)
        floor = jnp.where(
            by_p, jnp.maximum(floor, _nucleus_floor(srt, top_p)), floor)
        return jnp.where(scaled < floor, -jnp.inf, scaled)

    return jax.lax.cond(jnp.any(by_k | by_p), truncated, lambda: scaled)


def _target_probs(logits, temperature, top_k=None, top_p=None):
    """The target distribution :func:`sample_tokens` samples from, as
    explicit per-token probabilities — the p(x) of the rejection-sampling
    acceptance rule (Leviathan et al. 2023).  Applies EXACTLY the same
    transforms as ``sample_tokens``' traced branch (fp32 cast,
    clamped-temperature scaling, then :func:`_truncate`, the one copy of
    the per-row top-k / top-p mask, under the same guard) and then
    normalises, so accept/resample decisions are made against the same
    distribution the plain step would sample.  As there, a row's
    probabilities are independent of its batch.

    ``logits``: (B, S, V); knobs: (B,) vectors (or static scalars,
    broadcast).  Returns f32 (B, S, V) rows summing to 1."""
    logits = logits.astype(jnp.float32)
    b = logits.shape[0]

    def rows(knob, dtype):
        if knob is None:
            return None
        return jnp.broadcast_to(jnp.asarray(knob, dtype), (b,))[:, None, None]

    t = rows(temperature, jnp.float32)
    scaled = _truncate(logits / jnp.maximum(t, 1e-6), t > 0.0,
                       rows(top_k, jnp.int32), rows(top_p, jnp.float32))
    return jax.nn.softmax(scaled, axis=-1)


def accept_draft_tokens(logits, drafts, draft_mask, key, temperature=0.0,
                        top_k=None, top_p=None, pad_token_id: int = 0,
                        draft_probs=None):
    """Accept-longest-prefix verification for speculative decoding — the
    in-graph half of the serving engine's spec-decode step (the drafter
    lives on the host: serving/drafter.py).

    One verify pass scored the window ``[t, d_1 .. d_{S-1}]`` (current
    token + S-1 proposed drafts) in a single forward, so ``logits[:, j]``
    is the next-token distribution AFTER consuming the window's first
    j+1 tokens.  Each position j samples a token via
    :func:`sample_tokens` (its own ``fold_in(key, j)`` subkey, the same
    per-row temperature/top-k/top-p vectors the plain step uses); a
    draft ``d_{j+1}`` is *verified* when position j's sampled token
    equals it, and the row commits the longest verified prefix plus one
    bonus token — 1 to S tokens per step.

    Acceptance policy: **greedy rows** (``temperature <= 0``) match
    against the argmax, so the committed stream is token-identical to
    plain one-token-per-step greedy decode (the exact-parity case of
    Leviathan et al. 2023).  **Sampled rows** depend on ``draft_probs``:

      * ``draft_probs=None`` (legacy): accept only position 0 — plain
        decode behaviour, no approximation;
      * ``draft_probs`` given — f32 (B, S-1, V), the drafter's proposal
        distribution q per drafted column — full **rejection sampling**:
        draft ``d_j`` is accepted w.p. ``min(1, p(d_j)/q(d_j))`` against
        the target p from :func:`_target_probs`; the first rejected
        column commits a resample from the normalised residual
        ``max(0, p - q)`` instead, and a fully-verified row commits a
        bonus token sampled from the last position's target.  The
        committed stream is distributed EXACTLY as plain sampling
        (Leviathan et al. 2023, Thm 1).  Convention: a column the
        drafter skipped carries an all-zero q row (and
        ``draft_mask=False``), making its residual the plain target —
        the first non-drafted column is an ordinary sample.  One-hot q
        rows express a deterministic proposer (the n-gram drafter):
        accept w.p. min(1, p(d)), residual = p with d removed.

    ``logits``: (B, S, V); ``drafts``: int (B, S-1); ``draft_mask``:
    bool (B, S-1), True where the column holds a real proposal (pad
    columns can never be "verified", even if the model happens to emit
    the pad id).  Returns ``(tokens, n_accepted)``: int32 (B, S) whose
    columns past each row's ``n_accepted`` are ``pad_token_id``, and
    int32 (B,) in [1, S].
    """
    b, s, _ = logits.shape
    out = jnp.stack(
        [sample_tokens(logits[:, j], jax.random.fold_in(key, j),
                       temperature, top_k, top_p) for j in range(s)],
        axis=1)                                            # (B, S)
    if s == 1:
        return out, jnp.ones((b,), jnp.int32)
    match = (out[:, :-1] == drafts) & draft_mask           # (B, S-1)
    if draft_probs is None:
        if isinstance(temperature, (int, float)):
            if temperature > 0.0:
                match = jnp.zeros_like(match)
        else:
            match = match & (temperature <= 0.0)[:, None]
        # longest verified prefix: cumprod zeroes everything past the
        # first mismatch; +1 is the bonus token the last verified
        # position earned
        n = (1 + jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                         axis=1)).astype(jnp.int32)
        keep = jnp.arange(s)[None, :] < n[:, None]
        return jnp.where(keep, out, jnp.int32(pad_token_id)), n
    # rejection sampling: greedy rows keep the exact argmax-match rule
    # (token-identical to plain greedy decode); sampled rows accept
    # d_j w.p. min(1, p/q) — u < p/q  ⇔  u·q < p with u ~ U[0, 1)
    greedy_row = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32) <= 0.0, (b,))    # (B,)
    p = _target_probs(logits[:, :-1], temperature, top_k, top_p)
    q = jnp.asarray(draft_probs, jnp.float32)              # (B, S-1, V)
    d = drafts.astype(jnp.int32)[..., None]
    p_d = jnp.take_along_axis(p, d, axis=-1)[..., 0]       # (B, S-1)
    q_d = jnp.take_along_axis(q, d, axis=-1)[..., 0]
    u = jax.random.uniform(jax.random.fold_in(key, 0x5eed), (b, s - 1))
    acc = jnp.where(greedy_row[:, None], match,
                    (u * q_d < p_d) & draft_mask)
    n = (1 + jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                     axis=1)).astype(jnp.int32)
    # residual resample for the first rejected column; a zero-mass
    # residual (q == p pointwise, or an all-zero pad-column q) falls
    # back to the plain target — both limits are exact
    res = jnp.maximum(p - q, 0.0)
    mass = jnp.sum(res, axis=-1, keepdims=True)
    res = jnp.where(mass > 1e-9, res, p)
    resampled = jax.random.categorical(
        jax.random.fold_in(key, 0x7e5a),
        jnp.log(res + 1e-30), axis=-1).astype(jnp.int32)   # (B, S-1)
    # committed row: accepted drafts verbatim, then ONE fresh token at
    # column n-1 (residual resample, or the bonus sample when every
    # draft survived), pad after.  Greedy rows take the legacy ``out``
    # columns — identical tokens by the match rule.
    cand = jnp.concatenate([resampled, out[:, -1:]], axis=1)   # (B, S)
    cand = jnp.where(greedy_row[:, None], out, cand)
    drafts_pad = jnp.concatenate(
        [drafts.astype(jnp.int32),
         jnp.full((b, 1), pad_token_id, jnp.int32)], axis=1)
    col = jnp.arange(s)[None, :]
    toks = jnp.where(col < (n - 1)[:, None], drafts_pad,
                     jnp.where(col == (n - 1)[:, None], cand,
                               jnp.int32(pad_token_id)))
    return toks, n


UNMASK_STRATEGIES = ("low_confidence_dynamic", "low_confidence_static")


class BlockDiffusion(NamedTuple):
    """How a block-diffusion decoder generates (a model's
    ``block_diffusion``; the serving engine composes its block rows part
    from it): blocks of ``length`` positions, each opened as ``length``
    copies of ``mask_token_id`` and denoised in place; ``steps``,
    ``strategy`` and ``threshold`` are the unmasking rule's defaults,
    which a request may override (``SamplingParams``)."""

    length: int
    mask_token_id: int
    steps: int = 4
    strategy: str = "low_confidence_dynamic"
    threshold: float = 0.9

    def static_count(self, strategy: Optional[str] = None) -> int:
        """What :func:`unmask_block` takes as a row's ``n_static``: the
        positions ``low_confidence_static`` unmasks a forward (``length /
        steps``, rounded up), or 0 for ``low_confidence_dynamic``."""
        strategy = self.strategy if strategy is None else strategy
        if strategy not in UNMASK_STRATEGIES:
            raise ValueError(
                f"unmasking strategy {strategy!r} is none of "
                f"{UNMASK_STRATEGIES}")
        if strategy == "low_confidence_dynamic":
            return 0
        return -(-self.length // max(1, self.steps))


def unmask_block(logits, block, mask_token_id: int, key, temperature,
                 top_k, top_p, n_static, threshold):
    """One denoising forward's epilogue, beside :func:`sample_tokens` and
    :func:`accept_draft_tokens`: from the logits of each row's block, unmask
    some of its still-masked positions.

    ``logits``: (S, B, V), position j's predicting the token AT j;
    ``block``: int (S, B), ``mask_token_id`` where masked; the knobs are
    :func:`sample_tokens`' per-row vectors; ``n_static`` int (S,) and
    ``threshold`` f32 (S,) the row's unmasking rule.  At every position the
    candidate ``x0`` is :func:`sample_tokens`' choice (the argmax of a
    greedy row: no sort; the mask token is never a candidate) and its
    confidence the softmax probability of ``x0`` (of the temperature-scaled
    logits where the row samples).  Of the MASKED positions a row unmasks

      * ``n_static == 0`` (``low_confidence_dynamic``): every one whose
        confidence passes ``threshold``, and the single most confident one
        if none does;
      * ``n_static > 0`` (``low_confidence_static``): the ``n_static`` most
        confident (all, where fewer are left).

    Ties go to the earlier position.  An unmasked token is never touched;
    a block that comes in mask-free (a commit forward) goes out as it is.
    Returns (the new block (S, B) int32, positions unmasked (S,) int32)."""
    s, b, vocab = logits.shape
    is_mask = jnp.arange(vocab) == mask_token_id
    scale = jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    x0, conf = [], []
    # a position of the block at a time: the float32 logits and the
    # sampling branch's temporaries (a sort of rows x vocabulary) are then
    # a B-th of the block's, and reused
    for j in range(b):
        lg = jnp.where(is_mask, -jnp.inf, logits[:, j].astype(jnp.float32))
        tok = sample_tokens(lg, jax.random.fold_in(key, j), temperature,
                            top_k, top_p)
        scaled = lg / scale
        x0.append(tok)
        conf.append(jnp.exp(
            jnp.take_along_axis(scaled, tok[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(scaled, axis=-1)))
    x0, conf = jnp.stack(x0, axis=1), jnp.stack(conf, axis=1)    # (S, B)
    masked = block == mask_token_id
    conf = jnp.where(masked, conf, -1.0)
    # a position's rank among its row's by confidence, ties to the earlier:
    # B x B comparisons, no sort
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(b)[None, None, :] < jnp.arange(b)[None, :, None]))
    rank = ahead.sum(-1)
    dynamic = (conf > threshold[:, None]) | (rank == 0)
    take = masked & jnp.where((n_static > 0)[:, None],
                              rank < n_static[:, None], dynamic)
    return (jnp.where(take, x0, block).astype(jnp.int32),
            take.sum(-1, dtype=jnp.int32))


def decode_mesh_specs(model, params, axis_names, paged_cache=False,
                      quantized_cache=False):
    """The DECLARED mesh layout of the decode state, as PartitionSpecs
    filtered to ``axis_names`` (no devices touched):

      * params per their declared TP/FSDP specs (so lm_head stays
        vocab-parallel on ``mp`` and the logits matmul runs sharded, with
        GSPMD inserting the argmax/sample reduction collectives) — a
        spec pytree matching ``params``;
      * the stacked KV cache (L, 2, B, max_len, Hkv, D): batch over
        dp×sharding, kv heads over ``mp`` — the serving layout matching
        how training shards attention.  The paged pool
        (L, 2, num_blocks, block_len, Hkv·D) shards its fused head axis
        on ``mp`` only (head-major, so a shard is whole kv heads): any
        block can back any slot, so the block axis must NOT be split
        over the batch axes;
      * input ids: batch over dp×sharding.

    :func:`_place_on_mesh` commits these specs with ``device_put``; the
    static-analysis mesh pre-flight (``ServingEngine.mesh_preflight``)
    lints against them abstractly, for meshes that need not exist on
    this host."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.fleet.mp_layers import _filter_spec

    names = set(axis_names)

    def fs(*entries):
        return P(*_filter_spec(entries, names))

    specs = model.param_shardings(include_buffers=True)

    # path-wise lookup: plain models carry a flat {name: spec} dict; a
    # quantized wrapper's packed {"fp"/"qw"/"qs": {name: spec}} store
    # nests one level — walking the value tree's own path keeps TP/FSDP
    # layouts instead of silently replicating everything whose top-level
    # key has no spec
    def _lookup(path):
        node = specs
        for p in path:
            key = getattr(p, "key", None)
            if isinstance(node, dict) and key in node:
                node = node[key]
            else:
                return None
        return None if isinstance(node, dict) else node

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    param_specs = jax.tree_util.tree_unflatten(treedef, [
        fs(*tuple(_lookup(path) or P())) for path, _ in flat])
    batch = tuple(a for a in ("dp", "sharding") if a in names)
    if paged_cache:
        cache_spec = fs(None, None, None, None, "mp")
        scale_spec = fs(None, None, None, "mp")
    else:
        cache_spec = fs(None, None, batch, None, "mp", None)
        scale_spec = fs(None, None, batch, None, "mp")
    if quantized_cache:
        # int8 cache pytree: payload keeps the bf16 layout, the per-
        # block(-granule)-per-kv-head scales shard their head axis on mp
        # alongside it
        cache_spec = {"kv": cache_spec, "scale": scale_spec}
    return param_specs, cache_spec, fs(batch)


def _place_on_mesh(model, params, cache, input_ids, paged_cache=False,
                   mesh=None):
    """Mesh-native decode (round-3 verdict #3): when a hybrid mesh is
    active, lay the decode state out on it before jitting, per the
    declared :func:`decode_mesh_specs` layout.

    ``mesh``: an explicit jax Mesh overriding the global active mesh —
    the mesh-sharded ServingEngine passes its own, so an engine can be
    mesh-placed without installing a process-global hybrid group.

    Single-device (no mesh): unchanged pass-through.  Recurrent decode
    states (Mamba/RWKV pytrees) are left unplaced — GSPMD propagates from
    the params/ids, and their state layouts are model-specific.
    """
    from ..distributed import env as _denv

    if mesh is None:
        mesh = _denv.active_mesh()
    if mesh is None or all(mesh.shape[a] == 1 for a in mesh.axis_names):
        return params, cache, input_ids
    from jax.sharding import NamedSharding

    quantized = isinstance(cache, dict) and "kv" in cache
    param_specs, cache_spec, ids_spec = decode_mesh_specs(
        model, params, mesh.axis_names, paged_cache=paged_cache,
        quantized_cache=quantized)
    params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        params, param_specs)
    input_ids = jax.device_put(input_ids, NamedSharding(mesh, ids_spec))
    if quantized or (isinstance(cache, jax.Array)
                     and cache.ndim == (5 if paged_cache else 6)):
        cache = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
            cache, cache_spec)
    return params, cache, input_ids


def greedy_generate(model, input_ids, max_new_tokens: int,
                    eos_token_id: Optional[int] = None,
                    pad_token_id: int = 0,
                    temperature: float = 0.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    seed: int = 0,
                    max_length: Optional[int] = None,
                    extra_inputs: Optional[dict] = None,
                    num_beams: int = 1,
                    length_penalty: float = 1.0):
    """Generate ``max_new_tokens`` continuations for a batch of prompts.

    ``model`` must expose ``decode_step(input_ids, cache, pos) ->
    (logits, cache)`` and ``.config``.  ``temperature == 0`` is greedy
    (the parity-tested path); ``temperature > 0`` samples, optionally
    top-k- and/or top-p- (nucleus-) truncated.  ``num_beams > 1`` switches
    to beam search (see :func:`beam_search_generate`; the sampling knobs
    must be off).  Returns int32 (batch, prompt_len + max_new_tokens);
    rows that hit ``eos_token_id`` are padded with ``pad_token_id``.

    ``extra_inputs``: dict of arrays forwarded to every ``decode_step``
    call as keyword arguments (e.g. a VLM's precomputed vision features) —
    they are real jit inputs, not baked constants, so the compiled program
    is reused across prompts AND images.
    """
    from ..nn.layer import bind_params

    if num_beams > 1:
        if temperature != 0.0 or top_k is not None or top_p is not None:
            raise ValueError("beam search is deterministic: temperature/"
                             "top_k/top_p must be unset with num_beams > 1")
        return beam_search_generate(
            model, input_ids, max_new_tokens, num_beams=num_beams,
            eos_token_id=eos_token_id, pad_token_id=pad_token_id,
            length_penalty=length_penalty, max_length=max_length,
            extra_inputs=extra_inputs)
    if max_new_tokens < 1:  # lax.scan(length=max_new_tokens-…) would give
        raise ValueError(    # an opaque negative-length error instead
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, s = input_ids.shape
    total = max_length if max_length is not None else s + max_new_tokens
    if total < s + max_new_tokens:
        raise ValueError(f"max_length {total} < prompt {s} + "
                         f"max_new_tokens {max_new_tokens}")
    limit = getattr(model.config, "max_position_embeddings", None)
    if limit is not None and total > limit:
        # past the RoPE cache jnp.take would CLAMP position ids (jax's
        # out-of-bounds gather mode) — silently wrong rotations, so refuse
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds the model's "
            f"max_position_embeddings ({limit})")
    # attention models carry the stacked KV cache; recurrent models
    # (Mamba/RWKV) provide their own O(1) state pytree instead
    if hasattr(model, "init_decode_state"):
        cache = model.init_decode_state(b, total)
    else:
        cache = init_kv_cache(model.config, b, total)
    params = model.state_dict(include_buffers=True)
    # quantized-decode hooks (models/quantized.py): ``unwrapped`` is the
    # Layer to bind, ``_prepare_params`` dequantises the packed store
    # in-graph; both default to the plain model
    bind_target = getattr(model, "unwrapped", model)
    prepare = getattr(model, "_prepare_params", lambda p: p)
    params, cache, input_ids = _place_on_mesh(bind_target, params, cache,
                                              input_ids)

    def pick(logits, key):
        return sample_tokens(logits, key, temperature, top_k, top_p)

    extra = extra_inputs or {}
    # one compiled scan per static generation config, cached on the model:
    # repeat generate() calls with the same shapes/settings (the serving
    # pattern) reuse the jitted program instead of re-tracing every call
    cache_key = (b, s, total, max_new_tokens, eos_token_id, pad_token_id,
                 temperature, top_k, top_p,
                 tuple(sorted((k, v.shape) for k, v in extra.items())))
    gen_cache = getattr(model, "_generate_jit_cache", None)
    if gen_cache is None:
        gen_cache = model._generate_jit_cache = {}
    if cache_key in gen_cache:
        out = gen_cache[cache_key](params, input_ids, cache,
                                   jax.random.key(seed), extra)
        return jnp.concatenate([input_ids, out], axis=1)

    @jax.jit
    def run(params, input_ids, cache, key, extra):
        with bind_params(bind_target, prepare(params)):
            # prefill: one pass over the whole prompt.  pos is the STATIC
            # int 0 (not a traced scalar) so attention layers can route
            # prefill through the Pallas flash kernel (llama.py decode)
            logits, cache = model.decode_step(input_ids, cache, 0, **extra)
            key, sub = jax.random.split(key)
            nxt = pick(logits[:, -1], sub)
            done = jnp.zeros((b,), bool)
            if eos_token_id is not None:
                done = nxt == eos_token_id

            def step(carry, _):
                cache, pos, tok, done, key = carry
                logits, cache = model.decode_step(tok[:, None], cache, pos,
                                                  **extra)
                key, sub = jax.random.split(key)
                new = pick(logits[:, -1], sub)
                if eos_token_id is not None:
                    new = jnp.where(done, pad_token_id, new)
                    done = done | (new == eos_token_id)
                return (cache, pos + 1, new, done, key), tok

            carry = (cache, jnp.int32(s), nxt, done, key)
            carry, toks = jax.lax.scan(step, carry, None,
                                       length=max_new_tokens - 1)
            # toks[i] is the token fed INTO step i; the final carry token
            # is the last generated one → exactly max_new_tokens total
            return jnp.concatenate([toks.T, carry[2][:, None]], axis=1)

    gen_cache[cache_key] = run
    out = run(params, input_ids, cache, jax.random.key(seed), extra)
    return jnp.concatenate([input_ids, out], axis=1)


def _nucleus_floor(sorted_logits, top_p):
    """The smallest logit top-p (nucleus) truncation keeps, from rows
    already sorted DESCENDING: the smallest set of tokens whose
    cumulative probability reaches ``top_p`` is kept (the first token
    always).  (..., 1)."""
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # drop tokens whose PRECEDING mass already reached p; the threshold
    # is the smallest kept logit
    drop = (cum - probs) >= top_p
    return jnp.min(jnp.where(drop, jnp.inf, sorted_logits), axis=-1,
                   keepdims=True)


def _nucleus_mask(logits, top_p):
    """Top-p (nucleus) truncation (parity: generation_utils'
    TopPProcess, upstream PaddleNLP layout): keep the smallest set of
    tokens whose cumulative probability reaches ``top_p``; mask the rest
    to -inf.  Sort-based — lax-friendly, no data-dependent shapes.
    ``top_p``: a static float (``sample_tokens``' static-knobs branch;
    unguarded, its own sort).  The traced per-row form is
    :func:`_truncate`, which shares the threshold rule below
    (:func:`_nucleus_floor`) and its one sort with top-k."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]         # desc
    kth = _nucleus_floor(sorted_logits, top_p)
    return jnp.where(logits < kth, -jnp.inf, logits)


def _gather_state(cache, idx):
    """Reorder decode state by flat beam indices ``idx`` (B*K,).

    Batch-axis convention: the stacked KV cache (a single 6-d array,
    (L, 2, B·K, S, H, D)) carries batch at axis 2; recurrent state pytrees
    (Mamba's conv/ssm, RWKV's shift/wkv accumulators) carry
    (layers, B·K, ...) — batch at axis 1."""
    if isinstance(cache, jax.Array):
        return jnp.take(cache, idx, axis=2)
    return jax.tree_util.tree_map(lambda a: jnp.take(a, idx, axis=1), cache)


def beam_search_generate(model, input_ids, max_new_tokens: int,
                         num_beams: int = 4,
                         eos_token_id: Optional[int] = None,
                         pad_token_id: int = 0,
                         length_penalty: float = 1.0,
                         max_length: Optional[int] = None,
                         extra_inputs: Optional[dict] = None):
    """Beam search (parity: generation_utils' beam_search decode strategy,
    upstream PaddleNLP layout) as one compiled ``lax.scan``.

    Static beam width; every beam advances every step (finished beams emit
    ``pad_token_id`` with probability 1, freezing their score) — no
    data-dependent control flow, the XLA-friendly formulation.  The token
    buffer is carried in the scan and beam-reordered each step (O(K·T) per
    step — fine for serving-scale T; a backtracking reconstruction would
    save bandwidth at the cost of a second scan).

    Scores are summed log-probs; the returned beam maximises
    ``score / length**length_penalty`` with ``length`` = generated tokens
    before EOS (the GNMT length normalisation, matching the reference's
    default beam scorer).  Returns int32 (batch, prompt + max_new_tokens).
    """
    from ..nn.layer import bind_params

    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if num_beams < 2:
        raise ValueError(f"num_beams must be >= 2, got {num_beams}")
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, s = input_ids.shape
    k = num_beams
    total = max_length if max_length is not None else s + max_new_tokens
    if total < s + max_new_tokens:
        raise ValueError(f"max_length {total} < prompt {s} + "
                         f"max_new_tokens {max_new_tokens}")
    limit = getattr(model.config, "max_position_embeddings", None)
    if limit is not None and total > limit:
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds the model's "
            f"max_position_embeddings ({limit})")
    if hasattr(model, "init_decode_state"):
        cache = model.init_decode_state(b * k, total)
    else:
        cache = init_kv_cache(model.config, b * k, total)
    params = model.state_dict(include_buffers=True)
    bind_target = getattr(model, "unwrapped", model)
    prepare = getattr(model, "_prepare_params", lambda p: p)
    params, cache, input_ids = _place_on_mesh(bind_target, params, cache,
                                              input_ids)
    # decode_step sees batch B·K, so per-row side inputs (e.g. a VLM's
    # vision features) must be beam-tiled too; beam-invariant, so no
    # per-step reorder is needed
    extra = {n: jnp.repeat(jnp.asarray(v), k, axis=0)
             for n, v in (extra_inputs or {}).items()}

    cache_key = ("beam", b, s, total, max_new_tokens, k, eos_token_id,
                 pad_token_id, length_penalty,
                 tuple(sorted((n, v.shape) for n, v in extra.items())))
    gen_cache = getattr(model, "_generate_jit_cache", None)
    if gen_cache is None:
        gen_cache = model._generate_jit_cache = {}
    if cache_key not in gen_cache:

        @jax.jit
        def run(params, input_ids, cache, extra):
            with bind_params(bind_target, prepare(params)):
                # prefill every beam with the same prompt (beams only
                # diverge from step 1, when scores break the tie)
                tiled = jnp.repeat(input_ids, k, axis=0)      # (B·K, S)
                # static pos=0: prefill may take the flash kernel path
                logits, cache = model.decode_step(tiled, cache, 0, **extra)
                logp0 = jax.nn.log_softmax(
                    logits[:, -1].astype(jnp.float32), axis=-1)
                v = logp0.shape[-1]
                # beam 0 carries the prompt; the rest start at -inf so the
                # first expansion draws K distinct tokens from beam 0
                init_bias = jnp.where(jnp.arange(k) == 0, 0.0, -jnp.inf)
                scores0 = logp0.reshape(b, k, v) + init_bias[None, :, None]
                top, flat = jax.lax.top_k(scores0.reshape(b, k * v), k)
                tok = (flat % v).astype(jnp.int32)            # (B, K)
                parent = flat // v
                gidx = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
                cache = _gather_state(cache, gidx)
                scores = top                                   # (B, K)
                done = (jnp.zeros((b, k), bool) if eos_token_id is None
                        else tok == eos_token_id)
                lengths = jnp.ones((b, k), jnp.int32)
                buf = jnp.full((b, k, max_new_tokens), pad_token_id,
                               jnp.int32)
                buf = buf.at[:, :, 0].set(tok)

                def step(carry, i):
                    cache, scores, buf, done, lengths, tok = carry
                    logits, cache = model.decode_step(
                        tok.reshape(b * k, 1), cache, jnp.int32(s) + i,
                        **extra)
                    logp = jax.nn.log_softmax(
                        logits[:, -1].astype(jnp.float32), axis=-1)
                    logp = logp.reshape(b, k, v)
                    if eos_token_id is not None:
                        # finished beams: pad extends with prob 1, all else
                        # impossible — the score freezes
                        pad_row = jnp.full((v,), -jnp.inf
                                           ).at[pad_token_id].set(0.0)
                        logp = jnp.where(done[:, :, None], pad_row, logp)
                    cand = scores[:, :, None] + logp           # (B, K, V)
                    top, flat = jax.lax.top_k(cand.reshape(b, k * v), k)
                    tok = (flat % v).astype(jnp.int32)
                    parent = flat // v
                    gidx = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
                    cache = _gather_state(cache, gidx)
                    buf = jnp.take_along_axis(buf, parent[:, :, None],
                                              axis=1)
                    buf = jax.lax.dynamic_update_index_in_dim(
                        buf, tok, i + 1, axis=2)
                    done = jnp.take_along_axis(done, parent, axis=1)
                    lengths = jnp.take_along_axis(lengths, parent, axis=1)
                    lengths = jnp.where(done, lengths, lengths + 1)
                    if eos_token_id is not None:
                        done = done | (tok == eos_token_id)
                    return (cache, top, buf, done, lengths, tok), None

                carry = (cache, scores, buf, done, lengths, tok)
                carry, _ = jax.lax.scan(step, carry,
                                        jnp.arange(max_new_tokens - 1))
                _, scores, buf, done, lengths, _ = carry
                norm = scores / (lengths.astype(jnp.float32)
                                 ** length_penalty)
                best = jnp.argmax(norm, axis=1)                # (B,)
                return jnp.take_along_axis(
                    buf, best[:, None, None], axis=1)[:, 0]    # (B, T)

        gen_cache[cache_key] = run
    out = gen_cache[cache_key](params, input_ids, cache, extra)
    return jnp.concatenate([input_ids, out], axis=1)


class DecodeStep(_Layer):
    """Exportable decode step: wraps a causal LM so ``jit.save`` can AOT-
    compile ``(input_ids, cache, pos) -> (logits, cache)`` to StableHLO —
    the serving artifact (parity: the reference's inference program with
    CacheKV inputs).  The cache-length dim may be symbolic (``None`` in the
    InputSpec), so ONE artifact serves any max_length."""

    def __init__(self, lm):
        super().__init__()
        self.lm = lm

    def forward(self, input_ids, cache, pos):
        return self.lm.decode_step(input_ids, cache, pos)
