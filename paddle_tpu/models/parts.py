"""The parts of a cache-carrying decode pass.

A model's ``decode_parts(parts, cache)`` runs ONE pass of its weights over
the tokens of every part laid end to end, and lets each part address its
own piece of the per-request state.  A :class:`DecodePart` is a run of
tokens that share one way of addressing that state: the serving engine's
decode rows (a row a slot, through the slots' block tables) are one part,
its prompt chunk (one row of many positions, through the cursor's table
row or slot) another.  ``decode_step(ids, cache, pos, ...)`` is the pass
over a list of one part.

What runs once over all tokens is everything TOKEN-WISE: the embedding,
the norms, every projection, the dense and routed FFNs, the head.  What
runs a part at a time, inside the part's ``scope``, is what addresses
per-request state: position encoding at the part's positions, the K/V
write and the cached-attention read through the part's table (or over its
``slots`` of a contiguous cache), a fixed-size state's update on the
part's ``slots``.  Within a layer the parts run in list order on the one
cache, so an earlier part's writes precede a later part's reads.

With one part nothing is reshaped: the activations keep the part's own
``(rows, positions, ...)`` and the pass traces op for op as a single
``decode_step`` always did.  With several, tokens lie on the LEADING axis,
``(Σ rows·positions, 1, ...)`` — the axis a mesh shards activations on.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import (Any, Callable, ContextManager, Mapping, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp

from ..distributed.fleet.mp_layers import constrain, vocab_parallel_lookup
from ..ops import flash_attention, fused_rope

__all__ = ["DecodePart", "PoolEntry", "ServingTraits", "CausalLMDecode",
           "join_tokens", "split_tokens", "join_valid", "part_by_part",
           "head_tokens", "slot_rows", "slot_rows_back", "carried_window",
           "part_site", "kv_attention", "band_mask"]


class PoolEntry(NamedTuple):
    """What a model declares (``ServingTraits.pool_entry``) whose paged pool
    holds something else a position than K and V rows of
    ``num_key_value_heads · head_dim``: the serving engine builds the pool,
    its bytes a block, the cost model's bytes a token and the block-walk
    counts of its spans from this, once, at construction."""

    arrays: int                 # the pool's second axis: 2 is K and V; 1 an
    #                             entry that is key and value at once
    width: int                  # lanes a position an array AS STORED (the
    #                             layout's padding to 128 lanes included)
    group: int                  # query rows a key in the flash-decode walk:
    #                             the heads that share one stored entry
    layout: Any = None          # the walk's static layout parameter
    #                             (``ops.pallas.decode_attention
    #                             .LatentLayout``), whose tiles and copy
    #                             groups the counts follow


@dataclasses.dataclass(frozen=True)
class ServingTraits:
    """THE CONTRACT between a model and ``serving.ServingEngine``, told here
    and nowhere else.

    Every served model has ``config``, ``state_dict`` and
    ``decode_parts(parts, cache)``: ONE pass of its weights over the tokens
    of every :class:`DecodePart` (module docstring;
    :class:`CausalLMDecode` is the one copy of it).  Whatever else the
    engine has to know it reads, once, at construction, from the model's
    ONE attribute ``serving_traits``: this record.  A model without the
    attribute has the defaults, which are llama's — K and V rows in every
    layout, nothing refused.  A model that keeps a decode state of its own
    (``init_decode_state``) and declares no ``slot_state`` is refused at
    construction, by name."""

    slot_state: Tuple[str, ...] = ()
    #   the leaves of the serving cache (a dict) that are fixed-size per
    #   slot (slot axis 1); the one other leaf is the paged pool's
    init_serving_cache: Optional[Callable[[int, int, int], Any]] = None
    #   (state rows, pool blocks, block length) -> that dict; a model with
    #   ``slot_state`` makes its whole serving cache itself
    pool_entry: Optional["PoolEntry"] = None
    #   what the paged pool holds a position where that is not K and V rows
    #   of ``num_key_value_heads · head_dim``: the pool's second axis and
    #   width, its bytes, the pre-flight and the spans' walk counts follow
    #   it, and under a prefix cache a layout that can walk a shared prefix
    #   once is told which rows share one (``DecodePart.shared``)
    block_diffusion: Any = None
    #   ``models.generation.BlockDiffusion``: generation by diffusion over
    #   blocks.  The engine's rows part is then a block of ``length``
    #   positions a row, its epilogue the unmasking rule, its chunk part
    #   stops at the prompt's last whole block
    expert_layers: int = 0
    #   routed-expert layers: the step programs hand the parts their real
    #   tokens (``valid``) and return the layers' load beside the sampled
    #   tokens (``distributed.moe.expert_load``)
    attention_windows: Tuple[Optional[int], ...] = ()
    #   per K/V layer, the sliding window its attention reads; None: the
    #   whole prefix.  Empty: every layer reads the whole prefix
    kernel_specs: Optional[Callable[[Any], list]] = None
    #   (token rows a pass) -> the pre-flight specs of the kernels only
    #   this model's step programs build
    unsupported: Mapping[str, str] = dataclasses.field(default_factory=dict)
    #   the engine layouts this model cannot run: layout -> the model's
    #   reason.  The engine words the refusal and asks in this mapping's
    #   order, the first that applies wins.  The keys: "contiguous_cache",
    #   "wave_prefill", "prefix_cache", "preemption" (``preempt`` or a host
    #   tier), "kv_cache_dtype" (any but bf16), "mesh", "spec_decode",
    #   "int8_weights"; any other is an error at the engine's construction


class DecodePart(NamedTuple):
    """One part of a decode pass (module docstring)."""

    input_ids: Any              # int (rows, positions)
    pos: Any                    # tokens already cached: an int, a traced
    #                             scalar, or an int (rows,) vector a row
    block_tables: Any = None    # int (rows, max_blocks): the rows' tables
    #                             into the paged pool; None: contiguous cache
    valid: Any = None           # bool (rows, positions), a prefix of each
    #                             row: the real tokens; None: all.  Padding
    #                             reaches no expert and advances no state
    slots: Optional[Tuple[Any, int]] = None
    #                             (first, count): the rows of every
    #                             slot-indexed leaf this part's rows are (a
    #                             contiguous cache, a fixed-size per-slot
    #                             state); None: all of them, in order
    last: Any = None            # int scalar: logits are wanted at this
    #                             position of each row alone; None: at all
    scope: Callable[[], ContextManager] = contextlib.nullcontext
    #                             opened around what runs for this part
    #                             alone (names its kernels in a trace)
    shared: Any = None          # decode rows over a pool read through a
    #                             prefix trie: which rows hold the same
    #                             blocks in their leading columns
    #                             (``ops.pallas.decode_attention
    #                             .SharedWalk``), for a read that can walk
    #                             them once for those rows; None: unknown


def join_tokens(xs):
    """Per-part ``(rows, positions, ...)`` arrays laid end to end on the
    leading axis, ``(tokens, 1, ...)``; one part stays as it is."""
    if len(xs) == 1:
        return xs[0]
    return jnp.concatenate(
        [x.reshape(-1, 1, *x.shape[2:]) for x in xs], axis=0)


def split_tokens(x, shapes):
    """The inverse of :func:`join_tokens`: ``shapes`` are the parts'
    ``(rows, positions)``."""
    if len(shapes) == 1:
        return [x]
    out, at = [], 0
    for b, s in shapes:
        out.append(x[at:at + b * s].reshape(b, s, *x.shape[2:]))
        at += b * s
    return out


def join_valid(parts):
    """The parts' ``valid`` masks as :func:`join_tokens` lays the tokens
    (a part without one is all real); None where no part has one."""
    if len(parts) == 1 or all(p.valid is None for p in parts):
        return parts[0].valid
    return join_tokens([
        jnp.ones(p.input_ids.shape, bool) if p.valid is None
        else jnp.asarray(p.valid) for p in parts])


def part_by_part(parts, tensors, state, fn):
    """What addresses per-request state, a part at a time: for each part in
    list order, inside its ``scope``, ``fn(i, part, state, *its tokens'
    cut of every array in tensors) -> (out, state)``; ``state`` threads
    through, so an earlier part's writes precede a later part's reads.
    Returns (the outs joined as :func:`join_tokens` lays them, state)."""
    shapes = [p.input_ids.shape for p in parts]
    cuts = zip(*(split_tokens(t, shapes) for t in tensors))
    outs = []
    for i, (p, cut) in enumerate(zip(parts, cuts)):
        with p.scope():
            out, state = fn(i, p, state, *cut)
        outs.append(out)
    return join_tokens(outs), state


def head_tokens(x, parts):
    """The hidden states the head is taken of — each part's at its
    ``last`` position where it names one, else all of them — joined, and
    their per-part ``(rows, positions)``."""
    shapes = [p.input_ids.shape for p in parts]
    kept = [h if p.last is None
            else jax.lax.dynamic_slice_in_dim(h, p.last, 1, axis=1)
            for h, p in zip(split_tokens(x, shapes), parts)]
    return join_tokens(kept), [h.shape[:2] for h in kept]


def slot_rows(leaf, slots, axis: int):
    """A part's rows of a slot-indexed ``leaf`` (slot axis ``axis``)."""
    if slots is None:
        return leaf
    return jax.lax.dynamic_slice_in_dim(leaf, slots[0], slots[1], axis=axis)


def slot_rows_back(leaf, rows, slots, axis: int):
    """Put a part's rows back where :func:`slot_rows` took them."""
    if slots is None:
        return rows
    return jax.lax.dynamic_update_slice_in_dim(leaf, rows, slots[0],
                                               axis=axis)


def carried_window(part, state, u, filt):
    """A causal convolution's carried window, for one part: the part's
    tokens ``u`` (B, s, C) behind the last ``keep`` inputs of ITS rows of
    ``state`` (rows, keep, C) — zeros for a row at position 0, whatever
    the state holds: a slot is reused without a reset — filtered by
    ``filt(ext (B, keep + s, C), s) -> (B, s, C)``.  Returns (the filtered
    tokens, ``state`` with the part's rows as of each row's last VALID
    token): ``part.valid`` marks the real tokens, a prefix of each row, and
    a row without one keeps its window."""
    b, s, _ = u.shape
    keep = state.shape[1]
    rows = slot_rows(state, part.slots, 0)
    fresh = jnp.broadcast_to(jnp.asarray(part.pos) == 0, (b,))
    prev = jnp.where(fresh[:, None, None], 0, rows).astype(u.dtype)
    ext = jnp.concatenate([prev, u], axis=1)
    y = filt(ext, s)
    if part.valid is None:
        rows = ext[:, s:].astype(state.dtype)
    else:
        n = jnp.asarray(part.valid).sum(axis=1, dtype=jnp.int32)     # (B,)
        last = jax.vmap(lambda e, i: jax.lax.dynamic_slice_in_dim(
            e, i, keep, axis=0))(ext, n)
        rows = jnp.where((n > 0)[:, None, None],
                         last.astype(state.dtype), rows)
    return y, slot_rows_back(state, rows, part.slots, 0)


def part_site(part, rope_cache):
    """Where one part's tokens sit: (pos — per row over the paged pool —,
    (B, s) position ids, the ids RoPE rotates by; ``rope_cache`` None: a
    model without rotary embedding, the ids as they are).  Shared by every
    attention layer that decodes over the stacked caches."""
    b, s = part.input_ids.shape
    pos = part.pos
    paged = part.block_tables is not None
    per_row = getattr(pos, "ndim", 0) == 1
    if paged and not per_row:
        pos = jnp.full((b,), pos, jnp.int32)
        per_row = True
    if per_row:
        position_ids = pos[:, None] + jnp.arange(s)[None, :]      # (B, s)
    else:
        position_ids = pos + jnp.arange(s)[None, :]
    if paged and rope_cache is not None:
        # prompt-pad positions may run past the RoPE table; clamp for
        # the rotation only (pad rows' outputs are never consumed)
        rope_ids = jnp.minimum(position_ids, rope_cache[0].shape[0] - 1)
    else:
        rope_ids = position_ids
    return pos, position_ids, rope_ids


def band_mask(s: int, window: int):
    """(1, 1, s, s) bool: key j inside query i's sliding window."""
    i = jnp.arange(s)
    return (i[:, None] - i[None, :] < window)[None, None]


def kv_attention(who: str, x, project, parts, rope_cache, cache, idx: int, *,
                 rope=None, window: Optional[int] = None, block: int = 1):
    """Attention of the tokens ``x`` of all ``parts`` over a stacked cache
    of plain K and V rows, for layer ``who`` (named in a refusal): the
    projections (``project(x) -> q, k, v`` split into heads, normed as the
    model norms them) once over all tokens; each part's RoPE
    (``rope(q, k, rope_cache, ids)``; None: rotate-half), K/V write and
    read at its own positions — with ``block_tables`` through the paged
    pool (per-row ``pos``), without them over the contiguous cache at one
    scalar ``pos`` (``generate()``).  ``window``: the sliding window the
    read is restricted to; ``block`` > 1: the block-causal mask, over the
    paged pool alone.  Returns (attention (rows, positions, heads, D) as
    :func:`join_tokens` lays it, cache).

    llama's attention is not this: it alone carries the int8 pool, per-row
    contiguous writes and the mesh constraints."""
    from ..ops.attention import (cached_decode_attention,
                                 paged_decode_attention)
    from .llama import paged_kv_write
    if isinstance(cache, dict):
        raise NotImplementedError(
            f"{who}.decode: the int8 KV cache is not supported")
    for p in parts:
        if p.block_tables is not None:
            continue
        if block > 1:
            raise NotImplementedError(
                f"{who}.decode: the block-causal read runs over the paged "
                f"pool (block_tables) only")
        if getattr(p.pos, "ndim", 0) != 0:
            raise NotImplementedError(
                f"{who}.decode: per-row positions need the paged pool "
                f"(block_tables); the contiguous cache is decoded at one "
                f"scalar position")
    sites = [part_site(p, rope_cache) for p in parts]

    def attend(i, part, cache, q, k, v):
        # the part's K/V land before the read (a block sees itself whole)
        pos, position_ids, rope_ids = sites[i]
        s = q.shape[1]
        q, k = (fused_rope(q, k, *rope_cache, rope_ids) if rope is None
                else rope(q, k, rope_cache, rope_ids))
        if part.block_tables is not None:
            cache, kvp, _ = paged_kv_write(cache, idx, k, v, position_ids,
                                           part.block_tables)
            return paged_decode_attention(
                q, kvp, idx, pos, part.block_tables, window=window,
                block=block), cache
        cache = jax.lax.dynamic_update_slice(
            cache, k.astype(cache.dtype)[None, None],
            (idx, 0, 0, pos, 0, 0))
        cache = jax.lax.dynamic_update_slice(
            cache, v.astype(cache.dtype)[None, None],
            (idx, 1, 0, pos, 0, 0))
        if isinstance(pos, int) and pos == 0 and s > 1:
            mask = None if window is None else band_mask(s, window)
            return flash_attention(q, k, v, causal=True,
                                   attn_mask=mask), cache
        return cached_decode_attention(q, cache[idx, 0], cache[idx, 1],
                                       pos, window=window), cache
    return part_by_part(parts, project(x), cache, attend)


class CausalLMDecode:
    """The served half of a causal LM, mixed into a ``Layer`` that has
    ``logits(hidden)`` and a ``model`` with ``embed_tokens``, ``layers``,
    the RoPE buffers and a final ``norm``; a model that embeds, norms or
    encodes position otherwise overrides ``_embed`` / ``_final_norm`` /
    ``_rope_cache``."""

    def _embed(self, input_ids):
        return vocab_parallel_lookup(self.model.embed_tokens, input_ids)

    def _rope_cache(self):
        return self.model.rope_cos, self.model.rope_sin

    def _final_norm(self, x):
        return self.model.norm(x)

    def decode_parts(self, parts, cache):
        """([logits a part], cache): ONE pass of the weights over the
        tokens of every :class:`DecodePart`, each addressing its own piece
        of ``cache`` (whatever the layers' ``decode(x, rope_cache, parts,
        cache, index)`` address: a stacked contiguous cache, a paged pool,
        a dict of leaves); a part's logits are (rows, positions, vocab), or
        (rows, 1, vocab) at its ``last``.  A part's ``valid`` marks its
        real tokens: routed experts leave padding out and a per-slot state
        advances by the real tokens only."""
        m = self.model
        # constrain the gathered activations (batch over dp×sharding) so
        # the SPMD partitioner shards the lookup output instead of falling
        # back to rematerialising the full embedding table per device (the
        # gather-on-sharded-dim cliff recorded in MULTICHIP_r02)
        x = constrain(
            self._embed(join_tokens([p.input_ids for p in parts])),
            ("dp", "sharding"), None, None)
        rope = self._rope_cache()
        for i, block in enumerate(m.layers):
            x, cache = block.decode(x, rope, parts, cache, i)
        x, shapes = head_tokens(x, parts)
        hidden = self._final_norm(x)
        with jax.named_scope("lm_head"):
            return split_tokens(self.logits(hidden), shapes), cache

    def decode_step(self, input_ids, cache, pos, block_tables=None,
                    valid=None):
        """(logits, cache): one cache-carrying decode step (prefill when
        ``input_ids`` is the whole prompt at pos=0, incremental when it is
        the last token): the pass over one part.  See models/generation.py
        for the cache layout, serving/kv_cache.py for the paged layout
        ``block_tables`` selects."""
        (logits,), cache = self.decode_parts(
            [DecodePart(input_ids, pos, block_tables, valid)], cache)
        return logits, cache

    def generate(self, input_ids, max_new_tokens: int = 32, **kw):
        """Greedy/sampled generation with the pre-allocated KV cache
        (parity: PaddleNLP ``model.generate``; see
        :func:`paddle_tpu.models.generation.greedy_generate`)."""
        from .generation import greedy_generate
        return greedy_generate(self, input_ids, max_new_tokens, **kw)
