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
from typing import Any, Callable, ContextManager, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["DecodePart", "PoolEntry", "join_tokens", "split_tokens",
           "join_valid", "part_by_part", "head_tokens", "slot_rows",
           "slot_rows_back"]


class PoolEntry(NamedTuple):
    """What a model declares (``kv_pool_entry``) whose paged pool holds
    something else a position than K and V rows of
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
