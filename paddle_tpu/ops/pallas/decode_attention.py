"""Split-KV flash-decode Pallas TPU kernel — the batched/long-context
serving hot path.

TPU-native equivalent of the FlashDecoding scheme (Dao et al.; the
PagedAttention-class engines' decode kernel on GPU): at q_len 1 the
(1, L) score row gives the MXU nothing to tile, so the win is pure
dataflow — read each row's live keys once, keep online-softmax partials
(m, l, acc) in VMEM across the walk, and never materialise the
(B, Hq, s, L) score tensor the XLA math path
(:func:`~paddle_tpu.ops.attention.cached_decode_attention_reference`)
builds in HBM.

**The walk is the row's own.**  The grid is ``(rows, q tiles)`` and
nothing else; the cache stays in HBM (``memory_space=pl.ANY``) and the
per-row positions, the block table and the int8 scale tables ride in as
scalar-prefetch operands.  For its ``(row, q tile)`` the body computes the
range of table columns that hold a key some query of the tile may see —
``[first, last]``, :func:`live_block_range`: the dead tail past the row's
depth is outside it, and so is everything behind a sliding window — and
runs a loop of ``ceil((last - first + 1) / G)`` trips over it.  Each trip
waits on one *group* of ``G`` blocks of K and of V, copied by
``pltpu.make_async_copy`` into a double-buffered VMEM scratch (one DMA
semaphore a copy), starts the next group's copies, and makes one
online-softmax update over the group's ``G · block`` keys a head: one
``(tile, D) x (D, G · block)`` product and one rescale a group.  A column
of the last group past ``last`` is not copied (its V rows are zeroed, its
scores masked by the position mask), and no table column outside
``[first, last]`` is ever dereferenced: those may hold the null block.
The step program is traced once and the trip count is read from the
positions, so a tick costs what its rows' live blocks cost, whatever the
deepest row or the table's width.  The next ``(row, q tile)``'s first
group is issued during this one's last trip, so a row's first copy does
not wait behind the row before it; the grid is therefore sequential
(``arbitrary``) and one SMEM word carries which buffer the next step
starts in.

``G`` is :func:`group_blocks`: as many blocks as hold ``GROUP_KEYS`` keys
(4 of the paged pool's 128-position blocks).  :func:`walk_counts` gives,
from the same bounds, the blocks a call's rows need and the block slots
the kernel walks for them; the serving engine puts both on its spans.

GQA stays grouped: Q is reshaped to (B, Hkv, G·s, D) and each kv head's
(G·s, D) query tile contracts the cache directly — bf16 operands on the
MXU with an fp32 accumulator, no Hq/Hkv KV broadcast.  A KV block is a
``(bk, Hkv·D)`` tile — rows of all kv heads' features side by side — so
it is one contiguous DMA and the per-head (bk, D) slice is a static lane
slice in VMEM.  The paged pool is STORED that way (below); the
contiguous (B, L, Hkv, D) cache is reshaped to it per call, which on the
TPU's tiled layouts is a physical copy of the layer's K and V (ROADMAP
S1; that cache's writers keep heads apart, so it keeps that cost).
Per-row ``pos`` masking happens inside the kernel
(key j visible to query row (si, g) iff j <= pos_b + si; with a static
``block`` > 1, iff j lies no later than the end of that position's block:
the block-causal mask of a block-diffusion decoder) with the same
fully-masked-row convention as the flash kernel (out = 0).

The cross-group merge is the same LSE algebra the ring-attention path
uses (ops/ring_attention.py ``merge_attention``), specialised to the
running (m, l, acc) form since groups arrive sequentially.

**Chunked prefill** (serving/engine.py mixed steps): the same kernel
generalises from q_len 1 to a q *chunk* — a span of prompt tokens
attending its cached prefix plus its own causal self-block.  q is cut
into tiles of ``bq`` tokens (``bq·G <= 64`` MXU rows each, sublane-padded
per tile) walked by the second grid dimension; the per-row ``pos`` mask
already encodes "key j visible to query offset si iff j <= pos + si", so
prefix + self-block causality needs no new machinery, and ``last`` is
per tile (early q tiles stop before the chunk's own later KV blocks —
causal block skipping for free).  Each tile reads its range again.
Routing for these shapes is counted under
``ops.kernel_path{op="chunked_prefill"}``.

**Speculative verify** (serving/engine.py spec-decode steps): the q-tile
machinery above IS the verify pass of self-drafted speculative decoding —
a (B, k+1) window of [current token, k drafts] at per-row depths scores
every draft in ONE pass of the weights, because the per-row ``pos`` mask
already gives query offset ``si`` exactly the causal view "cached prefix
+ the window's own earlier tokens".  The dispatch contract is the
chunked-prefill one (``s <= 2048``, ``s·G`` tiled at 64 rows), no new
kernel surface; the engine wraps its verify trace in
``ops._dispatch.kernel_path_hint("spec_verify")`` so these builds (and
their routing decisions) land under ``ops.kernel_path{op="spec_verify"}``
instead of the prefill-chunk label.

**Paged KV cache** (serving/kv_cache.py,
:func:`paged_decode_attention_pallas`): the cache of ALL layers is one
pooled ``(L, 2, num_blocks, block_len, Hkv·D)`` array and each row's
logical positions are backed by the physical blocks its
``(B, max_blocks)`` block table names.  The kernel is handed **the pool
itself** and copies ``pool[layer, 0 | 1, table[row, col]]``.  Nothing is
sliced out of the pool and nothing is reshaped, so a step's HBM traffic
is the live blocks it reads and the rows it writes, whatever the pool's
size.  The layer index rides in as one more scalar-prefetch value and
the call is jitted on its own (:func:`_flash_call`), so a model's layers
are ONE kernel body traced and lowered once a program, not once a layer
(the body unrolls heads and a group's blocks: traced sixteen times a
program it doubled the benchmark's set-up time).  One table
column == one cache block (``block_len`` must be 128-aligned), so a block
is one contiguous DMA, blocks may be scattered anywhere in the pool,
shared between rows, or partially filled (the in-kernel ``pos`` mask
handles partial blocks — column indices are logical).  The contiguous
layout runs the same kernel under the identity table
``table[bi, ki] = bi·chunks + ki`` over its reshaped
``(B·chunks, bk, Hkv·D)`` cache.

**A shared prefix walked once** (:class:`SharedWalk`,
:func:`latent_decode_attention_pallas`; the latent layout's decode rows):
rows that adopted one prefix through the trie hold the same block ids in
their leading table columns, and walking them a row at a time copies those
blocks once a row at a q tile of one row's heads.  Told which rows share
which columns, the walk runs in two parts under the one kernel name: the
body over q TILES of stacked rows, each walking its group's shared columns
once and handing on ``(acc, m, l)``, then the body over the rows from
their first own column on, each started from what its tile left.  The
copies fall by the rows a tile, the MXU's fill rises by them, and the
merge is the group boundary's rescale every walk already makes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import limits as _limits

NEG_INF = -1e30
# shape bounds live in ops/pallas/limits.py — ONE source of truth shared
# with the dispatch gate (ops.attention.decode_shape_gate) and the
# kernel pre-flight (static_analysis/kernel_registry.py); the
# dispatch-agreement lint proves the three stay in step
_LANES = _limits.LANES  # VPU lane width: m/l scratch rows padded to this
_MAX_Q_ROWS = _limits.MAX_Q_ROWS  # per-TILE s·G row cap — larger q tiles
_MAX_Q_LEN = _limits.MAX_Q_LEN  # beyond this: whole-prefill, flash territory

# keys one copy group holds: four of the pool's 128-position blocks, 1 MB
# of K and 1 MB of V at Hkv·D = 1024 in bf16, twice that double-buffered.
# Chosen on the chip (PERF.md §6, PR 30); kernel.decode_walk_live_pct is
# what another choice is judged by.
GROUP_KEYS = 512
# The VMEM a group's buffers (K and V, each twice) may take: at Hkv·D =
# 3840 (30 K/V heads of 128, no GQA; PR 46) a group of 512 keys would be
# 15.7 MB of the 16 a kernel may use, so a group is halved until it fits —
# to 256 keys there; every narrower pool keeps its 512
GROUP_VMEM = 8 * 1024 * 1024


def _pick_block_kv(kv_len: int, cap: int) -> int:
    """Largest KV chunk <= cap that divides kv_len on the 128-lane
    tiling; 0 when none exists (caller falls back to XLA)."""
    for d in range(min(cap, kv_len), 0, -1):
        if kv_len % d == 0 and d % 128 == 0:
            return d
    return 0


def contiguous_block_kv(kv_len: int, n_gran: Optional[int] = None,
                        block_kv: int = 0) -> int:
    """The KV block the kernel cuts a CONTIGUOUS cache of ``kv_len``
    positions into: an int8 cache's scale granule (``n_gran`` of them: one
    block == one (block, head) scale entry, exactly the paged contract),
    else the largest 128-aligned divisor under ``block_kv`` (0: the
    flag's cap).  Raises NotImplementedError where there is none."""
    if n_gran is not None:
        bk = kv_len // n_gran
        if bk * n_gran != kv_len or bk % 128:
            raise NotImplementedError(
                f"int8 scale granule {kv_len}/{n_gran} is not a "
                f"128-aligned divisor of the cache length")
        return bk
    if not block_kv:
        from ...flags import flag
        block_kv = int(flag("decode_attention_block_kv"))
    bk = _pick_block_kv(kv_len, block_kv)
    if not bk:
        raise NotImplementedError(
            f"max_length {kv_len} has no 128-aligned chunk "
            f"divisor <= {block_kv}")
    return bk


@dataclasses.dataclass(frozen=True)
class LatentLayout:
    """The row layout of a LATENT pool (``(L, 1, blocks, block_len, W)``:
    ONE entry a position that is key and value at once, shared by every
    head), as a static parameter of the kernel: the value is the entry's
    first ``value_width`` lanes (the normed latent), the key all ``W`` of
    them (the latent, the rotated RoPE key, zero lanes up to a multiple of
    128).  One kv head at a query group of every head, so a q tile may hold
    ``q_rows`` MXU rows (``q_rows // heads`` tokens of a prompt chunk: each
    tile reads its range again, so a chunk re-reads it ``s·heads / q_rows``
    times) and a copy group ``group_keys`` keys (one DMA a block, a third
    of the K/V layout's bytes a key: longer groups for the same buffers)."""
    value_width: int
    q_rows: int = 256
    group_keys: int = 1024


def _tiling(latent: Optional[LatentLayout]) -> Tuple[int, int]:
    """(a q tile's row cap, a copy group's keys) of a pool's layout: a
    latent pool's own, or the K/V layout's."""
    return ((latent.q_rows, latent.group_keys) if latent
            else (_MAX_Q_ROWS, GROUP_KEYS))


class SharedWalk(NamedTuple):
    """Which decode rows of a call walk a run of leading table columns
    TOGETHER (:func:`latent_decode_attention_pallas`): rows whose tables
    hold the same physical blocks in columns ``[0, n)`` are stacked into q
    tiles of :func:`tile_members` rows each, a tile walks those columns
    once for its members, and each member's own walk starts at column ``n``
    from what the tile left.  Fixed shapes, so one program serves every
    grouping; the live tiles come first."""

    n: Any                      # int (rows,): the leading columns row i
    #                             does not walk itself; 0: it walks alone
    at: Any                     # int (rows,): ``tile · members + place``
    #                             of a row with ``n > 0`` (any other row:
    #                             whatever saves a copy, it is not read)
    tile_rows: Any              # int (tiles, members): a tile's rows, the
    #                             first its LEADER, through whose table row
    #                             the shared columns are read; a place no
    #                             row holds names any row
    tile_n: Any                 # int (tiles,): the columns a tile walks;
    #                             0: an empty tile


def tile_members(latent: LatentLayout, heads: int) -> int:
    """The decode rows one shared tile stacks: a q tile's MXU rows over
    the heads a row brings."""
    return max(1, latent.q_rows // int(heads))


def stored_key_bytes(width: int, arrays: int, dtype) -> int:
    """What one key takes in a pool as stored: ``arrays`` arrays (K and V,
    or a latent pool's one) of ``width`` elements of ``dtype``.  The ONE
    reckoning of :func:`group_blocks`' input: the kernel, the engine's
    walk counts and the pre-flight spec all call it."""
    return int(arrays) * int(width) * jnp.dtype(dtype).itemsize


def group_blocks(bk: int, group_keys: int = GROUP_KEYS,
                 key_bytes: int = 0) -> int:
    """Blocks of ``bk`` positions one copy group holds (the kernel's G):
    ``group_keys`` keys' worth, halved while the group's double buffers —
    :func:`stored_key_bytes` a key — pass ``GROUP_VMEM``.  Left out,
    ``key_bytes`` halves nothing: right for a pool of at most 8 KiB a key
    (every accepted cell's), which is what the accepted benchmark's own
    test of the walk counts relies on (it may not be edited here)."""
    gb = max(1, int(group_keys) // int(bk))
    while gb > 1 and 2 * gb * int(bk) * int(key_bytes) > GROUP_VMEM:
        gb //= 2
    return gb


def q_tiles(s: int, g: int, max_rows: int = _MAX_Q_ROWS) -> Tuple[int, int]:
    """``(bq, nq)``: one grid step covers ``bq`` query tokens (``bq·g``
    MXU rows, at most ``max_rows``) and ``nq`` of them cover the ``s``
    tokens.  ``s <= bq`` is steady decode or a verify window, one tile."""
    bq = min(s, max(1, int(max_rows) // g))
    return bq, -(-s // bq)


def live_block_range(pos, qi, *, s, bq, bk, n_cols, window=None, first=None,
                     xp=jnp):
    """``(first, last)``: the table columns holding a key that some query
    of q tile ``qi`` (offsets ``qi·bq .. min((qi+1)·bq, s) - 1``) of a row
    at position ``pos`` may see — the columns the kernel walks, and the
    only ones it dereferences.  ``last`` holds the tile's last query's own
    key; ``first`` is 0, or with a sliding ``window`` the block of the
    oldest key the tile's FIRST query still sees, or the row's ``first``
    where a shared walk already read the columns before it
    (:class:`SharedWalk`).  Both stay inside the table whatever ``pos`` (an
    idle row parked past the cache included).  Scalars in the kernel
    (``xp=jnp``), arrays on the host (``xp=np``)."""
    last = xp.minimum((pos + xp.minimum((qi + 1) * bq, s) - 1) // bk,
                      n_cols - 1)
    if first is not None:
        return xp.minimum(first, last), last
    if window is None:
        return xp.zeros_like(last), last
    return xp.minimum(xp.maximum(pos + qi * bq - window + 1, 0) // bk,
                      last), last


def walk_counts(pos, s: int, g: int, *, bk: int, n_cols: int,
                window: Optional[int] = None,
                latent: Optional[LatentLayout] = None,
                first=None, key_bytes: int = 0) -> Tuple[int, int]:
    """``(kv_blocks, kv_walk)`` of one kernel call on the host, from the
    bounds the kernel itself uses: the blocks its rows' q tiles need
    (``last - first + 1`` each) and the block slots it walks for them
    (whole groups of :func:`group_blocks`).  ``pos``: the call's per-row
    positions; ``s``, ``g``: its q length and GQA group size; ``latent``:
    the latent pool's layout, whose tiles and groups are its own;
    ``first``: per row, the column its own walk starts at behind a shared
    one (:class:`SharedWalk`); ``key_bytes``: what a key takes over the
    pool's arrays (:func:`group_blocks`)."""
    max_rows, group_keys = _tiling(latent)
    bq, nq = q_tiles(int(s), int(g), max_rows)
    first, last = live_block_range(
        np.asarray(pos, np.int64).reshape(-1, 1), np.arange(nq)[None],
        s=int(s), bq=bq, bk=int(bk), n_cols=int(n_cols), window=window,
        first=(None if first is None
               else np.asarray(first, np.int64).reshape(-1, 1)), xp=np)
    need = last - first + 1
    gb = group_blocks(bk, group_keys, key_bytes)
    return int(need.sum()), int((-(-need // gb) * gb).sum())


def _kernel(pos_ref, bt_ref, layer_ref, *refs, scale, s, g, hkv, d, bq, nq,
            tile_p, bk, gb, n_cols, quantized, paged, window, block=1,
            latent=0, walk=None):
    if quantized:
        # int8 cache: the per-block-per-kv-head scales ride as two more
        # SCALAR-PREFETCH operands — flat f32 (B·n_cols·hkv,) SMEM tables
        # already gathered per row by the wrapper, so the body reads one
        # scalar per (row, block, head) and nothing scale-sized is ever
        # blocked through VMEM (a (1, hkv) block does not tile on a TPU)
        ks_ref, vs_ref, *refs = refs
    first_ref = resume = None
    if walk == "shared":
        # the tiles' part of a two-part walk (``SharedWalk``): a "row" is
        # a q tile of stacked decode rows at the last position its members
        # share, read through its leader's table row; it hands on the
        # running (acc, m, l) unnormalised.  One more scalar, the live
        # tiles' count, steers the block index maps alone
        _, q_ref, k_hbm, *o_ref, k_buf, sems, slot_sc, acc_sc, m_sc, l_sc \
            = refs
        v_hbm = v_buf = None
    elif walk == "own":
        # the rows' part: row i walks from column ``first_ref[i]`` on,
        # starting from what its tile left (``resume``: acc, m, l blocks
        # picked by the row's place, the second scalar)
        (first_ref, _, q_ref, k_hbm, *resume, o_ref,
         k_buf, sems, slot_sc, acc_sc, m_sc, l_sc) = refs
        v_hbm = v_buf = None
    elif latent:
        # a latent pool (``LatentLayout``): the block that came in as K is
        # V too — its first ``latent`` lanes — so there is no V operand, no
        # V buffer and one copy a block
        (q_ref, k_hbm, o_ref,
         k_buf, sems, slot_sc, acc_sc, m_sc, l_sc) = refs
        v_hbm = v_buf = None
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems, slot_sc, acc_sc, m_sc, l_sc) = refs
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    n_rows = pl.num_programs(0)
    span = functools.partial(live_block_range, s=s, bq=bq, bk=bk,
                             n_cols=n_cols, window=window)

    def bounds(pos, tile, row):
        if first_ref is None:
            return span(pos, tile)
        return span(pos, tile, first=first_ref[row])
    pos_b = pos_ref[bi]
    first, last = bounds(pos_b, qi, bi)
    n_groups = (last - first + gb) // gb
    gk = gb * bk                                  # keys a group

    def block_at(ref, which, blk):
        """The ``(bk, Hkv·D)`` block ``blk`` of K (``which`` 0) or V (1) as
        it lies in HBM: the pool's, under this call's layer, or the
        reshaped contiguous cache's."""
        return ref.at[layer_ref[0], which, blk] if paged else ref.at[blk]

    def copies(row, lo, hi, j, slot):
        """Per block of group ``j`` of ``row``'s walk ``[lo, hi]``: whether
        its column is inside the walk, and its copies into buffer ``slot``
        (K and V; a latent block's one).  The table is read at
        ``min(col, hi)``, never outside the walk."""
        out = []
        for i in range(gb):
            col = lo + j * gb + i
            at_col = jnp.minimum(col, hi)
            if walk == "shared":        # an empty tile's ``hi`` is -1
                at_col = jnp.maximum(at_col, 0)
            blk = bt_ref[row, at_col]
            at = pl.ds(i * bk, bk)
            out.append((col <= hi, [
                pltpu.make_async_copy(block_at(ref, which, blk),
                                      buf.at[slot, at],
                                      sems.at[slot, which, i])
                for which, (ref, buf) in enumerate(
                    ((k_hbm, k_buf),) if latent
                    else ((k_hbm, k_buf), (v_hbm, v_buf)))]))
        return out

    def start(row, lo, hi, j, slot):
        for live, block_copies in copies(row, lo, hi, j, slot):
            @pl.when(live)
            def _start():
                for c in block_copies:
                    c.start()

    # the walk's buffers alternate across the WHOLE grid: this step's
    # group j sits in buffer (slot0 + j) % 2, and its first group was
    # issued by the step before (the very first step issues its own)
    @pl.when((bi == 0) & (qi == 0))
    def _first_step():
        slot_sc[0] = 0
        start(bi, first, last, 0, 0)

    slot0 = slot_sc[0]
    step_q = jnp.where(qi + 1 < nq, qi + 1, 0)
    step_b = jnp.minimum(jnp.where(qi + 1 < nq, bi, bi + 1), n_rows - 1)
    has_next = (qi + 1 < nq) | (bi + 1 < n_rows)
    next_first, next_last = bounds(pos_ref[step_b], step_q, step_b)

    acc_sc[...] = jnp.zeros_like(acc_sc)
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    if resume:
        @pl.when(first_ref[bi] > 0)
        def _resume():
            for sc, ref in zip((acc_sc, m_sc, l_sc), resume):
                sc[:, pl.ds(0, bq * g)] = ref[0]

    def group(j, carry):
        slot = (slot0 + j) % 2
        in_row = j + 1 < n_groups

        @pl.when(in_row | has_next)
        def _prefetch():
            start(jnp.where(in_row, bi, step_b),
                  jnp.where(in_row, first, next_first),
                  jnp.where(in_row, last, next_last),
                  jnp.where(in_row, j + 1, 0), 1 - slot)

        for i, (live, block_copies) in enumerate(
                copies(bi, first, last, j, slot)):
            @pl.when(live)
            def _wait():
                for c in block_copies:
                    c.wait()

            if i:       # a group's first column is always inside the walk
                @pl.when(jnp.logical_not(live))
                def _blank():
                    # never copied: whatever the buffer holds there must
                    # not reach the PV product as 0 · NaN (its scores are
                    # masked: the columns lie past every visible position)
                    values = k_buf if latent else v_buf
                    values[slot, pl.ds(i * bk, bk)] = jnp.zeros(
                        (bk, hkv * d), values.dtype)

        # key j visible to tile row r = si·g + gi (si local to the tile)
        # iff j <= pos_b + qi·bq + si — under a block mask, iff j lies no
        # later than the END of that position's block of ``block``; rows
        # past bq·g are sublane padding and rows whose query offset runs
        # past s are the last tile's ragged tail — both fully masked
        # (out = 0)
        cols = (jax.lax.broadcasted_iota(jnp.int32, (tile_p, gk), 1)
                + (first + j * gb) * bk)
        rr = jax.lax.broadcasted_iota(jnp.int32, (tile_p, gk), 0)
        si = qi * bq + rr // g
        seen = pos_b + si
        if block > 1:
            seen = seen // block * block + (block - 1)
        keep = (cols <= seen) & (rr < bq * g) & (si < s)
        if window is not None:
            keep &= cols > pos_b + si - window
        if quantized:
            block_of = jax.lax.broadcasted_iota(jnp.int32, (1, gk), 1) // bk

            def by_block(ref, h):
                """The group's per-block scales of head ``h`` as one
                (1, gk) row (a scalar when the group is one block)."""
                at = [(bi * n_cols + jnp.minimum(first + j * gb + i, last))
                      * hkv + h for i in range(gb)]
                row = ref[at[0]]
                for i in range(1, gb):
                    row = jnp.where(block_of >= i, ref[at[i]], row)
                return row

        for h in range(hkv):
            qh = q_ref[0, h]                              # (tile_p, d)
            kh = k_buf[slot, :, pl.ds(h * d, d)]          # static lane slice
            vh = (k_buf[slot, :, pl.ds(0, latent)] if latent
                  else v_buf[slot, :, pl.ds(h * d, d)])
            k_s = scale
            if quantized:
                # int8 in [-127, 127] is exact in bf16, so the cast is
                # lossless; each block's uniform scale folds into the
                # multiplies that are there anyway (K into the softmax
                # scale, V into the probabilities) — no per-element
                # dequant multiply on the blocks
                kh = kh.astype(qh.dtype)
                vh = vh.astype(qh.dtype)
                k_s = scale * by_block(ks_ref, h)
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * k_s  # (tile_p, gk)
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_sc[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)    # rescale earlier groups
            p = jnp.exp(sc - m_new)
            p = jnp.where(keep, p, 0.0)  # kill exp(NEG_INF - NEG_INF) = 1
            l_new = alpha * l_sc[h][:, :1] + jnp.sum(p, axis=1,
                                                     keepdims=True)
            if quantized:
                p = p * by_block(vs_ref, h)
            pv = jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_sc[h] = acc_sc[h] * alpha + pv
            m_sc[h] = jnp.broadcast_to(m_new, m_sc[h].shape)
            l_sc[h] = jnp.broadcast_to(l_new, l_sc[h].shape)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    slot_sc[0] = (slot0 + n_groups) % 2
    if walk == "shared":
        # an empty tile shares its output blocks with the last live one
        # (the index maps): it writes nothing
        @pl.when(n_groups > 0)
        def _hand_on():
            for ref, sc in zip(o_ref, (acc_sc, m_sc, l_sc)):
                ref[0] = sc[...]
        return
    for h in range(hkv):
        l = l_sc[h][:, :1]
        o_ref[0, h] = (acc_sc[h] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, pos,
                            scale: Optional[float] = None,
                            block_kv: int = 0,
                            interpret: bool = False,
                            k_scale=None, v_scale=None,
                            window: Optional[int] = None):
    """Flash-decode over a pre-allocated CONTIGUOUS cache → (B, s, Hq, D)
    in q.dtype (the paged pool has its own entry,
    :func:`paged_decode_attention_pallas`).

    q: (B, s, Hq, D) new-token queries (s = 1 in steady-state decode,
    small for prefill-into-occupied-slot); ``pos``: scalar or int (B,)
    per-row positions — cache slots > pos+i are masked, and the blocks
    past them are not read.  k_cache/v_cache are (B, L, Hkv, D) with the
    new K/V already written.  Raises NotImplementedError for shapes the
    kernel does not cover (callers fall back to the XLA math path).

    **int8 cache** (``k_scale``/``v_scale`` given): k_cache/v_cache hold
    int8 payloads and the f32 ``(B, n_granules, Hkv)`` scales carry the
    per-granule-per-kv-head dequant factor; the KV block is pinned to
    the scale granule (``kv_len // n_granules``, 128-aligned).  Dequant
    happens inside the walk by folding each granule's scale into the
    multiplies that follow the products, so the HBM stream is the int8
    payload — half the bf16 bytes.
    """
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("int8 cache needs both k_scale and v_scale")
    b, kv_len, hkv, d = k_cache.shape
    _check_q(q, hkv)
    bk = contiguous_block_kv(
        kv_len, k_scale.shape[1] if quantized else None, block_kv)
    # contiguous = paged under the identity table: the cache reshaped to
    # a (B·chunks, bk, Hkv·D) pool with table [bi, ki] = bi·chunks + ki —
    # same DMAs, one kernel
    full = kv_len // bk
    bt = (jnp.arange(b, dtype=jnp.int32)[:, None] * full
          + jnp.arange(full, dtype=jnp.int32)[None, :])
    k2 = k_cache.reshape(b * full, bk, hkv * d)
    v2 = v_cache.reshape(b * full, bk, hkv * d)
    return _flash_decode(
        q, k2, v2, pos, bt, scale=scale, interpret=interpret, layer=None,
        scales=(k_scale, v_scale) if quantized else None, window=window)


def paged_decode_attention_pallas(q, pool, layer: int, pos, block_tables,
                                  scale: Optional[float] = None,
                                  interpret: bool = False,
                                  pool_scale=None,
                                  window: Optional[int] = None,
                                  block: int = 1):
    """Flash-decode of layer ``layer`` over the PAGED pool
    (serving/kv_cache.py ``init_paged_kv_cache``) → (B, s, Hq, D).

    ``pool`` is the whole ``(L, 2, num_blocks, block_len, Hkv·D)`` array
    of every layer's K (index 0) and V (index 1) blocks, the new K/V
    already written, and ``layer`` a static int: the kernel takes the
    pool as it lies in HBM and copies ``pool[layer, 0 | 1, block]`` — no
    per-layer slice, no reshape.
    ``block_tables`` is the int (B, max_blocks) map from each row's
    logical block index to its physical block (every entry valid, dead
    tail null-filled).  The logical cache length is
    ``max_blocks · block_len`` and one table column is one block,
    so ``block_len`` must be 128-aligned.  ``Hkv`` is read from the
    shapes (``pool.shape[-1] // q.shape[-1]``), so a head-sharded shard of
    the pool works unchanged.  ``pos`` and the refusals
    are :func:`decode_attention_pallas`'s.

    **int8 pool** (``pool_scale`` given): ``pool`` holds int8 payloads and
    ``pool_scale`` is the f32 ``(L, 2, num_blocks, Hkv)`` array of
    per-block-per-kv-head dequant factors; each row's scale rows are
    gathered through its block table here, so the kernel's SMEM tables
    scale with the batch geometry (like the block table itself), not
    with the pool.

    ``window`` (static): sliding-window attention — key ``j`` is visible
    to the query at position ``i`` only while ``i - j < window``.  The
    mask gains that lower bound, and the block walk starts at the window's
    first block: blocks wholly behind the window of every query of a
    q tile are neither copied nor scored.  ``None`` is full causal
    attention.

    ``block`` (static): the BLOCK-CAUSAL mask of block-diffusion decoders
    — key ``j`` is visible to the query at position ``i`` iff ``j // block
    <= i // block``: a position sees its own block whole.  1 is the causal
    mask.  The caller keeps ``pos`` and ``s`` multiples of ``block`` (and
    ``block`` divides a q tile), so a tile's last visible key is its last
    query's own and the block walk (:func:`live_block_range`) is the causal
    one.
    """
    d = q.shape[-1]
    bk, hd = pool.shape[-2:]
    if bk % 128:
        raise NotImplementedError(
            f"paged block_len {bk} is not 128-aligned")
    _check_q(q, hd // d)
    bt = jnp.asarray(block_tables, jnp.int32)
    scales = None
    if pool_scale is not None:
        rows = jnp.take(jnp.asarray(pool_scale, jnp.float32)[layer], bt,
                        axis=1, mode="clip")    # (2, B, max_blocks, Hkv)
        scales = (rows[0], rows[1])
    return _flash_decode(
        q, pool, pool, pos, bt, scale=scale, interpret=interpret,
        layer=int(layer), scales=scales, window=window, block=block)


def latent_decode_attention_pallas(q, pool, layer: int, pos, block_tables,
                                   layout: LatentLayout,
                                   scale: float,
                                   interpret: bool = False,
                                   shared: Optional[SharedWalk] = None):
    """Flash-decode of layer ``layer`` over a LATENT paged pool →
    ``(B, s, H, layout.value_width)``: latent attention in its absorbed
    form, where every head's key and value are one shared cached entry.

    ``pool`` is the whole ``(L, 1, num_blocks, block_len, W)`` array, the
    new entries already written; ``q`` is ``(B, s, H, W)``: each head's
    query against the entry as it is stored (the latent part already
    carried through the key up-projection, the RoPE part beside it, zeros
    in the lanes the entry pads).  The score is the plain product over all
    ``W`` lanes times ``scale`` (the caller's: the plain form's head size
    decides it, not ``W``), the value the entry's first
    ``layout.value_width`` lanes.  The walk, the mask, ``pos`` and
    ``block_tables`` are :func:`paged_decode_attention_pallas`'s; there is
    no window, no block mask and no int8 form of this layout.

    ``shared`` (:class:`SharedWalk`; decode rows, ``s == 1``): the walk in
    TWO parts, each a call of the one body under the one name.  The tiles'
    part stacks the queries of up to :func:`tile_members` rows that hold
    the same blocks in their leading ``n`` columns into one q tile and
    walks those columns ONCE for them, through the leader's table row;
    every such position lies a whole block behind every member's own, so
    the mask hides the last copy group's padding and nothing else.  It
    hands on the running ``(acc, m, l)`` a (row, head).  The rows' part is
    the walk above from column ``n`` on, started from what the row's tile
    left instead of from zeros — the
    running-softmax algebra of every other group boundary.  Every position
    of every row is scored once, as without it; a row with ``n == 0``
    takes the walk above whole."""
    b, s, hq, w = q.shape
    if pool.ndim != 5 or pool.shape[1] != 1 or pool.shape[-1] != w:
        raise NotImplementedError(
            f"latent pool {pool.shape} is not (L, 1, blocks, block_len, "
            f"{w}) for queries of width {w}")
    bk = pool.shape[-2]
    if bk % 128 or w % _LANES or layout.value_width % _LANES \
            or layout.value_width > w:
        raise NotImplementedError(
            f"latent layout: block_len {bk}, entry width {w} and value "
            f"width {layout.value_width} must be 128-aligned")
    if hq > layout.q_rows or s > _MAX_Q_LEN:
        raise NotImplementedError(
            f"latent layout: {hq} heads > a q tile of {layout.q_rows} "
            f"rows, or q_len {s} > {_MAX_Q_LEN}")
    bt = jnp.asarray(block_tables, jnp.int32)
    walk = functools.partial(_flash_decode, scale=scale, interpret=interpret,
                             layer=int(layer), scales=None, latent=layout)
    if shared is None:
        return walk(q, pool, None, pos, bt)
    share = SharedWalk(*(jnp.asarray(x, jnp.int32) for x in shared))
    tiles, members = share.tile_rows.shape
    if s != 1 or members != tile_members(layout, hq):
        raise NotImplementedError(
            f"latent layout: a shared walk stacks {tile_members(layout, hq)}"
            f" decode rows of q_len 1 a tile, not {members} of q_len {s}")
    # the tiles' part: a tile's members' queries laid head-minor below one
    # another, at the last position they share, through the leader's table
    # row (gathers; nothing is scattered back: a row finds what its tile
    # left through ``share.at``, in the rows' part's block index map)
    left = walk(q[:, 0][share.tile_rows].reshape(tiles, 1, members * hq, w),
                pool, None, share.tile_n * bk - 1,
                bt[share.tile_rows[:, 0]], part="shared",
                part_scalars=(jnp.sum(share.tile_n > 0, dtype=jnp.int32)
                              .reshape(1),))
    return walk(q, pool, None, pos, bt, part="own",
                part_scalars=(share.n, share.at),
                resume=tuple(x.reshape(tiles * members, 1, hq, x.shape[-1])
                             for x in left))


def _check_q(q, hkv: int) -> None:
    """The q-side shape gates both layouts share (ops/pallas/limits.py)."""
    _, s, hq, d = q.shape
    if hkv == 0 or hq % hkv:
        raise NotImplementedError(
            f"q heads ({hq}) must be a multiple of kv heads ({hkv})")
    if hq // hkv > _MAX_Q_ROWS:
        raise NotImplementedError(
            f"GQA group size {hq // hkv} > {_MAX_Q_ROWS}")
    if s > _MAX_Q_LEN:
        raise NotImplementedError(
            f"q_len {s} > {_MAX_Q_LEN}: whole-prefill-shaped q belongs to "
            f"the flash kernel")
    if d > _limits.MAX_HEAD_DIM:
        raise NotImplementedError(
            f"head_dim {d} > {_limits.MAX_HEAD_DIM}")


def _flash_decode(q, k_arr, v_arr, pos, bt, *, scale, interpret, layer,
                  scales, window=None, block=1, latent=None, part=None,
                  part_scalars=(), resume=()):
    """Both layouts' way into the one ``pallas_call``.  ``k_arr``/``v_arr``
    are the operands as they lie in HBM (the kernel leaves them there):
    the paged pool twice with its ``layer``, or the contiguous cache's K
    and V reshaped to ``(blocks, bk, Hkv·D)`` with ``layer`` None; ``bt``
    is the (B, columns) table of block ids and ``scales`` the int8 cache's
    (B, columns, Hkv) K and V scale tables, or None; ``window`` the static
    sliding window, or None; ``block`` the static length of the
    block-causal mask's blocks (1: causal); ``latent`` the
    :class:`LatentLayout` of a latent pool (``v_arr`` None), or None;
    ``part`` the part of a two-part walk this call is (``"shared"`` |
    ``"own"``, :func:`latent_decode_attention_pallas`), its scalars
    (``part_scalars``: the live tiles' count | each row's first column and
    its place among the tiles' results) and, for the rows' part, those
    results (``resume``)."""
    b, s, hq, d = q.shape
    hkv = k_arr.shape[-1] // d
    block = int(block)
    tiles = q_tiles(s, hq // hkv, _tiling(latent)[0])
    if block > 1 and (s % block or tiles[0] % block):
        raise NotImplementedError(
            f"block mask of {block}: q_len {s} and the q tile "
            f"{tiles[0]} must be multiples of it")
    quantized = scales is not None
    n_cols = bt.shape[1]
    if quantized and b * n_cols * hkv > _limits.MAX_SCALE_TABLE:
        raise NotImplementedError(
            f"int8 scale table {b}x{n_cols}x{hkv} > "
            f"{_limits.MAX_SCALE_TABLE} SMEM entries")
    if getattr(pos, "ndim", 0) == 1:
        pos_arr = jnp.asarray(pos, jnp.int32)
    else:
        pos_arr = jnp.full((b,), pos, jnp.int32)
    paged = layer is not None

    # past every eligibility gate: this trace builds the kernel — count
    # which cache layout it was built for (routing visibility, trace-time
    # side effect only); a tiled q walk is the chunked-prefill mode, and
    # an active kernel_path_hint relabels the build (the serving engine's
    # speculative verify window counts as op="spec_verify" — same q-tiled
    # machinery, different meaning: the q rows are draft tokens scored
    # against the live cache, not a prompt chunk streaming in)
    from .. import _dispatch as _disp
    _disp.count_kernel_path(
        _disp.kernel_path_op(
            "chunked_prefill" if tiles[1] > 1
            else "decode_attention_kernel"),
        "paged" if paged else "contiguous",
        **({"cache": "int8"} if quantized else
           {"cache": "latent"} if latent else {}))

    scalars = (pos_arr, bt, jnp.full((1,), layer or 0, jnp.int32))
    if quantized:
        scalars += tuple(jnp.asarray(t, jnp.float32).reshape(-1)
                         for t in scales)
    two_part = {"part": part, "resume": resume} if part else {}
    return _flash_call(
        scalars + tuple(part_scalars), q, k_arr, v_arr,
        scale=float(d ** -0.5 if scale is None else scale), paged=paged,
        window=None if window is None else int(window), block=block,
        interpret=interpret, latent=latent, name=_disp.kernel_name(
            "latent_flash_decode" if latent else "flash_decode"),
        **two_part)


@functools.partial(jax.jit, static_argnames=("scale", "paged", "window",
                                             "block", "interpret", "name",
                                             "latent", "part"))
def _flash_call(scalars, q, k_arr, v_arr, *, scale, paged, window, interpret,
                name, block=1, latent=None, part=None, resume=()):
    """The ``pallas_call`` with the q layout round it, jitted on its own:
    a model's layers differ only in the VALUE of the layer scalar, so they
    share one trace of the kernel body and one lowering of it in every
    program that calls them (``scalars``: positions, block table, layer,
    then the int8 cache's K and V scale tables).  ``latent``: the pool is
    a latent one (``LatentLayout``; ``v_arr`` None): one operand, one
    buffer, the output ``value_width`` wide.  ``part``: one part of a
    two-part walk (:func:`latent_decode_attention_pallas`), whose scalars
    end ``scalars``: ``"shared"`` returns the tiles' float32 ``(acc, m,
    l)`` as the body holds them, ``(tiles, 1, tile rows, value_width |
    lanes)``; ``"own"`` starts row ``i`` from block ``at[i]`` of each of
    ``resume``."""
    b, s, hq, d = q.shape
    bk, hd = k_arr.shape[-2:]
    hkv = hd // d
    g = hq // hkv
    rows = s * g
    max_rows, group_keys = _tiling(latent)
    bq, nq = q_tiles(s, g, max_rows)
    dv = latent.value_width if latent else d      # the output's width
    kv = (k_arr,) if latent else (k_arr, v_arr)
    gb = group_blocks(bk, group_keys,
                      stored_key_bytes(hd, len(kv), k_arr.dtype))
    tile_p = max(8, -(-(bq * g) // 8) * 8)  # sublane-pad each q tile
    # grouped-GQA q layout: (B, Hkv, s·G, D), row r = si·g + gi — then cut
    # into nq tiles of bq·g rows, each sublane-padded to tile_p, so one
    # BlockSpec block == one padded tile at row offset qi·tile_p
    qg = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, rows, d)
    if nq * bq * g != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, nq * bq * g - rows), (0, 0)))
    qg = qg.reshape(b, hkv, nq, bq * g, d)
    if tile_p != bq * g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, tile_p - bq * g),
                          (0, 0)))
    qg = qg.reshape(b, hkv, nq * tile_p, d)

    kernel = functools.partial(
        _kernel, scale=scale, s=s, g=g, hkv=hkv, d=d, bq=bq, nq=nq,
        tile_p=tile_p, bk=bk, gb=gb, n_cols=scalars[1].shape[1],
        quantized=not part and len(scalars) > 3, paged=paged, window=window,
        block=block, **({"latent": dv} if latent else {}),
        **({"walk": part} if part else {}))

    def q_idx(bi, qi, *scalars):
        if part == "shared":
            # an empty tile (they come last) stays on the last live
            # tile's blocks: nothing is fetched for it, nothing written
            bi = jnp.minimum(bi, jnp.maximum(scalars[3][0] - 1, 0))
        return (bi, 0, qi, 0)

    def left_idx(bi, qi, *scalars):
        return (scalars[4][bi], 0, 0, 0)        # the row's place: ``at``

    outs = [(dv, q.dtype)]
    if part == "shared":        # (acc, m, l) as the scratch holds them
        outs = [(dv, jnp.float32), (_LANES, jnp.float32),
                (_LANES, jnp.float32)]
    out_shape = [jax.ShapeDtypeStruct((b, hkv, nq * tile_p, n), dtype)
                 for n, dtype in outs]
    out_specs = [pl.BlockSpec((1, hkv, tile_p, n), q_idx) for n, _ in outs]
    if part != "shared":
        (out_shape,), (out_specs,) = out_shape, out_specs

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, nq),
            in_specs=[pl.BlockSpec((1, hkv, tile_p, d), q_idx)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(kv)
            + [pl.BlockSpec((1, *x.shape[1:]), left_idx) for x in resume],
            out_specs=out_specs,
            scratch_shapes=[
                # two buffers of one group of K and of V blocks (a latent
                # pool: of its one array), a DMA semaphore a copy, and the
                # buffer the next step starts in
                *(pltpu.VMEM((2, gb * bk, hd), a.dtype) for a in kv),
                pltpu.SemaphoreType.DMA((2, len(kv), gb)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv, tile_p, dv), jnp.float32),
                pltpu.VMEM((hkv, tile_p, _LANES), jnp.float32),
                pltpu.VMEM((hkv, tile_p, _LANES), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        # sequential: a step issues the next step's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*scalars, qg, *kv, *resume)
    if part == "shared":
        return out
    out = out.reshape(b, hkv, nq, tile_p, dv)[:, :, :, :bq * g]
    out = out.reshape(b, hkv, nq * bq * g, dv)[:, :, :rows]
    out = out.reshape(b, hkv, s, g, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, hq, dv).astype(q.dtype)
