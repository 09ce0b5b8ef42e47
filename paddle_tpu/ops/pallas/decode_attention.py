"""Split-KV flash-decode Pallas TPU kernel — the batched/long-context
serving hot path.

TPU-native equivalent of the FlashDecoding scheme (Dao et al.; the
PagedAttention-class engines' decode kernel on GPU): at q_len 1 the
(1, L) score row gives the MXU nothing to tile, so the win is pure
dataflow — split the KV cache into chunks, keep online-softmax partials
(m, l, acc) in VMEM across the chunk walk, and never materialise the
(B, Hq, s, L) score tensor the XLA math path
(:func:`~paddle_tpu.ops.attention.cached_decode_attention_reference`)
builds in HBM.

What makes this kernel O(actual context depth) instead of O(max_length)
— the regime BENCH_DECODE.json flagged (b=8, max_length 8192: 4.27 ms vs
the 2.78 ms bf16 weight-stream floor, 0.652x of the bound, because the
math path streams and mask-softmaxes the dead tail of the pre-allocated
cache every step):

  * per-row positions arrive as a **scalar-prefetch** operand, so the
    KV-chunk BlockSpec index maps can read them *before* the grid step
    runs and **clamp dead-tail chunks to the last live block** — Pallas
    elides the DMA when consecutive grid steps map to the same block, so
    the dead tail of the cache is never streamed from HBM.  This is the
    dynamic-shape-safe form of "the host passes ceil((max(pos)+s)/BLOCK)
    as the KV-chunk grid bound": the bound is derived in-kernel from the
    position vector itself, the grid stays static, and the serving
    engine's once-jitted step function never retraces as slots deepen;
  * a caller who *does* know a static bound (the bench depth sweep)
    passes ``live_len`` and the grid is trimmed outright;
  * dead chunks also skip their matmuls via ``pl.when`` — a skipped
    chunk costs one predicated-off grid step, not bandwidth.

GQA stays grouped: Q is reshaped to (B, Hkv, G·s, D) and each kv head's
(G·s, D) query tile contracts the cache directly — bf16 operands on the
MXU with an fp32 accumulator, no Hq/Hkv KV broadcast.  A KV chunk is a
``(bk, Hkv·D)`` tile — rows of all kv heads' features side by side — so
it is one contiguous DMA and the per-head (bk, D) slice is a static lane
slice in VMEM.  The paged pool is STORED that way (below); the
contiguous (B, L, Hkv, D) cache is reshaped to it per call, which on the
TPU's tiled layouts is a physical copy of the layer's K and V (ROADMAP
S1; that cache's writers keep heads apart, so it keeps that cost).
Per-row ``pos`` masking happens inside the kernel
(key j visible to query row (si, g) iff j <= pos_b + si) with the same
fully-masked-row convention as the flash kernel (out = 0).

The cross-chunk merge is the same LSE algebra the ring-attention path
uses (ops/ring_attention.py ``merge_attention``), specialised to the
running (m, l, acc) form since chunks arrive sequentially.

**Chunked prefill** (serving/engine.py mixed steps): the same kernel
generalises from q_len 1 to a q *chunk* — a span of prompt tokens
attending its cached prefix plus its own causal self-block.  q is cut
into tiles of ``bq`` tokens (``bq·G <= 64`` MXU rows each, sublane-padded
per tile) walked by a second grid dimension; the per-row ``pos`` mask
already encodes "key j visible to query offset si iff j <= pos + si", so
prefix + self-block causality needs no new machinery, and the dead-tail
clamp becomes per-tile (early q tiles skip the chunk's own later KV
blocks — causal block skipping for free).  Routing for these shapes is
counted under ``ops.kernel_path{op="chunked_prefill"}``.

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
:func:`paged_decode_attention_pallas`): the kernel also serves the
block-table layout, where the cache of ALL layers is one pooled
``(L, 2, num_blocks, block_len, Hkv·D)`` array and each row's logical
positions are backed by the physical blocks its ``(B, max_blocks)`` block
table names.  The kernel is handed **the pool itself** and the static
layer index: K and V are the same operand, and their index maps return
``(layer, 0 | 1, table[bi, min(ki, last_live)], 0, 0)`` — the table rides
in as a SECOND scalar-prefetch operand.  Nothing is sliced out of the
pool and nothing is reshaped, so a step's HBM traffic is the live blocks
it reads and the rows it writes, whatever the pool's size.  One KV chunk
== one cache block (``block_len`` must be 128-aligned), so a block is one
contiguous DMA, blocks may be scattered anywhere in the pool, shared
between rows, or partially filled (the in-kernel ``pos`` mask already
handles partial blocks — column indices are logical).  The contiguous
layout runs the same kernel under the identity table
``table[bi, ki] = bi·chunks + ki`` over its reshaped
``(B·chunks, bk, Hkv·D)`` cache, which is how PR 2's dead-tail clamping
now reads — clamping the logical chunk index before the table lookup maps
dead-tail grid steps to the row's last live block, the DMA is elided, and
the kernel's reads still stop at the live prefix.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
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


def _pick_block_kv(kv_len: int, cap: int) -> int:
    """Largest KV chunk <= cap that divides kv_len on the 128-lane
    tiling; 0 when none exists (caller falls back to XLA)."""
    for d in range(min(cap, kv_len), 0, -1):
        if kv_len % d == 0 and d % 128 == 0:
            return d
    return 0


def _kernel(pos_ref, bt_ref, *refs, scale, s, g, hkv, d, bq, tile_p, bk,
            chunks, n_cols, quantized, window=None):
    if quantized:
        # int8 cache: the per-block-per-kv-head scales ride as two more
        # SCALAR-PREFETCH operands — flat f32 (B·n_cols·hkv,) SMEM tables
        # already gathered per row by the wrapper, so the body reads one
        # scalar per (row, chunk, head) and nothing scale-sized is ever
        # blocked through VMEM (a (1, hkv) block does not tile on a TPU)
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc = refs
    del bt_ref  # consumed by the index maps, not the body
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    pos_b = pos_ref[bi]
    # last chunk holding a key visible to ANY row of this q tile (query
    # offsets qi·bq .. min((qi+1)·bq, s) - 1)
    last_live = (pos_b + jnp.minimum((qi + 1) * bq, s) - 1) // bk

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    live = ki <= last_live
    if window is not None:
        # first chunk holding a key inside the window of ANY row of this
        # q tile (its earliest query, offset qi·bq, sees back furthest)
        live &= ki >= jnp.maximum(pos_b + qi * bq - window + 1, 0) // bk

    @pl.when(live)
    def _compute():
        # key j visible to tile row r = si·g + gi (si local to the tile)
        # iff j <= pos_b + qi·bq + si; rows past bq·g are sublane padding
        # and rows whose query offset runs past s are the last tile's
        # ragged tail — both fully masked (out = 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (tile_p, bk), 1) + ki * bk
        rr = jax.lax.broadcasted_iota(jnp.int32, (tile_p, bk), 0)
        si = qi * bq + rr // g
        keep = (cols <= pos_b + si) & (rr < bq * g) & (si < s)
        if window is not None:
            keep &= cols > pos_b + si - window
        kv = k_ref[0]  # (bk, hkv·d) — one contiguous chunk, all kv heads
        vv = v_ref[0]
        for h in range(hkv):
            qh = q_ref[0, h]                   # (tile_p, d)
            kh = kv[:, h * d:(h + 1) * d]      # static lane slice
            vh = vv[:, h * d:(h + 1) * d]
            if quantized:
                # int8 in [-127, 127] is exact in bf16, so the cast is
                # lossless; the block's uniform scale folds into the
                # existing post-dot scalar multiplies (K into the
                # softmax scale, V after the PV accumulate) — no
                # per-element dequant multiply on the chunk
                kh = kh.astype(qh.dtype)
                vh = vh.astype(qh.dtype)
                sc_at = (bi * n_cols + ki) * hkv + h
                k_s = scale * ks_ref[sc_at]
                v_s = vs_ref[sc_at]
            else:
                k_s = scale
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * k_s  # (tile_p, bk)
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_sc[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)    # rescale earlier chunks
            p = jnp.exp(sc - m_new)
            p = jnp.where(keep, p, 0.0)  # kill exp(NEG_INF - NEG_INF) = 1
            l_new = alpha * l_sc[h][:, :1] + jnp.sum(p, axis=1,
                                                     keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if quantized:
                pv = pv * v_s
            acc_sc[h] = acc_sc[h] * alpha + pv
            m_sc[h] = jnp.broadcast_to(m_new, m_sc[h].shape)
            l_sc[h] = jnp.broadcast_to(l_new, l_sc[h].shape)

    @pl.when(ki == chunks - 1)
    def _finish():
        for h in range(hkv):
            l = l_sc[h][:, :1]
            o_ref[0, h] = (acc_sc[h]
                           / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, pos,
                            scale: Optional[float] = None,
                            block_kv: int = 0,
                            live_len: Optional[int] = None,
                            interpret: bool = False,
                            k_scale=None, v_scale=None,
                            window: Optional[int] = None):
    """Flash-decode over a pre-allocated CONTIGUOUS cache → (B, s, Hq, D)
    in q.dtype (the paged pool has its own entry,
    :func:`paged_decode_attention_pallas`).

    q: (B, s, Hq, D) new-token queries (s = 1 in steady-state decode,
    small for prefill-into-occupied-slot); ``pos``: scalar or int (B,)
    per-row positions — cache slots > pos+i are masked.  k_cache/v_cache
    are (B, L, Hkv, D) with the new K/V already written.

    ``live_len``: optional static bound on max(pos)+s (trims the chunk
    grid outright; without it the scalar-prefetch clamp stops the HBM
    streaming at each row's live prefix dynamically).  Raises
    NotImplementedError for shapes the kernel does not cover (callers
    fall back to the XLA math path).

    **int8 cache** (``k_scale``/``v_scale`` given): k_cache/v_cache hold
    int8 payloads and the f32 ``(B, n_granules, Hkv)`` scales carry the
    per-granule-per-kv-head dequant factor; the KV chunk is pinned to
    the scale granule (``kv_len // n_granules``, 128-aligned).  Dequant
    happens inside the chunk loop by folding each granule's scale into
    the post-dot scalar multiplies, so the HBM stream is the int8
    payload — half the bf16 bytes.
    """
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("int8 cache needs both k_scale and v_scale")
    b, kv_len, hkv, d = k_cache.shape
    _check_q(q, hkv)
    if quantized:
        # the scale granule pins the KV chunk: one chunk == one
        # (block, head) scale entry, exactly the paged contract
        n_gran = k_scale.shape[1]
        bk = kv_len // n_gran
        if bk * n_gran != kv_len or bk % 128:
            raise NotImplementedError(
                f"int8 scale granule {kv_len}/{n_gran} is not a "
                f"128-aligned divisor of the cache length")
    else:
        if not block_kv:
            from ...flags import flag
            block_kv = int(flag("decode_attention_block_kv"))
        bk = _pick_block_kv(kv_len, block_kv)
        if not bk:
            raise NotImplementedError(
                f"max_length {kv_len} has no 128-aligned chunk "
                f"divisor <= {block_kv}")
    # contiguous = paged under the identity table: the cache reshaped to
    # a (B·chunks, bk, Hkv·D) pool with table [bi, ki] = bi·chunks + ki —
    # same DMAs, one kernel
    full = kv_len // bk
    bt = (jnp.arange(b, dtype=jnp.int32)[:, None] * full
          + jnp.arange(full, dtype=jnp.int32)[None, :])
    k2 = k_cache.reshape(b * full, bk, hkv * d)
    v2 = v_cache.reshape(b * full, bk, hkv * d)

    def at(blk):
        return (blk, 0, 0)

    return _flash_decode(
        q, k2, v2, (1, bk, hkv * d), at, at, pos, bt, scale=scale,
        live_len=live_len, interpret=interpret, layout="contiguous",
        scales=(k_scale, v_scale) if quantized else None, window=window)


def paged_decode_attention_pallas(q, pool, layer: int, pos, block_tables,
                                  scale: Optional[float] = None,
                                  live_len: Optional[int] = None,
                                  interpret: bool = False,
                                  pool_scale=None,
                                  window: Optional[int] = None):
    """Flash-decode of layer ``layer`` over the PAGED pool
    (serving/kv_cache.py ``init_paged_kv_cache``) → (B, s, Hq, D).

    ``pool`` is the whole ``(L, 2, num_blocks, block_len, Hkv·D)`` array
    of every layer's K (index 0) and V (index 1) blocks, the new K/V
    already written, and ``layer`` a static int: the kernel takes the
    pool as its K and its V operand and its index maps pick
    ``(layer, 0 | 1, block)`` — no per-layer slice, no reshape.
    ``block_tables`` is the int (B, max_blocks) map from each row's
    logical block index to its physical block (every entry valid, dead
    tail null-filled).  The logical cache length is
    ``max_blocks · block_len`` and the KV chunk is pinned to one block,
    so ``block_len`` must be 128-aligned.  ``Hkv`` is read from the
    shapes (``pool.shape[-1] // q.shape[-1]``), so a head-sharded shard of
    the pool works unchanged.  ``pos``, ``live_len`` and the refusals
    are :func:`decode_attention_pallas`'s.

    **int8 pool** (``pool_scale`` given): ``pool`` holds int8 payloads and
    ``pool_scale`` is the f32 ``(L, 2, num_blocks, Hkv)`` array of
    per-block-per-kv-head dequant factors; each row's scale rows are
    gathered through its block table here, so the kernel's SMEM tables
    scale with the batch geometry (like the block table itself), not
    with the pool.

    ``window`` (static): sliding-window attention — key ``j`` is visible
    to the query at position ``i`` only while ``i - j < window``.  The
    mask gains that lower bound, and the block walk a lower clamp beside
    the dead-tail one: blocks wholly behind the window of every query of a
    q tile are neither DMA'd nor scored.  ``None`` builds the kernel as it
    was.
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
        q, pool, pool, (None, None, 1, bk, hd),
        lambda blk: (layer, 0, blk, 0, 0),
        lambda blk: (layer, 1, blk, 0, 0), pos, bt, scale=scale,
        live_len=live_len, interpret=interpret, layout="paged",
        scales=scales, window=window)


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


def _flash_decode(q, k_arr, v_arr, kv_block, k_at, v_at, pos, bt, *, scale,
                  live_len, interpret, layout, scales, window=None):
    """The one ``pallas_call`` behind both layouts.  ``k_arr``/``v_arr``
    are the operands as they lie in HBM, ``kv_block`` the BlockSpec shape
    that cuts one ``(1, bk, Hkv·D)`` chunk out of them, and
    ``k_at``/``v_at`` map a physical block id to that chunk's block
    index; ``bt`` is the (B, chunks) table of block ids and ``scales``
    the int8 cache's (B, chunks, Hkv) K and V scale tables, or None;
    ``window`` the static sliding window, or None."""
    b, s, hq, d = q.shape
    bk, hd = kv_block[-2:]
    hkv = hd // d
    g = hq // hkv
    rows = s * g
    quantized = scales is not None
    # q tiling: one grid step covers bq query tokens (bq·g MXU rows).
    # s <= bq is the steady-decode / small-s case — nq == 1, exactly the
    # original kernel.  Larger s (a chunked-prefill q chunk attending its
    # paged prefix plus its own causal self-block) walks q tiles over a
    # second grid dimension; the per-tile dead-tail clamp skips KV chunks
    # past pos + (qi+1)·bq - 1, so early tiles also skip the chunk's own
    # later keys — causal block skipping for free.
    bq = min(s, max(1, _MAX_Q_ROWS // g))
    nq = -(-s // bq)
    if scale is None:
        scale = d ** -0.5
    n_cols = chunks = bt.shape[1]
    if quantized and b * n_cols * hkv > _limits.MAX_SCALE_TABLE:
        raise NotImplementedError(
            f"int8 scale table {b}x{n_cols}x{hkv} > "
            f"{_limits.MAX_SCALE_TABLE} SMEM entries")
    if live_len is not None:
        chunks = max(1, min(chunks, -(-int(live_len) // bk)))
    tile_p = max(8, -(-(bq * g) // 8) * 8)  # sublane-pad each q tile
    if getattr(pos, "ndim", 0) == 1:
        pos_arr = jnp.asarray(pos, jnp.int32)
    else:
        pos_arr = jnp.full((b,), pos, jnp.int32)
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
            "chunked_prefill" if nq > 1 else "decode_attention_kernel"),
        layout, **({"cache": "int8"} if quantized else {}))

    kernel = functools.partial(
        _kernel, scale=float(scale), s=s, g=g, hkv=hkv, d=d, bq=bq,
        tile_p=tile_p, bk=bk, chunks=chunks, n_cols=n_cols,
        quantized=quantized,
        **({} if window is None else {"window": int(window)}))

    def q_idx(bi, qi, ki, pos_ref, bt_ref, *_):
        return (bi, 0, qi, 0)

    def live_block(bi, qi, ki, pos_ref, bt_ref):
        # clamp the LOGICAL chunk index to this q tile's last live block,
        # then dereference the block table: dead-tail chunks re-map to the
        # same physical block as the previous grid step → Pallas elides
        # the DMA, so HBM traffic stops at the tile's live prefix.
        # Null-block aliasing rule (checked statically by the kernel
        # pre-flight's ClampCheck and asserted by kv_cache.table_row):
        # dead-tail table columns past `last` MAY hold NULL_BLOCK (0) —
        # the clamp guarantees they are never dereferenced — but a LIVE
        # column (<= last) mapping to block 0 would alias the null
        # block's pad data into this row's attention window.
        last = (pos_ref[bi] + jnp.minimum((qi + 1) * bq, s) - 1) // bk
        if window is None:
            return bt_ref[bi, jnp.minimum(ki, last)]
        # the same trick from below: grid steps before the tile's first
        # block inside the window re-map to that block, which is then
        # fetched once and, until the walk reaches it, not scored
        first = jnp.maximum(pos_ref[bi] + qi * bq - window + 1, 0) // bk
        return bt_ref[bi, jnp.clip(ki, first, last)]

    def k_idx(bi, qi, ki, pos_ref, bt_ref, *_):
        return k_at(live_block(bi, qi, ki, pos_ref, bt_ref))

    def v_idx(bi, qi, ki, pos_ref, bt_ref, *_):
        return v_at(live_block(bi, qi, ki, pos_ref, bt_ref))

    scalars = (pos_arr, bt)
    if quantized:
        scalars += tuple(jnp.asarray(t, jnp.float32).reshape(-1)
                         for t in scales)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, nq, chunks),
            in_specs=[
                pl.BlockSpec((1, hkv, tile_p, d), q_idx),
                pl.BlockSpec(kv_block, k_idx),
                pl.BlockSpec(kv_block, v_idx),
            ],
            out_specs=pl.BlockSpec((1, hkv, tile_p, d), q_idx),
            scratch_shapes=[
                pltpu.VMEM((hkv, tile_p, d), jnp.float32),
                pltpu.VMEM((hkv, tile_p, _LANES), jnp.float32),
                pltpu.VMEM((hkv, tile_p, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, nq * tile_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=_disp.kernel_name("flash_decode"),
    )(*scalars, qg, k_arr, v_arr)
    out = out.reshape(b, hkv, nq, tile_p, d)[:, :, :, :bq * g]
    out = out.reshape(b, hkv, nq * bq * g, d)[:, :, :rows]
    out = out.reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, hq, d).astype(q.dtype)
