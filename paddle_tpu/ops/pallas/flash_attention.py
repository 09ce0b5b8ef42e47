"""Blocked flash-attention Pallas TPU kernel (forward + backward).

TPU-native equivalent of the reference's flash-attention integration
(upstream layout: paddle/phi/kernels/gpu/flash_attn_kernel.cu +
flash_attn_grad_kernel.cu, which wrap the external CUDA flashattn library).
Here the kernel is first-party, written for the MXU/VMEM architecture:

  * online-softmax forward (Flash-2): the KV loop is the innermost grid
    dimension; running max ``m``, normaliser ``l`` and the fp32 accumulator
    live in VMEM scratch that persists across that dimension, so the
    (Sq, Skv) score matrix never exists in HBM;
  * returns the per-row log-sum-exp (``softmax_lse`` in the reference's
    API) — the hook that makes ring/context-parallel attention possible;
  * backward recomputes scores blockwise from (q, k, v, out, lse) — the
    Flash-2 two-kernel scheme: one accumulating dq over KV blocks, one
    accumulating dk/dv over Q blocks, with ``delta = rowsum(dO·O)``
    precomputed in XLA;
  * GQA: K/V keep their own (fewer) heads; the BlockSpec index maps fold
    the q-head → kv-head mapping, so grouped KV is never broadcast in HBM;
  * causal masking is bottom-right aligned (matches the reference's
    flash-attn convention when Sq < Skv) and fully-masked tiles skip their
    matmuls via ``pl.when``.

Layout: public API takes (B, S, H, D) (the reference's flash-attn layout);
kernels run in (B, H, S, D).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
from . import limits as _limits
from .._dispatch import kernel_name

_LANES = _limits.LANES  # VPU lane width: m/l scratch rows padded to this


def _aligned_divisor(seq: int, cap: int, align: int) -> int:
    """Largest block <= cap that divides seq on the (8,128) register
    tiling — so any aligned seq gets the kernel at the best dividing tile
    instead of falling back when the flag doesn't divide it."""
    for d in range(min(cap, seq), 0, -1):
        if seq % d == 0 and d % align == 0:
            return d
    return min(cap, seq)  # none aligned: _validate rejects → XLA path


def _block_sizes(sq: int, skv: int, head_dim: int):
    """Tile sizes for the Pallas grid; tunable via the
    ``flash_attention_block_q``/``flash_attention_block_kv`` flags (parity:
    the reference's FLAGS-tuned fused-attention tiling).

    The flag values are swept at head_dim 128 (see flags.py); for larger
    heads the caps scale down by d/128 so the fp32 scores + q/kv/acc tiles
    stay inside VMEM — a Mosaic OOM is a hard compile error, not a
    catchable fallback."""
    from ...flags import flag
    scale = max(1, head_dim // 128)
    cap_q = max(8, int(flag("flash_attention_block_q")) // scale)
    cap_k = max(128, int(flag("flash_attention_block_kv")) // scale)
    return (_aligned_divisor(sq, cap_q, 8),
            _aligned_divisor(skv, cap_k, 128))


def _validate(q, k, v, sq, skv, bq, bk):
    if sq % bq or skv % bk:
        raise NotImplementedError(
            f"flash kernel needs seq divisible by block ({sq}%{bq}, "
            f"{skv}%{bk})")
    if bq % 8 or bk % 128:
        # scores tile is (bq sublanes x bk lanes): keep blocks on the
        # (8, 128) register tiling; odd seqs shorter than the block would
        # otherwise become odd-sized single blocks — let those take the
        # XLA path instead of a Mosaic corner case
        raise NotImplementedError(
            f"flash kernel blocks must align to (8, 128), got ({bq}, {bk})")
    if q.shape[-1] != k.shape[-1] or k.shape[:2] != v.shape[:2]:
        raise NotImplementedError("q/k/v head_dim mismatch")
    if k.shape[1] == 0 or q.shape[1] % k.shape[1]:
        raise NotImplementedError("q heads must be a multiple of kv heads")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _seg_mask(sq_ref, skv_ref):
    """Segment-id blocks → (bq, bk) same-document mask.

    Blocks arrive pre-broadcast in Mosaic-friendly layouts (q ids over the
    lane dim, kv ids over sublanes — the (8,128) tiling forbids raw (1, b)
    blocks): sq_ref (1, bq, _LANES), skv_ref (1, 8, bk)."""
    return sq_ref[0][:, :1] == skv_ref[0][:1, :]


def _seg_broadcast(seg_q, seg_kv):
    """(B, Sq)/(B, Skv) ids → lane/sublane-broadcast arrays for the grid."""
    b, sq = seg_q.shape
    skv = seg_kv.shape[1]
    q3 = jnp.broadcast_to(seg_q.astype(jnp.int32)[:, :, None],
                          (b, sq, _LANES))
    kv3 = jnp.broadcast_to(seg_kv.astype(jnp.int32)[:, None, :],
                           (b, 8, skv))
    return q3, kv3


def _mask_for(causal, segmented, bq, bk, q_start, kv_start, offset,
              sq_ref, skv_ref):
    mask = None
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (cols + kv_start) <= (rows + q_start + offset)
    if segmented:
        sm = _seg_mask(sq_ref, skv_ref)
        mask = sm if mask is None else (mask & sm)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, segmented,
                offset, bq, bk, kv_steps):
    if segmented:
        sq_ref, skv_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q_start = qi * bq
    kv_start = ki * bk
    # bottom-right causal: query row i attends to kv cols <= i + offset;
    # fully-masked tiles skip their matmuls entirely
    run = (kv_start <= q_start + (bq - 1) + offset) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        mask = _mask_for(causal, segmented, bq, bk, q_start, kv_start,
                         offset, sq_ref if segmented else None,
                         skv_ref if segmented else None)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:, :1]                                   # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)              # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                        # rescale old
        p = jnp.exp(s - m_new)                                 # (bq, bk)
        if mask is not None:
            # exp(NEG_INF - NEG_INF) = 1 for fully-masked rows; zero it
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                    # (bk, d)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + pv
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l = l_sc[:, :1]
        safe_l = jnp.maximum(l, 1e-37)
        o_ref[0, 0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        lse = m_sc[:, :1] + jnp.log(safe_l)
        # fully-masked rows: lse = -inf-ish, out = 0 (matches reference).
        # lane dim broadcast to _LANES: TPU block tiling needs a 128 last dim
        lse_ref[0, 0] = jnp.broadcast_to(
            jnp.where(l > 0, lse, NEG_INF), (lse.shape[0], lse_ref.shape[-1]))


def _fwd(q, k, v, seg_q=None, seg_kv=None, scale: float = 1.0,
         causal: bool = False, interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) → (out, lse).
    seg_q/seg_kv: optional (B, Sq)/(B, Skv) int32 packed-document ids."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    bq, bk = _block_sizes(sq, skv, d)
    offset = skv - sq
    kv_steps = skv // bk
    segmented = seg_q is not None

    grid = (b, hq, sq // bq, skv // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, segmented=segmented,
        offset=offset, bq=bq, bk=bk, kv_steps=kv_steps)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, bq, _LANES), lambda b_, h, qi, ki: (b_, qi, 0)),
            pl.BlockSpec((1, 8, bk), lambda b_, h, qi, ki: (b_, 0, ki)),
        ]
        args += list(_seg_broadcast(seg_q, seg_kv))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=kernel_name("flash_attn_fwd"),
    )(*args)
    return out, lse  # lse lane-broadcast (b, hq, sq, _LANES); callers slice


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, segmented, offset, bq, bk, kv_steps):
    if segmented:
        sq_ref, skv_ref, dq_ref, dq_sc = rest
    else:
        sq_ref = skv_ref = None
        dq_ref, dq_sc = rest
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q_start = qi * bq
    kv_start = ki * bk
    run = (kv_start <= q_start + (bq - 1) + offset) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                       # (bq, 1)
        delta = delta_ref[0, 0][:, :1]                   # (bq, 1)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(causal, segmented, bq, bk, q_start, kv_start,
                         offset, sq_ref, skv_ref)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                             # (bq, bk)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # kill exp(NEG_INF - NEG_INF) = 1
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_sc[:] += jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == kv_steps - 1)
    def _finish():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, segmented, offset, bq, bk, q_steps):
    if segmented:
        sq_ref, skv_ref, dk_ref, dv_ref, dk_sc, dv_sc = rest
    else:
        sq_ref = skv_ref = None
        dk_ref, dv_ref, dk_sc, dv_sc = rest
    qi = pl.program_id(3)
    ki = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q_start = qi * bq
    kv_start = ki * bk
    run = (kv_start <= q_start + (bq - 1) + offset) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(causal, segmented, bq, bk, q_start, kv_start,
                         offset, sq_ref, skv_ref)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # kill exp(NEG_INF - NEG_INF) = 1
        dv_sc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                     # (bq, bk)
        dk_sc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(qi == q_steps - 1)
    def _finish():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, interpret, res, grads):
    q, k, v, seg_q, seg_kv, out, lse4 = res  # lse4: lane-broadcast residual
    do, dlse = grads
    do = do.astype(q.dtype)
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    bq, bk = _block_sizes(sq, skv, d)
    offset = skv - sq
    segmented = seg_q is not None
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # (b, hq, sq)
    # the lse cotangent folds into the ds formula exactly:
    #   ds = p*(dp - delta)*scale + p*dlse*scale = p*(dp - (delta-dlse))*scale
    delta = delta - dlse.astype(jnp.float32)
    # lane-broadcast for TPU block tiling (last dim = _LANES); lse stays in
    # its broadcast layout from the forward — no slice/re-broadcast round trip
    delta4 = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    seg_args = list(_seg_broadcast(seg_q, seg_kv)) if segmented else []

    def seg_specs(ix_q, ix_kv):
        return ([pl.BlockSpec((1, bq, _LANES), ix_q),
                 pl.BlockSpec((1, 8, bk), ix_kv)] if segmented else [])

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, segmented=segmented,
        offset=offset, bq=bq, bk=bk, kv_steps=skv // bk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, hq, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
        ] + seg_specs(lambda b_, h, qi, ki: (b_, qi, 0),
                      lambda b_, h, qi, ki: (b_, 0, ki)),
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=kernel_name("flash_attn_bwd_dq"),
    )(q, k, v, do, lse4, delta4, *seg_args)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, segmented=segmented,
        offset=offset, bq=bq, bk=bk, q_steps=sq // bq)
    # per-q-head dk/dv; grouped heads are reduced after the kernel
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hq, skv // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, ki, qi: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, ki, qi: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, ki, qi: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, ki, qi: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b_, h, ki, qi: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b_, h, ki, qi: (b_, h, qi, 0)),
        ] + seg_specs(lambda b_, h, ki, qi: (b_, qi, 0),
                      lambda b_, h, ki, qi: (b_, 0, ki)),
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ki, qi: (b_, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ki, qi: (b_, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=kernel_name("flash_attn_bwd_dkv"),
    )(q, k, v, do, lse4, delta4, *seg_args)
    if g > 1:
        dk = dk.reshape(b, hkv, g, skv, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, hkv, g, skv, d).sum(axis=2).astype(v.dtype)
    if segmented:
        import numpy as _np
        f0 = jax.dtypes.float0
        return (dq, dk, dv, _np.zeros(seg_q.shape, f0),
                _np.zeros(seg_kv.shape, f0))
    return dq, dk, dv, None, None


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, seg_q, seg_kv, scale, causal, interpret):
    out, lse4 = _fwd(q, k, v, seg_q, seg_kv, scale, causal, interpret)
    return out, lse4[..., 0]


def _flash_fwd(q, k, v, seg_q, seg_kv, scale, causal, interpret):
    out, lse4 = _fwd(q, k, v, seg_q, seg_kv, scale, causal, interpret)
    return (out, lse4[..., 0]), (q, k, v, seg_q, seg_kv, out, lse4)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention_pallas(q, k, v, causal: bool = False,
                           scale: Optional[float] = None,
                           interpret: bool = False, segment_ids=None,
                           kv_segment_ids=None):
    """(B, S, H, D) flash attention → (out (B,S,H,D), lse (B,H,S)).

    ``segment_ids``: optional (B, Sq) int packed-document ids (varlen
    form); cross-document pairs are masked INSIDE the kernel — packed
    pretraining batches keep the flash memory profile instead of an O(S²)
    masked fallback.  ``kv_segment_ids``: optional (B, Skv) ids for the
    keys when they are NOT the queries' own positions — the ring-attention
    case, where each hop attends a visiting KV block from another rank's
    sequence slice; defaults to ``segment_ids`` (self-attention)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if scale is None:
        scale = d ** -0.5
    bq, bk = _block_sizes(sq, skv, d)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    _validate(qt, kt, vt, sq, skv, bq, bk)
    if segment_ids is not None and kv_segment_ids is None and sq != skv:
        raise NotImplementedError(
            "segment_ids without kv_segment_ids assume self-attention "
            "(sq == skv); pass kv_segment_ids for cross-slice attention")
    seg_q = (None if segment_ids is None
             else jnp.asarray(segment_ids, jnp.int32))
    seg_kv = (seg_q if kv_segment_ids is None
              else jnp.asarray(kv_segment_ids, jnp.int32))
    if seg_q is None and seg_kv is not None:
        raise ValueError("kv_segment_ids requires segment_ids")
    out, lse = _flash(qt, kt, vt, seg_q, seg_kv, float(scale), bool(causal),
                      bool(interpret))
    return jnp.swapaxes(out, 1, 2), lse
