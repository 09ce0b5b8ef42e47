"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464), a linear-attention layer whose memory of a request is one
float32 matrix ``S`` (``d_k × d_v``) a head.  A token reads and writes it
whole::

    S̃   = exp(g_t) · S_{t-1}                       (g_t ≤ 0: the decay)
    r_t  = v_t − S̃ᵀ k_t                            (what S̃ gets wrong of v_t)
    S_t  = S̃ + β_t · k_t r_tᵀ                       (β_t in [0, 2])
    o_t  = S_tᵀ q_t

Three forms of it, all float32:

* :func:`gated_delta_recurrence` — the recurrence as written, a
  ``lax.scan`` a token: the oracle;
* the one-token STEP over many rows (the serving engine's decode rows):
  bound by the bandwidth of reading and writing every row's ``S`` once;
* the CHUNKED form for one row of many positions (a prompt chunk), the
  WY / UT transform over sub-chunks of ``SUB_CHUNK`` tokens: inside a
  sub-chunk everything that does not read ``S`` — the unit-lower-triangular
  solve ``T = (I + strict_lower(β_i k_i·k_j e^{γ_i−γ_j}))⁻¹``, ``W = T(β e^γ
  k)``, ``U = T(β v)`` — is matrix products over all sub-chunks at once
  (:func:`chunk_operands`); the sub-chunks are then walked in order with
  the carried ``S``: ``V' = U − W S``, ``o = (q e^γ) S + tril(q kᵀ e^{γ_i −
  γ_j}) V'``, ``S ← e^{γ_C} S + (k e^{γ_C − γ})ᵀ V'``.

**Padding is an identity step**: a token that is not ``valid`` takes ``g =
0`` and ``β = 0`` (:func:`mask_invalid`), and then ``S_t = S_{t-1}`` exactly
in all three forms.

**The serving state** is ONE leaf for all layers and rows, ``(layers, rows,
d_k, heads · d_v)``: heads side by side on the lane axis, so that a head of
``d_v`` 192 pads nothing in tiled memory.  :func:`gated_delta_update`
advances the rows of one part (``models.parts.DecodePart``) in that leaf: on
a TPU through the two Pallas kernels of ``ops/pallas/gated_delta.py``,
which alias the leaf (only the rows that hold a real token are read and
written, the rest of the leaf stays where it is); elsewhere, and when a
kernel refuses a shape, through the XLA twins here.  Counted under
``ops.kernel_path{op="gated_delta_step" | "gated_delta_chunk"}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.logging import vlog_once
from . import _dispatch
from .attention import (KIND_BACKEND, KIND_KERNEL, KIND_SHAPE, WARN_KINDS,
                        FallbackReason, reason_kind)

__all__ = ["gated_delta_recurrence", "gated_delta_step",
           "gated_delta_chunked", "gated_delta_update", "mask_invalid",
           "heads_to_lanes", "lanes_to_heads", "unit_lower_inverse",
           "chunk_operands", "sub_chunk", "SUB_CHUNK"]

SUB_CHUNK = 64
_HI = jax.lax.Precision.HIGHEST
_ein = functools.partial(jnp.einsum, precision=_HI,
                         preferred_element_type=jnp.float32)


def heads_to_lanes(s):
    """``(..., H, d_k, d_v)`` → the leaf's ``(..., d_k, H · d_v)``."""
    *lead, h, dk, dv = s.shape
    return jnp.moveaxis(s, -3, -2).reshape(*lead, dk, h * dv)


def lanes_to_heads(leaf, heads: int):
    """The inverse of :func:`heads_to_lanes`."""
    *lead, dk, hv = leaf.shape
    return jnp.moveaxis(leaf.reshape(*lead, dk, heads, hv // heads), -2, -3)


def mask_invalid(g, beta, valid):
    """``g`` and ``β`` (..., H) with the tokens that are not ``valid``
    (...,) made identity steps; ``valid`` None: all are."""
    if valid is None:
        return g, beta
    keep = jnp.asarray(valid)[..., None]
    return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)


def gated_delta_step(s, q, k, v, g, beta):
    """One token a row: ``s`` (..., H, d_k, d_v), q and k (..., H, d_k), v
    (..., H, d_v), g and β (..., H) → (o (..., H, d_v), s)."""
    s = s * jnp.exp(g)[..., None, None]
    r = v - _ein("...kv,...k->...v", s, k)
    s = s + (beta[..., None] * k)[..., :, None] * r[..., None, :]
    return _ein("...kv,...k->...v", s, q), s


def gated_delta_recurrence(q, k, v, g, beta, s0=None):
    """The oracle: one row's T tokens, a token at a time.  q, k (T, H,
    d_k), v (T, H, d_v), g, β (T, H), ``s0`` (H, d_k, d_v) or None for
    zeros → (o (T, H, d_v), S_T)."""
    f32 = jnp.float32
    q, k, v, g, beta = (jnp.asarray(x, f32) for x in (q, k, v, g, beta))
    if s0 is None:
        s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), f32)

    def step(s, x):
        o, s = gated_delta_step(s, *x)
        return s, o
    s, o = jax.lax.scan(step, jnp.asarray(s0, f32), (q, k, v, g, beta))
    return o, s


def sub_chunk(t: int) -> int:
    """The sub-chunk length for a row of ``t`` positions: ``SUB_CHUNK``, or
    for a shorter row the power of two (8 at least) that holds it."""
    if t >= SUB_CHUNK:
        return SUB_CHUNK
    return max(8, 1 << max(0, t - 1).bit_length())


def unit_lower_inverse(a):
    """``(I + A)⁻¹`` for ``A`` (..., C, C) STRICTLY lower triangular, by
    matrix products alone: the diagonal blocks of 16 by the finite Neumann
    series ``(I − N)(I + N²)(I + N⁴)(I + N⁸)`` (``N¹⁶ = 0``), then block
    pairs merged, ``[[T₁, 0], [−T₂ A₂₁ T₁, T₂]]``, until one block is left —
    every intermediate is a block of the true inverse, so nothing grows
    that the result does not hold.  Whole matrices under masks, no slicing
    (the form a kernel's lanes like too).  ``C`` is 16 · 2ᵐ, or a power of
    two below 16."""
    c = a.shape[-1]
    i = jnp.arange(c)
    eye = jnp.eye(c, dtype=a.dtype)

    def same_block(b):
        return (i[:, None] // b) == (i[None, :] // b)
    mm = functools.partial(jnp.matmul, precision=_HI)
    b = min(16, c)
    if c % b or (c // b) & (c // b - 1) or (c < 16 and c & (c - 1)):
        raise ValueError(f"unit_lower_inverse: C = {c} is not 16 · 2^m")
    n = jnp.where(same_block(b), a, 0.0)
    t, p, reach = eye - n, n, 1
    while 2 * reach < b:
        p, reach = mm(p, p), 2 * reach
        t = t + mm(t, p)
    while b < c:
        off = jnp.where(same_block(2 * b) & ~same_block(b), a, 0.0)
        t, b = t - mm(mm(t, off), t), 2 * b
    return t


def chunk_operands(q, k, v, g, beta, c: int):
    """What the chunked form computes WITHOUT the state, for one row of T
    tokens (q, k (T, H, d_k), v (T, H, d_v), g, β (T, H), invalid tokens
    already identity steps), padded with identity steps to N whole
    sub-chunks of c: a dict of, per sub-chunk n and head h,

    ``w`` (N, H, c, d_k)   ``T (β e^γ k)``: V' = u − w S
    ``u`` (N, H, c, d_v)   ``T (β v)``
    ``qd`` (N, H, c, d_k)  ``q e^γ``: the part of o that reads S
    ``p`` (N, H, c, c)     ``tril(q kᵀ e^{γ_i − γ_j})``: o += p V'
    ``kd`` (N, H, c, d_k)  ``k e^{γ_c − γ}``: S ← d S + kdᵀ V'
    ``d`` (N, H)           ``e^{γ_c}``, the sub-chunk's whole decay
    """
    pad = -q.shape[0] % c

    def heads_first(x):
        x = jnp.pad(jnp.asarray(x, jnp.float32),
                    ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(-1, c, *x.shape[1:]), 2, 1)
    q, k, v, g, beta = (heads_first(x) for x in (q, k, v, g, beta))
    gam = jnp.cumsum(g, axis=-1)                            # (N, H, c)
    i = jnp.arange(c)
    lower = i[:, None] >= i[None, :]
    # e^{γ_i − γ_j} where i ≥ j (an exponent ≤ 0), 0 above the diagonal
    decay = jnp.exp(jnp.where(lower, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))
    kk = _ein("nhid,nhjd->nhij", k, k)
    a = jnp.where(i[:, None] > i[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    tri = unit_lower_inverse(a)
    eg = jnp.exp(gam)[..., None]
    bcol = beta[..., None]
    return {
        "w": _ein("nhij,nhjd->nhid", tri, bcol * eg * k),
        "u": _ein("nhij,nhjd->nhid", tri, bcol * v),
        "qd": q * eg,
        "p": _ein("nhid,nhjd->nhij", q, k) * decay,
        "kd": k * jnp.exp(gam[..., -1:] - gam)[..., None],
        "d": jnp.exp(gam[..., -1]),
    }


def gated_delta_chunked(q, k, v, g, beta, s0=None, chunk: int = None):
    """The chunked form for one row, the XLA twin of the chunk kernel:
    same arguments and result as :func:`gated_delta_recurrence`.  T is
    padded to whole sub-chunks with identity steps."""
    t, h, dk = q.shape
    c = chunk or sub_chunk(t)
    ops = chunk_operands(q, k, v, g, beta, c)
    if s0 is None:
        s0 = jnp.zeros((h, dk, v.shape[2]), jnp.float32)

    def walk(s, x):
        vp = x["u"] - _ein("hcd,hdv->hcv", x["w"], s)
        o = (_ein("hcd,hdv->hcv", x["qd"], s)
             + _ein("hij,hjv->hiv", x["p"], vp))
        s = s * x["d"][:, None, None] + _ein("hcd,hcv->hdv", x["kd"], vp)
        return s, o
    s, o = jax.lax.scan(walk, jnp.asarray(s0, jnp.float32), ops)
    # (N, H, c, d_v) → (T, H, d_v)
    return jnp.moveaxis(o, 1, 2).reshape(-1, h, v.shape[2])[:t], s


# -- the serving leaf --------------------------------------------------------

def _reference(leaf, layer, first, q, k, v, g, beta, live, fresh):
    """The XLA twin of both kernels (arguments as
    :func:`gated_delta_update` hands them on): the step where a row holds
    one position, the chunked form a row where it holds more."""
    h = q.shape[2]
    rows = jax.lax.dynamic_slice_in_dim(leaf[layer], first, q.shape[0])
    s = jnp.where(fresh[:, None, None, None], 0.0, lanes_to_heads(rows, h))
    if q.shape[1] == 1:
        o, s = gated_delta_step(s, *(x[:, 0] for x in (q, k, v, g, beta)))
        o = o[:, None]
    else:
        o, s = jax.vmap(gated_delta_chunked)(q, k, v, g, beta, s)
    rows = jnp.where(live[:, None, None], heads_to_lanes(s), rows)
    return o, jax.lax.dynamic_update_slice(
        leaf, rows[None].astype(leaf.dtype), (layer, first, 0, 0))


def _run(op, reason, pallas_fn, reference_fn):
    """Count the decision and run it (``ops/attention.py``'s rule: the
    kernel, or the XLA twin when there is no Pallas backend or the kernel
    refuses the shape at call time)."""
    if reason is None:
        try:
            out = pallas_fn()
            _dispatch.count_kernel_path(op, "pallas")
            return out
        except NotImplementedError as e:
            reason = FallbackReason(str(e), KIND_KERNEL)
    _dispatch.count_kernel_path(op, "xla_math")
    if _dispatch.use_pallas() and reason_kind(reason) in WARN_KINDS:
        vlog_once(1, f"{op}:{reason}",
                  f"{op}: falling back to the XLA math path ({reason})")
    return reference_fn()


def gated_delta_update(leaf, layer: int, slots, q, k, v, g, beta, *,
                       valid=None, fresh=None):
    """Advance the rows of one part in the serving leaf.

    ``leaf`` (layers, rows, d_k, H · d_v) float32; the part's B rows are
    ``leaf[layer, first : first + B]`` with ``slots = (first, B)`` (None:
    all rows, from 0).  q, k (B, s, H, d_k), v (B, s, H, d_v), g, β (B, s,
    H): each row's s tokens.  ``valid`` bool (B, s), a prefix of each row
    (None: all): the real tokens; a row with none keeps its state.
    ``fresh`` bool (B,): the row starts from zeros whatever the leaf holds
    (a request's first tokens; a slot is reused without a reset).

    Returns (o (B, s, H, d_v) float32 — zeros for a row without a real
    token —, the leaf).  One position a row is the STEP, one row of many
    positions the CHUNK; several rows of several positions (``generate()``'s
    prefill of a batch) run the chunk's XLA twin a row."""
    b, s, h, dk = q.shape
    first = 0 if slots is None else slots[0]
    f32 = jnp.float32
    q, k, v, g, beta = (jnp.asarray(x, f32) for x in (q, k, v, g, beta))
    g, beta = mask_invalid(g, beta, valid)
    live = (jnp.ones((b,), bool) if valid is None
            else jnp.asarray(valid).any(axis=1))
    fresh = (jnp.zeros((b,), bool) if fresh is None
             else jnp.broadcast_to(jnp.asarray(fresh), (b,))) & live
    args = (leaf, layer, first, q, k, v, g, beta, live, fresh)
    reason = None
    if not _dispatch.use_pallas():
        reason = FallbackReason(
            f"no Pallas-capable backend ({_dispatch.default_backend()})",
            KIND_BACKEND)
    op = "gated_delta_step" if s == 1 else "gated_delta_chunk"
    if reason is None and s > 1 and b != 1:
        reason = FallbackReason(
            f"the chunk kernel walks one row, got {b}", KIND_SHAPE)

    def pallas():
        from .pallas import gated_delta as kernels
        return getattr(kernels, op + "_pallas")(
            *args, interpret=_dispatch.pallas_interpret())
    o, leaf = _run(op, reason, pallas, lambda: _reference(*args))
    return jnp.where(live[:, None, None, None], o, 0.0), leaf
