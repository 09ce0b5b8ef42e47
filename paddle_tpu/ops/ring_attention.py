"""Ring attention + Ulysses: sequence/context-parallel attention.

TPU-native, in-tree equivalent of the reference's long-context stack
(upstream: the ``sep`` axis plumbing in fleet's topology.py; the ring
flash-attention itself lives out-of-tree in PaddleNLP's
ring_flash_attention.py — SURVEY.md §5 "long-context").  Here both schemes
are first-class framework ops (the survey's stated place to exceed the
reference in-tree):

  * **ring attention**: Q stays put; KV blocks rotate around the ``sep``
    mesh axis via ``lax.ppermute`` (collective-permute rides the ICI ring).
    Each hop runs the Pallas flash kernel on the resident block and merges
    online in log-space using the kernel's LSE output — the
    blockwise/ring-attention recurrence.  Causality is handled per hop:
    diagonal block = causal kernel, source-after-destination = skipped
    (masked to -inf), source-before = full attention.
  * **Ulysses**: ``lax.all_to_all`` re-shards seq↔heads so each rank runs
    full-sequence attention on a head slice, then transposes back.  Cheaper
    than ring for moderate sequence lengths; needs heads % sep == 0.

Both are *per-shard* functions to be used inside ``shard_map`` (the model
wraps them via paddle_tpu.distributed.context_parallel); autodiff flows
through ppermute/all_to_all, so no hand-written backward is needed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .attention import NEG_INF, flash_attention

__all__ = ["merge_attention", "ring_attention_shard",
           "ulysses_attention_shard"]


def merge_attention(out_a, lse_a, out_b, lse_b):
    """Combine two attention partial results over disjoint KV sets.

    out: (B, S, H, D); lse: (B, H, S) — the log-sum-exp the flash kernel
    returns.  Stable log-space merge; fully-masked parts (lse = NEG_INF)
    contribute nothing.
    """
    m = jnp.maximum(lse_a, lse_b)
    m = jnp.where(m <= NEG_INF / 2, 0.0, m)  # both dead: avoid -inf - -inf
    wa = jnp.exp(lse_a - m)                   # (B, H, S)
    wb = jnp.exp(lse_b - m)
    denom = jnp.maximum(wa + wb, 1e-37)
    lse = m + jnp.log(denom)
    # weights move to (B, S, H, 1) for the out layout
    wa_o = jnp.swapaxes(wa / denom, 1, 2)[..., None].astype(out_a.dtype)
    wb_o = jnp.swapaxes(wb / denom, 1, 2)[..., None].astype(out_b.dtype)
    out = out_a * wa_o + out_b * wb_o
    lse = jnp.where((lse_a <= NEG_INF / 2) & (lse_b <= NEG_INF / 2),
                    NEG_INF, lse)
    return out, lse


def _as_varying(x, like, axis_name):
    """Mark a constant as varying over every mesh axis that ``like`` varies
    over (plus ``axis_name``) — lax.switch branches and scan carries must
    agree on varying-axes types."""
    want = frozenset(getattr(jax.typeof(like), "vma", frozenset())) \
        | {axis_name}
    have = frozenset(getattr(jax.typeof(x), "vma", frozenset()))
    missing = tuple(want - have)
    return lax.pcast(x, missing, to="varying") if missing else x


def _block(q, k, v, mode, scale, axis_name, seg_q=None, seg_kv=None):
    """One Q-block × KV-block attention partial.  mode: 0=skip, 1=full,
    2=causal-diagonal.  Returns (out, lse).

    ``seg_q``/``seg_kv``: packed-document ids of the local queries and of
    the *visiting* KV block (they differ on off-diagonal hops) — the
    varlen × ring composition; cross-document pairs mask inside the flash
    kernel, and a hop whose whole KV block is cross-document yields dead
    rows (lse = -inf) that the merge ignores."""
    def skip(_):
        b, s, h, d = q.shape
        return (_as_varying(jnp.zeros_like(q), q, axis_name),
                _as_varying(jnp.full((b, h, s), NEG_INF, jnp.float32), q,
                            axis_name))

    def full(_):
        return flash_attention(q, k, v, causal=False, scale=scale,
                               return_lse=True, segment_ids=seg_q,
                               kv_segment_ids=seg_kv)

    def diag(_):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               return_lse=True, segment_ids=seg_q,
                               kv_segment_ids=seg_kv)

    return lax.switch(mode, (skip, full, diag), None)


def ring_attention_shard(q, k, v, axis_name: str, causal: bool = True,
                         scale: Optional[float] = None, segment_ids=None):
    """Per-shard ring attention (run inside shard_map over ``axis_name``).

    q/k/v: this rank's sequence slice, (B, S_local, H, D) / (B, S_local,
    H_kv, D).  Global sequence order = rank order along the axis.
    ``segment_ids``: this rank's slice of the packed-document ids,
    (B, S_local) — they rotate around the ring WITH the KV blocks, so each
    hop masks local queries against the visiting block's documents (the
    varlen × context-parallel composition; LSE merge is unchanged).
    Returns (out, lse) for the local slice.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    p = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]  # KV moves to the next rank
    seg_q = (None if segment_ids is None
             else jnp.asarray(segment_ids, jnp.int32))

    def step(carry, t):
        out, lse, kt, vt, st = carry
        src = (my - t) % p  # whose KV block we hold at hop t
        if causal:
            mode = jnp.where(src == my, 2, jnp.where(src < my, 1, 0))
        else:
            mode = jnp.asarray(1)
        o_t, l_t = _block(q, kt, vt, mode, scale, axis_name,
                          seg_q=seg_q, seg_kv=st)
        out, lse = merge_attention(out, lse, o_t, l_t)
        # rotate every hop (uniform across ranks — collectives must not sit
        # under data-dependent control flow); the p-th rotation restores KV
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        if st is not None:
            st = lax.ppermute(st, axis_name, perm)
        return (out, lse, kt, vt, st), None

    b, s, h, d = q.shape
    out0 = _as_varying(jnp.zeros_like(q), q, axis_name)
    lse0 = _as_varying(jnp.full((b, h, s), NEG_INF, jnp.float32), q,
                       axis_name)
    (out, lse, _, _, _), _ = lax.scan(step, (out0, lse0, k, v, seg_q),
                                      jnp.arange(p))
    return out, lse


def ulysses_attention_shard(q, k, v, axis_name: str, causal: bool = True,
                            scale: Optional[float] = None, segment_ids=None):
    """Per-shard Ulysses attention: all_to_all seq↔heads, full-seq flash,
    all_to_all back.  Heads (q and kv) must divide the axis size.

    ``segment_ids``: this rank's (B, S_local) packed-document ids; since
    every rank sees the FULL sequence after the all_to_all (on a head
    slice), the ids are all-gathered along the axis — (B, S) int32 is
    cheap on the wire — and the flash kernel masks as in the single-shard
    varlen case."""
    p = lax.axis_size(axis_name)

    def to_full_seq(x):  # (B, S/p, H, D) -> (B, S, H/p, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_local_seq(x):  # (B, S, H/p, D) -> (B, S/p, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    if q.shape[2] % p or k.shape[2] % p:
        raise ValueError(f"Ulysses needs heads divisible by the cp degree "
                         f"(q heads {q.shape[2]}, kv heads {k.shape[2]}, "
                         f"degree {p})")
    qf, kf, vf = to_full_seq(q), to_full_seq(k), to_full_seq(v)
    seg_full = (None if segment_ids is None
                else lax.all_gather(jnp.asarray(segment_ids, jnp.int32),
                                    axis_name, axis=1, tiled=True))
    out, lse = flash_attention(qf, kf, vf, causal=causal, scale=scale,
                               return_lse=True, segment_ids=seg_full)
    # lse is (B, H/p, S_global): transpose back to the per-shard contract
    # (B, H_local, S_local) that ring_attention_shard honours
    lse = lax.all_to_all(lse, axis_name, split_axis=2, concat_axis=1,
                         tiled=True)
    return to_local_seq(out), lse
