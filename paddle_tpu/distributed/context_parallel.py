"""Context parallelism: the model-facing wrapper over ring/Ulysses attention.

Equivalent of the reference's sep-parallel integration (upstream: the
``sep`` axis of fleet's HybridCommunicateGroup + PaddleNLP's
RingFlashAttention module) — here in-tree and first-class.

``context_parallel_attention`` embeds a ``shard_map`` over the ``sep`` axis
inside the surrounding jit program: activations arrive sharded
(batch over dp×sharding, seq over sep, heads over mp per the model's
constraints) and the per-shard ring/Ulysses functions run XLA collectives
over the ICI ring.  On a mesh without a sep axis (or degree 1) it falls
back to plain flash attention.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from ..ops.attention import flash_attention
from ..ops.ring_attention import (ring_attention_shard,
                                  ulysses_attention_shard)
from ..utils.logging import vlog_once
from . import env

__all__ = ["context_parallel_attention"]


def _fallback(reason: str):
    """One-shot VLOG(1) when sequence parallelism is requested but inert —
    the caller gets plain (single-shard) flash attention instead."""
    vlog_once(1, f"context_parallel:{reason}",
              f"context_parallel_attention: running plain flash attention "
              f"({reason})")


def context_parallel_attention(q, k, v, causal: bool = True,
                               scale: Optional[float] = None,
                               mode: str = "ring", axis: str = "sep",
                               mesh=None, segment_ids=None):
    """Attention over seq-sharded activations.

    q: (B, S, Hq, D), k/v: (B, S, Hkv, D) with S the *global* sequence,
    sharded over ``axis`` by the caller's constraints.  mode: "ring" |
    "ulysses".  ``segment_ids``: optional (B, S) packed-document ids,
    sharded over ``axis`` like the sequence (the varlen × CP composition —
    SURVEY §5 long-context row).  Returns out (B, S, Hq, D), seq-sharded
    the same way.
    """
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"mode must be 'ring' or 'ulysses', got {mode!r}")
    m = mesh if mesh is not None else env.active_mesh()
    if m is None or axis not in m.axis_names or m.shape[axis] == 1:
        _fallback("no active mesh" if m is None
                  else f"mesh has no {axis!r} axis" if axis not in m.axis_names
                  else f"{axis!r} degree is 1")
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               segment_ids=segment_ids)
    shard_fn = (ring_attention_shard if mode == "ring"
                else ulysses_attention_shard)
    batch_axes = tuple(a for a in ("dp", "sharding") if a in m.axis_names)
    b_spec = batch_axes if batch_axes else None
    h_spec = "mp" if "mp" in m.axis_names else None
    qkv_spec = P(b_spec, axis, h_spec, None)
    lse_spec = P(b_spec, h_spec, axis)
    seg_spec = P(b_spec, axis)

    if segment_ids is None:
        fn = jax.shard_map(
            lambda q_, k_, v_: shard_fn(q_, k_, v_, axis, causal=causal,
                                        scale=scale),
            mesh=m,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=(qkv_spec, lse_spec))
        out, _ = fn(q, k, v)
    else:
        fn = jax.shard_map(
            lambda q_, k_, v_, s_: shard_fn(q_, k_, v_, axis, causal=causal,
                                            scale=scale, segment_ids=s_),
            mesh=m,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
            out_specs=(qkv_spec, lse_spec))
        out, _ = fn(q, k, v, segment_ids)
    return out
