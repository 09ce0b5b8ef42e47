"""Llama-family decoder — the flagship model.

The reference keeps model code out-of-tree (PaddleNLP's modeling_llama builds
on the framework's fused_attention / fused_rope / mp_layers / PipelineLayer);
here the model is in-tree because it is the north-star benchmark workload
(BASELINE.md: Llama-3-8B hybrid-parallel tokens/sec/chip + MFU).

TPU-first design decisions:
  * every parameter carries its hybrid-parallel ``PartitionSpec`` at creation
    (tp on the ``mp`` axis, FSDP/ZeRO-3 on the ``sharding`` axis) — GSPMD
    inserts the all-gathers/psums that the reference's mp_layers +
    group_sharded stage-3 implement by hand;
  * attention runs through ``paddle_tpu.ops.flash_attention`` (Pallas kernel
    on TPU, returns LSE so ring/context parallelism can merge blocks);
  * RoPE caches are fp32 buffers, activations bf16, losses/reductions fp32;
  * activation layout is (batch, seq, hidden) with batch sharded over
    (dp, sharding) and seq over sep (context parallelism) via sharding
    constraints between blocks;
  * recompute ≙ ``jax.checkpoint`` around each decoder block
    (config.recompute), the reference's fleet recompute equivalent.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.fleet.mp_layers import constrain, vocab_parallel_lookup
from ..nn import functional as F
from ..tensor.math import matmul
from ..nn import initializer as I
from ..nn.common import RMSNorm
from ..nn.layer import Layer
from ..ops import build_rope_cache, flash_attention, fused_rope
from .parts import CausalLMDecode, part_by_part, part_site

__all__ = ["LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
           "LlamaModel", "LlamaForCausalLM", "llama3_8b_config",
           "tiny_llama_config"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"
    recompute: bool = False
    # remat policy when recompute=True: "full" (save only block boundaries),
    # "dots" (save matmul outputs, recompute elementwise — the reference's
    # selective recompute; cheaper re-FLOPs, more memory)
    recompute_policy: str = "full"
    # context parallelism over the sep axis: "ring" | "ulysses" | "gspmd"
    # ("gspmd" = no explicit CP; XLA gathers KV per the sharding constraints)
    context_parallel: str = "ring"

    def __post_init__(self):
        if self.context_parallel not in ("ring", "ulysses", "gspmd"):
            raise ValueError(
                f"context_parallel must be 'ring', 'ulysses' or 'gspmd', "
                f"got {self.context_parallel!r}")
        if self.recompute_policy not in ("full", "dots"):
            raise ValueError(
                f"recompute_policy must be 'full' or 'dots', "
                f"got {self.recompute_policy!r}")

    @property
    def remat_policy(self):
        if self.recompute_policy == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return None  # full remat

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama3_8b_config(**overrides) -> LlamaConfig:
    """Llama-3-8B (the BASELINE.md workload)."""
    cfg = LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=8192, rms_norm_eps=1e-5, rope_theta=500000.0,
        dtype="bfloat16")
    return dataclasses.replace(cfg, **overrides)


def tiny_llama_config(**overrides) -> LlamaConfig:
    """Small config for tests/dry runs."""
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128)
    return dataclasses.replace(cfg, **overrides)


def _batch_spec(ndim: int) -> Tuple:
    """Activation sharding: batch over (dp, sharding), seq over sep."""
    return (("dp", "sharding"), "sep") + (None,) * (ndim - 2)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _quantized_paged_write(kv, sc, idx: int, kvsl: int, x, phys, off):
    """Scatter-time int8 quantization into the paged pool — the write
    half of the quantized KV cache (the read half is the flash-decode
    kernel's in-chunk dequant).

    ``kv``: (L, 2, nb, bl, Hkv·D) int8 pool; ``sc``: (L, 2, nb, Hkv)
    f32 per-block-per-kv-head scales; ``x``: (B, s, Hkv, D) new K or V;
    ``phys``/``off``: (B, s) physical block / in-block offset per token.
    Heads are split on the (B, s) gathered blocks only, never on the pool.

    Per-block scales are RUNNING maxima, so a new token whose absmax
    exceeds its block's current scale grows the scale — and the block's
    existing int8 payload must be re-expressed under the new scale or
    its values would silently inflate.  Two-phase scatter, both phases
    order-independent under duplicate indices:

      1. block phase — scatter-max the per-token needed scales into the
         scale rows, then rewrite each touched block's payload by
         ``round(payload · old/new)``; tokens sharing a block gather the
         SAME (old, new) pair, so duplicate block writes carry identical
         payloads;
      2. token phase — quantize each new token under its block's final
         scale and scatter at its unique (phys, off) cell.

    Pad tokens ride in with ``phys == 0`` (the null block): its scale
    and payload become junk, which the null-block convention already
    guarantees no reader trusts.  A zero final scale (empty block, zero
    token) quantizes through a guard divisor of 1.
    """
    f32 = jnp.float32
    b, s, hkv, d = x.shape
    needed = jnp.max(jnp.abs(x.astype(f32)), axis=-1) / 127.0  # (B,s,Hkv)
    old = sc[idx, kvsl, phys]                                  # (B,s,Hkv)
    sc = sc.at[idx, kvsl, phys].max(needed)
    new = sc[idx, kvsl, phys]
    safe = jnp.where(new > 0, new, 1.0)
    ratio = jnp.where(new > 0, old / safe, 0.0)
    pay = kv[idx, kvsl, phys]                            # (B,s,bl,Hkv·D)
    pay = jnp.clip(jnp.round(pay.astype(f32).reshape(b, s, -1, hkv, d)
                             * ratio[:, :, None, :, None]), -127, 127)
    kv = kv.at[idx, kvsl, phys].set(
        pay.astype(jnp.int8).reshape(b, s, -1, hkv * d))
    tok = jnp.clip(jnp.round(x.astype(f32) / safe[..., None]), -127, 127)
    kv = kv.at[idx, kvsl, phys, off].set(
        tok.astype(jnp.int8).reshape(b, s, hkv * d))
    return kv, sc


@functools.partial(jax.jit, static_argnums=(2, 3))
def _quantized_contiguous_write(kv, sc, idx: int, kvsl: int, x,
                                position_ids):
    """The contiguous-row form of :func:`_quantized_paged_write`: the
    scale granule (``max_len // n_gran`` positions of one row) plays the
    block's role.  ``kv``: (L, 2, B, max_len, Hkv, D) int8; ``sc``:
    (L, 2, B, n_gran, Hkv) f32; ``position_ids``: (B, s) or (1, s) —
    positions at/past ``max_len`` fall out of bounds and every scatter
    drops them (the chunked engine's idle-row convention)."""
    f32 = jnp.float32
    b = kv.shape[2]
    s = position_ids.shape[-1]
    n_gran = sc.shape[3]
    gr = kv.shape[3] // n_gran
    pos = jnp.broadcast_to(position_ids, (b, s))
    gi = pos // gr                                             # (B, s)
    rr = jnp.arange(b)[:, None]
    needed = jnp.max(jnp.abs(x.astype(f32)), axis=-1) / 127.0
    old = sc[idx, kvsl][rr, gi]
    sc = sc.at[idx, kvsl, rr, gi].max(needed)
    new = sc[idx, kvsl][rr, gi]
    safe = jnp.where(new > 0, new, 1.0)
    ratio = jnp.where(new > 0, old / safe, 0.0)
    pos_g = gi[..., None] * gr + jnp.arange(gr)                # (B,s,gr)
    rr3 = rr[..., None]
    pay = kv[idx, kvsl][rr3, pos_g]                      # (B,s,gr,Hkv,D)
    pay = jnp.clip(jnp.round(pay.astype(f32)
                             * ratio[:, :, None, :, None]), -127, 127)
    kv = kv.at[idx, kvsl, rr3, pos_g].set(pay.astype(jnp.int8))
    tok = jnp.clip(jnp.round(x.astype(f32) / safe[..., None]), -127, 127)
    kv = kv.at[idx, kvsl, rr, pos].set(tok.astype(jnp.int8))
    return kv, sc


def paged_kv_write(cache, idx: int, k, v, position_ids, block_tables):
    """Write a chunk's K and V, (B, s, Hkv, D) at the logical
    ``position_ids`` (B, s), into layer ``idx`` of the paged pool through
    the rows' ``block_tables`` (B, max_blocks): (physical block, offset)
    scatters of whole ``Hkv·D`` rows; positions past a table's coverage —
    prompt padding — are steered to the null block (id 0).  ``cache`` is
    the pool array or the int8 pool's ``{"kv", "scale"}``; returns
    ``(cache, pool payload, scales or None)``.  Shared by every attention
    layer that keeps plain K and V rows in the pool."""
    quantized = isinstance(cache, dict)
    kvp = cache["kv"] if quantized else cache
    b, s = position_ids.shape
    phys, off = paged_write_site(position_ids, block_tables, kvp.shape[3])
    sc = None
    if quantized:
        kvp, sc = _quantized_paged_write(kvp, cache["scale"], idx, 0, k,
                                         phys, off)
        kvp, sc = _quantized_paged_write(kvp, sc, idx, 1, v, phys, off)
        sc = constrain(sc, None, None, None, "mp")
    else:
        with jax.named_scope("kv_write"):
            kvp = kvp.at[idx, 0, phys, off].set(
                k.astype(kvp.dtype).reshape(b, s, -1))
            kvp = kvp.at[idx, 1, phys, off].set(
                v.astype(kvp.dtype).reshape(b, s, -1))
    kvp = constrain(kvp, None, None, None, None, "mp")
    return ({"kv": kvp, "scale": sc} if quantized else kvp), kvp, sc


def paged_write_site(position_ids, block_tables, bl: int):
    """(physical block, offset in it), each (B, s), of the logical
    ``position_ids`` (B, s) through the rows' ``block_tables``
    (B, max_blocks) over blocks of ``bl``; positions past a table's
    coverage — prompt padding — go to the null block (id 0)."""
    max_blocks = block_tables.shape[1]
    rows = jnp.arange(position_ids.shape[0])[:, None]              # (B, 1)
    lb = position_ids // bl                                        # (B, s)
    phys = jnp.where(
        lb < max_blocks,
        block_tables[rows, jnp.minimum(lb, max_blocks - 1)],
        jnp.int32(0))                      # out-of-table pads -> null block
    return phys, position_ids % bl


def _layer_slots(leaf, idx: int, slots):
    """Layer ``idx`` of a contiguous-cache leaf (layer axis 0, slot axis 2)
    cut to a part's ``slots``: a one-layer cache of those rows."""
    first, n = slots
    z = jnp.int32(0)
    return jax.lax.dynamic_slice(
        leaf, (jnp.int32(idx), z, first) + (z,) * (leaf.ndim - 3),
        (1, leaf.shape[1], n) + leaf.shape[3:])


def _layer_slots_back(leaf, rows, idx: int, slots):
    z = jnp.int32(0)
    return jax.lax.dynamic_update_slice(
        leaf, rows, (jnp.int32(idx), z, slots[0]) + (z,) * (leaf.ndim - 3))


class LlamaAttention(Layer):
    """GQA attention with RoPE and flash attention.

    TP: head dims sharded on ``mp`` (column-parallel qkv, row-parallel o);
    FSDP: the other weight dim sharded on ``sharding``.
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.config = c
        hd, nh, nkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        init = I.Normal(std=c.initializer_range)
        self.q_proj = self.create_parameter(
            (c.hidden_size, nh * hd), dtype=c.dtype, initializer=init,
            sharding=P("sharding", "mp"), attr_name="q_proj")
        self.k_proj = self.create_parameter(
            (c.hidden_size, nkv * hd), dtype=c.dtype, initializer=init,
            sharding=P("sharding", "mp"), attr_name="k_proj")
        self.v_proj = self.create_parameter(
            (c.hidden_size, nkv * hd), dtype=c.dtype, initializer=init,
            sharding=P("sharding", "mp"), attr_name="v_proj")
        self.o_proj = self.create_parameter(
            (nh * hd, c.hidden_size), dtype=c.dtype, initializer=init,
            sharding=P("mp", "sharding"), attr_name="o_proj")

    def _proj(self, x):
        """q, k, v of every token of ``x``, split into heads: token-wise."""
        c = self.config
        b, s, _ = x.shape
        q = matmul(x, self.q_proj).reshape(b, s, c.num_attention_heads,
                                           c.head_dim)
        k = matmul(x, self.k_proj).reshape(b, s, c.num_key_value_heads,
                                           c.head_dim)
        v = matmul(x, self.v_proj).reshape(b, s, c.num_key_value_heads,
                                           c.head_dim)
        return q, k, v

    def _qkv(self, x, rope_cache, position_ids=None):
        q, k, v = self._proj(x)
        cos, sin = rope_cache
        q, k = fused_rope(q, k, cos, sin, position_ids)
        return q, k, v

    def forward(self, x, rope_cache, position_ids=None, segment_ids=None):
        c = self.config
        b, s, _ = x.shape
        q, k, v = self._qkv(x, rope_cache, position_ids)
        # heads on mp, batch on (dp, sharding), seq on sep
        if c.context_parallel in ("ring", "ulysses"):
            from ..distributed.context_parallel import \
                context_parallel_attention
            q = constrain(q, ("dp", "sharding"), "sep", "mp", None)
            k = constrain(k, ("dp", "sharding"), "sep", "mp", None)
            v = constrain(v, ("dp", "sharding"), "sep", "mp", None)
            if segment_ids is not None:
                segment_ids = constrain(segment_ids, ("dp", "sharding"),
                                        "sep")
            out = context_parallel_attention(q, k, v, causal=True,
                                             mode=c.context_parallel,
                                             segment_ids=segment_ids)
        else:
            q = constrain(q, ("dp", "sharding"), "sep", "mp", None)
            k = constrain(k, ("dp", "sharding"), None, "mp", None)
            v = constrain(v, ("dp", "sharding"), None, "mp", None)
            out = flash_attention(q, k, v, causal=True,
                                  segment_ids=segment_ids)
        return matmul(out.reshape(b, s, -1), self.o_proj)

    def decode(self, x, rope_cache, parts, cache, idx: int):
        """Incremental decode against the STACKED cache — contiguous
        (L, 2, B, max_len, Hkv, D), or the paged pool (last paragraph):
        write this chunk's K/V in place at ``(idx, ·, ·, pos)`` and attend
        over this layer's part of it.

        Dataflow is the design here (round-5 measurement): the carried
        cache is only ever touched by *chunk-sized*
        ``lax.dynamic_update_slice`` writes — XLA aliases them in place
        through the scan carry.  The previous structure (extract a layer's
        full (B, max_len, Hkv, D) slice, update, write the slice back)
        forced whole-cache copies every layer every step: measured 42.7 ms
        /step at b=8, max_len 8192 on the bench chip vs the ~4 ms
        weight-stream bound (BENCH_DECODE.json).

        Two attention regimes (round-3 verdict #9):

          * **prefill** (``pos`` is the static int 0 and s > 1, as
            generation.py passes it): attention over the cache at pos 0
            is exactly causal attention over the chunk's own fresh K/V —
            the uninitialised cache tail is unreachable — so it routes
            through the Pallas flash kernel when eligible;
          * **incremental** (traced ``pos``, q_len 1): HBM-bound; runs
            :func:`~paddle_tpu.ops.attention.cached_decode_attention` —
            grouped GQA, bf16 operands, fp32 accumulation, no K/V
            expansion.  That dispatcher in turn routes long caches
            (max_len >= FLAGS_decode_attention_min_len) on Pallas
            backends to the split-KV flash-decode kernel
            (ops/pallas/decode_attention.py): the position vector rides
            into the kernel as a scalar-prefetch operand and sizes each
            row's block walk, so each step streams only each row's
            LIVE cache prefix — per-step cost follows actual context
            depth, not max_len (the b=8 max_len-8192 regression in
            BENCH_DECODE.json).  Short caches keep the XLA math path,
            which already runs at the weight-stream bound.

        ``pos`` may also be an int (B,) vector of PER-ROW positions — the
        serving engine's slot batch, every row a different request at a
        different depth.  The write becomes a batched scatter (row i at
        column pos[i]) and the cache mask compares against the row's own
        position vector; the scalar paths are untouched.  The per-row
        vector is exactly the live-prefix hint the flash-decode kernel
        consumes — no extra plumbing between the engine and the kernel.

        ``block_tables`` (int (B, max_blocks)) switches to the PAGED
        cache (serving/kv_cache.py): ``cache`` is the pooled
        (L, 2, num_blocks, block_len, Hkv·D) array — stored as the
        flash-decode kernel reads it — and row i's logical position p
        lives at physical ``(block_tables[i, p // block_len],
        p % block_len)``.  Writes become (physical block, offset)
        scatters of whole ``Hkv·D`` rows; positions past the table's
        coverage — prompt padding in a prefill-into-slot wave — are
        steered to the null block (id 0, scratch by convention), so a
        padded wave can never clobber live or shared blocks.  The
        attention read hands THE POOL, this layer's index and the table
        to :func:`~paddle_tpu.ops.attention.paged_decode_attention`,
        whose Pallas kernel dereferences all three in the copies its body
        issues: no layer's K or V is sliced out of the pool or
        reshaped, so the step's cache traffic is what it writes and the
        live blocks it reads.  Paged decode always uses per-row positions
        (a scalar is broadcast).

        ``parts`` (:mod:`~paddle_tpu.models.parts`): ``x`` holds the
        tokens of every part.  The projections and the output projection
        run once over all of them; each part then takes its own tokens'
        q, k, v through RoPE at ITS positions, the write and the read
        described above through ITS table (or over its ``slots`` of the
        contiguous cache: one layer's rows are cut out, written, read and
        put back), in list order on the one cache, inside its ``scope``.

        x: (B, s, H*D), or all parts' tokens (T, 1, H*D).  Returns
        (out, cache).
        """
        sites = [part_site(p, rope_cache) for p in parts]

        def attend(i, p, cache, q, k, v):
            if p.block_tables is not None or p.slots is None:
                return self._attend(q, k, v, rope_cache, p, sites[i], cache,
                                    idx)
            # its slots of the contiguous cache: a one-layer cache of them
            rows = jax.tree_util.tree_map(
                lambda a: _layer_slots(a, idx, p.slots), cache)
            out, rows = self._attend(q, k, v, rope_cache, p, sites[i], rows,
                                     0)
            return out, jax.tree_util.tree_map(
                lambda a, r: _layer_slots_back(a, r, idx, p.slots), cache,
                rows)
        out, cache = part_by_part(parts, self._proj(x), cache, attend)
        return matmul(out.reshape(*out.shape[:2], -1), self.o_proj), cache

    def _attend(self, q, k, v, rope_cache, part, site, cache, idx: int):
        """One part's write and read: its (B, s, heads, D) projections
        against layer ``idx`` of ``cache``.  Returns ((B, s, H, D), cache)."""
        from ..ops.attention import (cached_decode_attention,
                                     paged_decode_attention)

        pos, position_ids, rope_ids = site
        b, s = q.shape[:2]
        quantized = isinstance(cache, dict)
        kvp = cache["kv"] if quantized else cache
        per_row = getattr(pos, "ndim", 0) == 1
        q, k = fused_rope(q, k, *rope_cache, rope_ids)
        if part.block_tables is not None:
            q = constrain(q, ("dp", "sharding"), None, "mp", None)
            cache, kvp, sc = paged_kv_write(cache, idx, k, v, position_ids,
                                            part.block_tables)
            return paged_decode_attention(q, kvp, idx, pos,
                                          part.block_tables,
                                          pool_scale=sc), cache
        if quantized:
            sc = cache["scale"]
            kvp, sc = _quantized_contiguous_write(kvp, sc, idx, 0, k,
                                                  position_ids)
            kvp, sc = _quantized_contiguous_write(kvp, sc, idx, 1, v,
                                                  position_ids)
            q = constrain(q, ("dp", "sharding"), None, "mp", None)
            kvp = constrain(kvp, None, None, ("dp", "sharding"), None,
                            "mp", None)
            sc = constrain(sc, None, None, ("dp", "sharding"), None, "mp")
            cache = {"kv": kvp, "scale": sc}
            if isinstance(pos, int) and pos == 0 and s > 1:
                # prefill keeps the exact fresh K/V for the flash read;
                # the quantization loss starts at the first cached read
                k = constrain(k, ("dp", "sharding"), None, "mp", None)
                v = constrain(v, ("dp", "sharding"), None, "mp", None)
                out = flash_attention(q, k, v, causal=True)
            else:
                out = cached_decode_attention(
                    q, kvp[idx, 0], kvp[idx, 1], pos,
                    k_scale=sc[idx, 0], v_scale=sc[idx, 1])
            return out, cache
        if per_row:
            rows = jnp.arange(b)[:, None]                          # (B, 1)
            cache = cache.at[idx, 0, rows, position_ids].set(
                k.astype(cache.dtype))
            cache = cache.at[idx, 1, rows, position_ids].set(
                v.astype(cache.dtype))
        else:
            cache = jax.lax.dynamic_update_slice(
                cache, k.astype(cache.dtype)[None, None],
                (idx, 0, 0, pos, 0, 0))
            cache = jax.lax.dynamic_update_slice(
                cache, v.astype(cache.dtype)[None, None],
                (idx, 1, 0, pos, 0, 0))
        q = constrain(q, ("dp", "sharding"), None, "mp", None)
        cache = constrain(cache, None, None, ("dp", "sharding"), None,
                          "mp", None)
        if isinstance(pos, int) and pos == 0 and s > 1:
            k = constrain(k, ("dp", "sharding"), None, "mp", None)
            v = constrain(v, ("dp", "sharding"), None, "mp", None)
            out = flash_attention(q, k, v, causal=True)
        else:
            out = cached_decode_attention(q, cache[idx, 0], cache[idx, 1],
                                          pos)
        return out, cache


class LlamaMLP(Layer):
    """SwiGLU MLP — gate/up column-parallel, down row-parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        init = I.Normal(std=c.initializer_range)
        self.gate_proj = self.create_parameter(
            (c.hidden_size, c.intermediate_size), dtype=c.dtype,
            initializer=init, sharding=P("sharding", "mp"),
            attr_name="gate_proj")
        self.up_proj = self.create_parameter(
            (c.hidden_size, c.intermediate_size), dtype=c.dtype,
            initializer=init, sharding=P("sharding", "mp"),
            attr_name="up_proj")
        self.down_proj = self.create_parameter(
            (c.intermediate_size, c.hidden_size), dtype=c.dtype,
            initializer=init, sharding=P("mp", "sharding"),
            attr_name="down_proj")

    def forward(self, x):
        return matmul(F.swiglu(matmul(x, self.gate_proj),
                               matmul(x, self.up_proj)),
                      self.down_proj)


def swiglu_mlp(config, width: int) -> LlamaMLP:
    """SwiGLU MLP of a given width — an expert model's dense layers' and
    its shared expert's: :class:`LlamaMLP`, which reads ``hidden_size``,
    ``intermediate_size``, ``initializer_range`` and ``dtype`` of whatever
    config it is given."""
    return LlamaMLP(dataclasses.replace(config, intermediate_size=width))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps,
                                       dtype=config.dtype)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps,
                                                dtype=config.dtype)
        self.mlp = LlamaMLP(config)

    def forward(self, x, rope_cache, position_ids=None, segment_ids=None):
        x = x + self.self_attn(self.input_layernorm(x), rope_cache,
                               position_ids, segment_ids)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return constrain(x, *_batch_spec(x.ndim))

    def decode(self, x, rope_cache, parts, cache, idx: int):
        # named for the device trace (the scopes reach every op's
        # ``op_name``; the engine's program_part names the kernel)
        with jax.named_scope("attn"):
            a, cache = self.self_attn.decode(
                self.input_layernorm(x), rope_cache, parts, cache, idx)
            x = x + a
        with jax.named_scope("ffn"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x, cache


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        from ..nn.layer import LayerList
        self.layers = LayerList(
            [LlamaDecoderLayer(c) for _ in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)
        cos, sin = build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    base=c.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)

    def forward(self, input_ids, position_ids=None, segment_ids=None):
        """``segment_ids``: optional (B, S) packed-document ids — enables
        varlen pretraining batches (several documents packed per row with
        no cross-attention); masking happens inside the flash kernel.
        Pass matching ``position_ids`` (restarting per document) for the
        standard packing recipe."""
        c = self.config
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        x = constrain(x, *_batch_spec(x.ndim))
        rope = (self.rope_cos, self.rope_sin)
        for block in self.layers:
            if c.recompute and self.training:
                x = jax.checkpoint(
                    lambda h, blk=block: blk(h, rope, position_ids,
                                             segment_ids),
                    policy=c.remat_policy)(x)
            else:
                x = block(x, rope, position_ids, segment_ids)
        return self.norm(x)


def mask_boundary_labels(labels, segment_ids):
    """Drop labels at packed-document boundaries: the position whose next
    token opens ANOTHER document is a packing artifact, not a prediction
    target (-1 = ignored by :func:`causal_lm_loss`)."""
    boundary = segment_ids[:, :-1] != segment_ids[:, 1:]
    return jnp.where(jnp.pad(boundary, ((0, 0), (0, 1))), -1, labels)


def causal_lm_loss(logits, labels):
    """Mean next-token cross entropy in fp32 over (possibly vocab-sharded)
    logits — the ParallelCrossEntropy dataflow: no logits all-gather."""
    logits = constrain(logits, ("dp", "sharding"), "sep", "mp")
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    gold = jnp.take_along_axis(
        shifted, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    loss = lse - gold
    valid = (labels >= 0).astype(jnp.float32)
    return jnp.sum(loss * valid) / jnp.maximum(jnp.sum(valid), 1.0)


class LlamaForCausalLM(CausalLMDecode, Layer):
    """Causal LM head + loss (the train-step entry the benchmarks drive);
    ``decode_parts`` / ``decode_step`` / ``generate`` are
    :class:`~paddle_tpu.models.parts.CausalLMDecode`'s, over the stacked
    (L, 2, B, max_len, Hkv, D) cache of
    :func:`paddle_tpu.models.generation.init_kv_cache` or, for parts with
    ``block_tables``, the pooled paged cache of
    :func:`paddle_tpu.serving.kv_cache.init_paged_kv_cache`."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = self.create_parameter(
                (config.hidden_size, config.vocab_size), dtype=config.dtype,
                initializer=I.Normal(std=config.initializer_range),
                sharding=P("sharding", "mp"), attr_name="lm_head")

    def logits(self, hidden):
        if self.config.tie_word_embeddings:
            w = self.model.embed_tokens
            return matmul(hidden, w.T)
        return matmul(hidden, self.lm_head)

    def forward(self, input_ids, position_ids=None, segment_ids=None):
        return self.logits(self.model(input_ids, position_ids, segment_ids))

    def compute_loss(self, input_ids, labels, position_ids=None,
                     segment_ids=None):
        if segment_ids is not None:
            # attention masking can't fix boundary labels — that is a label
            # problem, not a leakage problem; see mask_boundary_labels
            labels = mask_boundary_labels(labels, segment_ids)
        return causal_lm_loss(
            self.forward(input_ids, position_ids, segment_ids), labels)


def draft_model_from(model, params=None, num_layers: int = 1):
    """A truncated-target draft model for speculative decoding: the same
    architecture at ``num_layers`` decoder blocks, REUSING the target's
    embedding, first ``num_layers`` blocks, final norm and LM head
    (jax arrays are immutable, so "reuse" is zero-copy aliasing — the
    only new memory is the draft's own KV cache, owned by the engine's
    :class:`~paddle_tpu.serving.drafter.DraftModelDrafter`).

    Truncation is the cheapest well-aligned drafter: it shares the
    target's vocabulary and embedding geometry exactly, so its proposal
    distribution q lives on the same support as the target's p — the
    shape the rejection-sampling acceptance needs.  Returns
    ``(draft_model, draft_params)``; ``params`` defaults to the
    target's own ``state_dict(include_buffers=True)`` (pass the
    engine's mesh-placed params to alias placed shards instead).
    """
    import dataclasses
    n = int(num_layers)
    if not 1 <= n <= model.config.num_hidden_layers:
        raise ValueError(
            f"num_layers must be in [1, {model.config.num_hidden_layers}]"
            f", got {n}")
    cfg = dataclasses.replace(model.config, num_hidden_layers=n)
    draft = LlamaForCausalLM(cfg)
    src = (params if params is not None
           else model.state_dict(include_buffers=True))
    merged = type(src)(
        (k, src[k] if k in src else v)
        for k, v in draft.state_dict(include_buffers=True).items())
    return draft, merged


# ---------------------------------------------------------------------------
# pipeline-parallel form: the same model as a flat list of LayerDescs
# (parity: PaddleNLP's LlamaForCausalLMPipe built on fleet's PipelineLayer)
# ---------------------------------------------------------------------------

class LlamaEmbeddingPipe(Layer):
    """Stage-0 piece: token embedding (vocab-parallel)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.embed_tokens = self.create_parameter(
            (config.vocab_size, config.hidden_size), dtype=config.dtype,
            initializer=I.Normal(std=config.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")

    def forward(self, input_ids):
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        return constrain(x, *_batch_spec(x.ndim))


class LlamaDecoderLayerPipe(LlamaDecoderLayer):
    """Decoder block carrying its own (deterministic) RoPE buffers, so any
    stage can host it without cross-stage buffer plumbing."""

    def __init__(self, config: LlamaConfig):
        super().__init__(config)
        cos, sin = build_rope_cache(config.max_position_embeddings,
                                    config.head_dim, base=config.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)
        self._recompute = config.recompute
        self.config = config

    def forward(self, x):
        rope = (self.rope_cos, self.rope_sin)
        if self._recompute and self.training:
            return jax.checkpoint(
                lambda h: super(LlamaDecoderLayerPipe, self).forward(
                    h, rope),
                policy=self.config.remat_policy)(x)
        return super().forward(x, rope)


class LlamaHeadPipe(Layer):
    """Last-stage piece: final norm + LM head → logits."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps,
                            dtype=config.dtype)
        self.lm_head = self.create_parameter(
            (config.hidden_size, config.vocab_size), dtype=config.dtype,
            initializer=I.Normal(std=config.initializer_range),
            sharding=P("sharding", "mp"), attr_name="lm_head")

    def forward(self, x):
        return matmul(self.norm(x), self.lm_head)


def llama_pipe_descs(config: LlamaConfig):
    """(layer_descs, loss_fn) for PipelineLayer — same parameter-creation
    order as LlamaForCausalLM, so identical seeds give identical weights."""
    from ..distributed.pipeline import LayerDesc

    descs = [LayerDesc(LlamaEmbeddingPipe, config)]
    descs += [LayerDesc(LlamaDecoderLayerPipe, config)
              for _ in range(config.num_hidden_layers)]
    descs.append(LayerDesc(LlamaHeadPipe, config))
    return descs, causal_lm_loss
