"""Latent-attention MoE decoder (the ``deepseek_v3`` key set: multi-head
latent attention over a sigmoid-routed dropless MoE beside a shared
expert).

With ``x`` the residual stream and ``N(·)`` an RMS norm with its own weight
(ε ``rms_norm_eps``), pre-norm, two norms a layer::

    h'     = x + Attn(N_1(x))
    y      = h' + FFN(N_2(h'))
    logits = N_f(x_L) · W_head                      (untied)

**Attention** (``h = N_1(x)``; H heads; ``r`` = ``qk_rope_head_dim``, ``n``
= ``qk_nope_head_dim``, ``v`` = ``v_head_dim``, ``c`` = ``kv_lora_rank``):

    c_q             = N_q(h W_dq)                   (q_lora_rank)
    [q_nope ‖ q_r]  = c_q W_uq                      (H × (n + r))
    [c_kv ‖ k_r]    = h W_dkv                       (c + r)
    c               = N_kv(c_kv)
    q_rope, k_rope  = RoPE(q_r), RoPE(k_r)          ONE k_rope for all heads

RoPE (θ ``rope_theta``, no scaling) turns lanes ``(2i, 2i+1)`` as a pair
(``rope_interleave``) and leaves them in place.  The **cached entry** of a
position is ``[c ‖ k_rope]``: after the norm, after the rotation, key and
value of every head at once.

*Plain form* (``forward``)::

    [k_nope_h ‖ v_h] = c W_ukv                      (n + v a head)
    s_h(i, j) = (q_nope_h(i)·k_nope_h(j) + q_rope_h(i)·k_rope(j)) / √(n + r)
    o_h = Σ_j softmax_j≤i(s_h)(i, j) v_h(j);   out = concat_h(o_h) W_o

*Absorbed form* (``decode``, over the latent pool): with ``W_uk,h`` and
``W_uv,h`` head ``h``'s columns of ``W_ukv``::

    q̃_h = q_nope_h W_uk,hᵀ                          (c)
    s_h(i, j) = (q̃_h(i)·c(j) + q_rope_h(i)·k_rope(j)) / √(n + r)
    õ_h = Σ_j p_h(i, j) c(j)                        (c)
    o_h = õ_h W_uv,h

the same mathematics with the up-projection moved from every cached
position to the query and the result: the pool holds ``c + r`` values a
position a layer instead of ``H (n + r + v)``.  bf16 operands, float32
accumulation and softmax.  The pool (``serving_traits.pool_entry``) is
``(L, 1, blocks, block_len, W)`` with ``W`` the entry padded with zero
lanes to a multiple of 128 (the TPU's tiled HBM layout pads a minor axis so
anyway); the read is
:func:`~paddle_tpu.ops.attention.latent_decode_attention`, the flash-decode
walk with that layout as a static parameter.

**FFN.**  Layers below ``first_k_dense_replace``: a SwiGLU of
``intermediate_size``.  The others: ``s = sigmoid(N_2(h') W_r)`` in float32
over ALL ``n_routed_experts``; the ``num_experts_per_tok`` largest of
``s + b`` (``noaux_tc``'s selection bias; ``n_group = topk_group = 1``: no
group limit); weights ``s`` (without ``b``) there, divided by their sum
(``norm_topk_prob``), times ``routed_scaling_factor``
(:class:`~paddle_tpu.distributed.moe.SigmoidTopKGate`); each routed expert
and the ``n_shared_experts`` shared one a SwiGLU of
``moe_intermediate_size``; :class:`~paddle_tpu.distributed.moe
.HeldExpertsMoE` computes the experts this expert-parallel rank holds
(``ep_rank`` of ``ep_size``); what experts held elsewhere would add is left
out.

The multi-token-prediction module (``num_nextn_predict_layers``) is a draft
layer past the last one: it adds nothing to the model's logits and is not
built.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.fleet.mp_layers import vocab_parallel_lookup
from ..distributed.moe import (HeldExpertsMoE, SigmoidTopKGate,
                               held_experts_kernel_specs)
from ..nn import initializer as I
from ..nn.common import RMSNorm
from ..nn.layer import Layer, LayerList
from ..ops import build_rope_cache
from ..ops.pallas.decode_attention import LatentLayout
from ..tensor.math import matmul
from .llama import paged_write_site, swiglu_mlp
from .parts import (CausalLMDecode, PoolEntry, ServingTraits, join_valid,
                    part_by_part, part_site)

__all__ = ["LatentMoeConfig", "LatentMoeForCausalLM",
           "tiny_latent_moe_config", "rope_pairs"]

_LANES = 128


@dataclasses.dataclass
class LatentMoeConfig:
    """The published ``deepseek_v3``-shaped keys (defaults:
    JoyAI-LLM-Flash), plus the expert-parallel share this instance holds.
    ``head_dim`` is published as the RoPE width and ``num_key_value_heads``
    as the head count; neither sizes anything here."""
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168           # the leading dense layers' MLP
    moe_intermediate_size: int = 768        # one expert, routed or shared
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hidden_act: str = "silu"
    attention_bias: bool = False
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 1       # the draft module: not built
    initializer_range: float = 0.02
    dtype: str = "float32"
    # this instance's share of every expert layer: rank ``ep_rank`` of
    # ``ep_size`` holds experts [rank, rank + 1) · n_routed_experts / ep_size
    ep_size: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        def only(what):
            raise NotImplementedError(f"LatentMoeConfig: only {what}")
        if (self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc"
                or self.n_group != 1 or self.topk_group != 1):
            only("sigmoid routing with a selection bias and no group limit "
                 f"(scoring_func={self.scoring_func!r}, topk_method="
                 f"{self.topk_method!r}, n_group={self.n_group}, "
                 f"topk_group={self.topk_group})")
        if self.rope_scaling is not None or not self.rope_interleave:
            only("unscaled RoPE over interleaved pairs (rope_scaling="
                 f"{self.rope_scaling!r}, rope_interleave="
                 f"{self.rope_interleave})")
        if (self.moe_layer_freq != 1 or self.hidden_act != "silu"
                or self.attention_bias or self.tie_word_embeddings
                or not self.q_lora_rank):
            only("every layer past the dense ones routing, silu, no "
                 "attention bias, an untied head and a low-rank query")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} "
                             f"is odd: RoPE turns pairs")
        if (self.n_routed_experts % self.ep_size
                or not 0 <= self.ep_rank < self.ep_size):
            raise ValueError(
                f"{self.n_routed_experts} experts do not split over ep_size "
                f"{self.ep_size} (ep_rank {self.ep_rank})")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def experts_held(self) -> Tuple[int, int]:
        """[lo, hi): the routed experts whose weights this rank holds."""
        n = self.n_routed_experts // self.ep_size
        return self.ep_rank * n, (self.ep_rank + 1) * n

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def entry_values(self) -> int:
        """Values a cached position holds a layer: latent + RoPE key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def entry_width(self) -> int:
        """The entry as stored: padded with zero lanes to a multiple of 128
        (the tiled HBM layout pads a minor axis to that anyway, and the
        walk's copies and products then lie on whole lane tiles)."""
        return -(-self.entry_values // _LANES) * _LANES

    @property
    def latent_layout(self) -> LatentLayout:
        """The static layout the flash-decode walk reads the pool by: the
        value is the entry's first ``kv_lora_rank`` lanes."""
        return LatentLayout(value_width=self.kv_lora_rank)


def tiny_latent_moe_config(**overrides) -> LatentMoeConfig:
    """Small config for tests: one dense layer and two expert layers, four
    heads, a latent of 128 and a RoPE key of 32 (an entry of 160 values
    stored in 256 lanes), eight experts, top 2."""
    cfg = LatentMoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, head_dim=32,
        q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=16,
        qk_rope_head_dim=32, v_head_dim=16, n_routed_experts=8,
        num_experts_per_tok=2, max_position_embeddings=512,
        rope_theta=10000.0)
    return dataclasses.replace(cfg, **overrides)


def rope_pairs(x, cos, sin, position_ids=None):
    """RoPE over interleaved pairs: lanes ``(2i, 2i+1)`` of the last axis
    of ``x`` (B, S, ..., r) turn by the angle of position and frequency
    ``i`` (``cos``/``sin``: (positions, r/2) caches) and stay in place.
    ``position_ids`` (B, S): each token's position; None: ``0..S-1``."""
    if position_ids is None:
        cos, sin = cos[None, :x.shape[1]], sin[None, :x.shape[1]]
    else:
        cos = jnp.take(cos, position_ids, axis=0)
        sin = jnp.take(sin, position_ids, axis=0)
    mid = (1,) * (x.ndim - 3)
    cos = cos.reshape(*cos.shape[:2], *mid, -1)
    sin = sin.reshape(*sin.shape[:2], *mid, -1)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def latent_pool_write(cache, idx: int, entry, position_ids, block_tables):
    """Write a part's entries (B, s, W) at the logical ``position_ids``
    (B, s) into layer ``idx`` of the latent pool ``(L, 1, blocks,
    block_len, W)`` through the rows' ``block_tables``: what
    :func:`~paddle_tpu.models.llama.paged_kv_write` does for K and V rows;
    positions past a table's coverage go to the null block."""
    phys, off = paged_write_site(position_ids, block_tables, cache.shape[3])
    with jax.named_scope("kv_write"):
        return cache.at[idx, 0, phys, off].set(entry.astype(cache.dtype))


class LatentAttention(Layer):
    """Multi-head latent attention in both forms (module docstring)."""

    def __init__(self, config: LatentMoeConfig):
        super().__init__()
        c = config
        self.config = c
        nh = c.num_attention_heads
        init = I.Normal(std=c.initializer_range)

        def proj(name, shape, spec):
            return self.create_parameter(shape, dtype=c.dtype,
                                         initializer=init, sharding=spec,
                                         attr_name=name)
        col, row = P("sharding", "mp"), P("mp", "sharding")
        self.q_a_proj = proj("q_a_proj", (c.hidden_size, c.q_lora_rank), col)
        self.q_a_layernorm = RMSNorm(c.q_lora_rank, epsilon=c.rms_norm_eps,
                                     dtype=c.dtype)
        self.q_b_proj = proj("q_b_proj",
                             (c.q_lora_rank, nh * c.qk_head_dim), col)
        self.kv_a_proj_with_mqa = proj(
            "kv_a_proj_with_mqa", (c.hidden_size, c.entry_values), col)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, epsilon=c.rms_norm_eps,
                                      dtype=c.dtype)
        self.kv_b_proj = proj(
            "kv_b_proj",
            (c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)), col)
        self.o_proj = proj("o_proj", (nh * c.v_head_dim, c.hidden_size), row)
        self.scale = 1.0 / math.sqrt(c.qk_head_dim)

    # -- token-wise ---------------------------------------------------------

    def _down(self, x):
        """(q_nope (B, S, H, n), q_r (B, S, H, r), c (B, S, c) normed, k_r
        (B, S, r) not yet rotated) of every token."""
        c = self.config
        b, s, _ = x.shape
        q = matmul(self.q_a_layernorm(matmul(x, self.q_a_proj)),
                   self.q_b_proj).reshape(b, s, c.num_attention_heads,
                                          c.qk_head_dim)
        kv = matmul(x, self.kv_a_proj_with_mqa)
        return (q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:],
                self.kv_a_layernorm(kv[..., :c.kv_lora_rank]),
                kv[..., c.kv_lora_rank:])

    def _up(self):
        """``W_ukv`` by head: (W_uk (c, H, n), W_uv (c, H, v))."""
        c = self.config
        w = self.kv_b_proj.reshape(c.kv_lora_rank, c.num_attention_heads,
                                   c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _out(self, o):
        return matmul(o.reshape(*o.shape[:2], -1), self.o_proj)

    # -- the plain form -----------------------------------------------------

    def forward(self, x, rope_cache, position_ids=None):
        with jax.named_scope("attn.latent_plain"):
            q_nope, q_r, lat, k_r = self._down(x)
            q_rope = rope_pairs(q_r, *rope_cache, position_ids)
            k_rope = rope_pairs(k_r, *rope_cache, position_ids)
            w_uk, w_uv = self._up()
            f32 = jnp.float32
            k_nope = jnp.einsum("bjc,chn->bjhn", lat, w_uk)
            v = jnp.einsum("bjc,chv->bjhv", lat, w_uv)
            scores = (jnp.einsum("bihn,bjhn->bhij", q_nope, k_nope,
                                 preferred_element_type=f32)
                      + jnp.einsum("bihr,bjr->bhij", q_rope, k_rope,
                                   preferred_element_type=f32)) * self.scale
            s = x.shape[1]
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            p = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
            o = jnp.einsum("bhij,bjhv->bihv", p.astype(v.dtype), v,
                           preferred_element_type=f32)
            return self._out(o.astype(x.dtype))

    # -- the absorbed form over the latent pool -----------------------------

    def decode(self, x, rope_cache, parts, cache, idx: int):
        """Decode over the latent paged pool: the projections, the key
        up-projection carried to the query, the value up-projection and
        the output projection once over the tokens of all ``parts``; each
        part's RoPE, entry write and absorbed read at its own positions
        through its block table.  Returns (out, cache)."""
        c = self.config
        with jax.named_scope("attn.latent"):
            for p in parts:
                if p.block_tables is None:
                    raise NotImplementedError(
                        "LatentAttention.decode: the absorbed read runs "
                        "over the paged latent pool (block_tables) only")
            q_nope, q_r, lat, k_r = self._down(x)
            w_uk, w_uv = self._up()
            q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)
            sites = [part_site(p, rope_cache) for p in parts]
            o_lat, cache = part_by_part(
                parts, (q_lat, q_r, lat, k_r), cache,
                lambda i, p, cache, *cut: self._attend(
                    *cut, rope_cache, p, sites[i], cache, idx))
            o = jnp.einsum("bshc,chv->bshv", o_lat, w_uv)
            return self._out(o), cache

    def _attend(self, q_lat, q_r, lat, k_r, rope_cache, part, site, cache,
                idx: int):
        """One part's RoPE, write and read against layer ``idx``: the
        part's entries land before the read (a chunk sees itself)."""
        from ..ops.attention import latent_decode_attention
        c = self.config
        pos, position_ids, rope_ids = site
        q_rope = rope_pairs(q_r, *rope_cache, rope_ids)
        k_rope = rope_pairs(k_r, *rope_cache, rope_ids)
        pad = c.entry_width - c.entry_values

        def stored(a, b):
            """[a ‖ b ‖ zero lanes] as the pool stores an entry."""
            out = jnp.concatenate([a, b], axis=-1)
            return jnp.pad(out, ((0, 0),) * (out.ndim - 1) + ((0, pad),))
        cache = latent_pool_write(cache, idx, stored(lat, k_rope),
                                  position_ids, part.block_tables)
        return latent_decode_attention(
            stored(q_lat, q_rope), cache, idx, pos, part.block_tables,
            c.latent_layout, self.scale, shared=part.shared), cache


class LatentMoE(Layer):
    """Router, this rank's share of the routed experts, and the shared
    expert (whole)."""

    def __init__(self, config: LatentMoeConfig):
        super().__init__()
        c = config
        self.gate = SigmoidTopKGate(
            c.hidden_size, c.n_routed_experts, c.num_experts_per_tok,
            route_scale=c.routed_scaling_factor, route_norm=c.norm_topk_prob,
            dtype=c.dtype)
        self.experts = HeldExpertsMoE(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, held=c.experts_held, dtype=c.dtype)
        self.shared_experts = swiglu_mlp(
            c, c.moe_intermediate_size * c.n_shared_experts)

    def forward(self, x, valid=None):
        with jax.named_scope("ffn.route"):
            idx, w = self.gate.route(x.reshape(-1, x.shape[-1]))
        routed = self.experts(x, idx, w, valid=valid)
        with jax.named_scope("ffn.shared"):
            return self.shared_experts(x) + routed


class LatentDecoderLayer(Layer):
    def __init__(self, config: LatentMoeConfig, index: int):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                                       dtype=c.dtype)
        self.self_attn = LatentAttention(c)
        self.post_attention_layernorm = RMSNorm(
            c.hidden_size, epsilon=c.rms_norm_eps, dtype=c.dtype)
        self.dense = index < c.first_k_dense_replace
        self.mlp = (swiglu_mlp(c, c.intermediate_size) if self.dense
                    else LatentMoE(c))

    def _ffn(self, h, valid=None):
        y = self.post_attention_layernorm(h)
        if self.dense:
            with jax.named_scope("ffn.dense"):
                return h + self.mlp(y)
        return h + self.mlp(y, valid=valid)

    def forward(self, x, rope_cache, position_ids=None):
        return self._ffn(x + self.self_attn(self.input_layernorm(x),
                                            rope_cache, position_ids))

    def decode(self, x, rope_cache, parts, cache, idx: int):
        with jax.named_scope("attn"):
            a, cache = self.self_attn.decode(
                self.input_layernorm(x), rope_cache, parts, cache, idx)
            h = x + a
        with jax.named_scope("ffn"):
            return self._ffn(h, join_valid(parts)), cache


class LatentMoeModel(Layer):
    def __init__(self, config: LatentMoeConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList(
            [LatentDecoderLayer(c, i) for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)
        cos, sin = build_rope_cache(c.max_position_embeddings,
                                    c.qk_rope_head_dim, base=c.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)

    def forward(self, input_ids, position_ids=None):
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        rope = (self.rope_cos, self.rope_sin)
        for block in self.layers:
            x = block(x, rope, position_ids)
        return self.norm(x)


# the engine layouts this model cannot run, and why
# (``models.parts.ServingTraits.unsupported``).  A prefix cache is fine: the
# trie addresses blocks, not layouts
_UNSUPPORTED = {
    "contiguous_cache": "the absorbed read walks the latent paged pool only",
    "wave_prefill": "the wave's prefill program writes K and V rows; a "
                    "prompt here goes through the chunk part's absorbed "
                    "read",
    "kv_cache_dtype": "the latent pool has no int8 form (one scale a block "
                      "would cover the latent and the RoPE key alike) and "
                      "no demotion",
    "preemption": "no test shows the block movers exact on a latent pool",
    "mesh": "the latent walk has no sharded form, the held-experts layer no "
            "exchange",
    "spec_decode": "the model drafter keeps a contiguous K/V cache and "
                   "draft_model_from truncates a llama; the draft module "
                   "(num_nextn_predict_layers) is not built",
    "int8_weights": "quantize_for_decode knows no stacked expert weights "
                    "and no low-rank projections",
}


class LatentMoeForCausalLM(CausalLMDecode, Layer):
    """Causal LM over :class:`LatentMoeModel`, served
    (:class:`~paddle_tpu.models.parts.CausalLMDecode`) over the latent
    paged pool."""

    def __init__(self, config: LatentMoeConfig):
        super().__init__()
        self.config = config
        self.model = LatentMoeModel(config)
        self.lm_head = self.create_parameter(
            (config.hidden_size, config.vocab_size), dtype=config.dtype,
            initializer=I.Normal(std=config.initializer_range),
            sharding=P("sharding", "mp"), attr_name="lm_head")

    def logits(self, hidden):
        return matmul(hidden, self.lm_head)

    def forward(self, input_ids, position_ids=None):
        """Logits (B, T, V) of whole sequences: the plain form."""
        return self.logits(self.model(input_ids, position_ids))

    @property
    def serving_traits(self) -> ServingTraits:
        c = self.config
        return ServingTraits(
            # ONE array a layer of one entry a position: the normed latent
            # and the rotated RoPE key, key and value of every head at
            # once, padded to whole lane tiles
            pool_entry=PoolEntry(arrays=1, width=c.entry_width,
                                 group=c.num_attention_heads,
                                 layout=c.latent_layout),
            expert_layers=c.num_expert_layers,
            kernel_specs=functools.partial(held_experts_kernel_specs, c),
            unsupported=_UNSUPPORTED)
