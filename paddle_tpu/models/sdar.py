"""SDAR-MoE decoder (``model_type: sdar_moe``, the SDAR family): a
block-diffusion language model over a softmax-routed dropless MoE.

With ``x`` the residual stream and ``N(·)`` an RMS norm with its own weight
(ε ``rms_norm_eps``)::

    h      = x + Attn(N_in(x)) W_o
    x'     = h + Σ_k w_k · Expert_{e_k}(N_post(h))
    logits = N_f(x_L) · W_head                      (untied)

``Attn``: q as ``num_attention_heads`` heads of ``head_dim``, k and v as
``num_key_value_heads``, no bias; q and k take a per-head RMS norm (one
weight vector of ``head_dim`` a kind) BEFORE RoPE (rotate-half, θ
``rope_theta``); softmax attention at ``head_dim^-½`` under the
**block-causal** mask: with ``B = block_length``, key ``j`` is visible to
the query at position ``i`` iff ``j // B <= i // B`` — a position sees
every earlier block and its own block whole, in the prompt and in
generation alike.  The logits at position ``i`` predict the token AT ``i``
(no shift).  Every layer routes: :class:`~paddle_tpu.distributed.moe
.SoftmaxTopKGate` chooses ``num_experts_per_tok`` of ``num_experts`` by
softmax probability (renormalised over the chosen, ``norm_topk_prob``) and
:class:`~paddle_tpu.distributed.moe.HeldExpertsMoE` computes the experts
this expert-parallel rank holds (``ep_rank`` of ``ep_size``); what experts
held elsewhere would add is left out.  No shared expert, no dense layer
(``decoder_sparse_step`` 1, ``mlp_only_layers`` empty: the published
``intermediate_size`` is used by no layer).

**Generation** is autoregressive over blocks and masked diffusion inside
one (``models.generation.BlockDiffusion``, ``unmask_block``): a new block
is ``B`` copies of ``mask_token_id``; each denoising forward runs the
block's ``B`` positions against the committed K/V of everything before it
and the block's own K/V and unmasks some of the still-masked positions by
confidence; a block that goes in mask-free is committed — the K/V that
forward writes are the ones later blocks read.  The serving engine drives
it (``ServingEngine``, "Block diffusion"): this model's ``serving_traits``
declare ``block_diffusion`` and the engine composes a block rows part for
it.

The cache row is plain K and V of ``num_key_value_heads · head_dim``, so
the model decodes over the paged pool llama uses
(:func:`~paddle_tpu.models.parts.kv_attention`, which takes the mask's
block length as ``block=``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.fleet.mp_layers import vocab_parallel_lookup
from ..distributed.moe import (HeldExpertsMoE, SoftmaxTopKGate,
                               held_experts_kernel_specs)
from ..nn import initializer as I
from ..nn.common import RMSNorm
from ..nn.layer import Layer, LayerList
from ..ops import build_rope_cache, flash_attention, fused_rope
from ..tensor.math import matmul
from .generation import BlockDiffusion
from .parts import CausalLMDecode, ServingTraits, join_valid, kv_attention

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "tiny_sdar_config",
           "block_causal_mask"]


@dataclasses.dataclass
class SdarMoeConfig:
    """The published ``sdar_moe`` keys (defaults: SDAR-30B-A3B-Chat), the
    generation settings the config does not state (``block_length`` …
    ``confidence_threshold``: the family's released defaults), and the
    expert-parallel share this instance holds."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144           # published; no layer uses it
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"
    # generation by diffusion over blocks
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    # this instance's share of every expert layer: rank ``ep_rank`` of
    # ``ep_size`` holds experts [rank, rank + 1) · num_experts / ep_size
    ep_size: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise NotImplementedError(
                "SdarMoeConfig: every layer routes (decoder_sparse_step 1, "
                f"no mlp_only_layers); got {self.decoder_sparse_step}, "
                f"{self.mlp_only_layers}")
        if self.tie_word_embeddings:
            raise NotImplementedError("SdarMoeConfig: the head is untied")
        if (self.num_experts % self.ep_size
                or not 0 <= self.ep_rank < self.ep_size):
            raise ValueError(
                f"{self.num_experts} experts do not split over ep_size "
                f"{self.ep_size} (ep_rank {self.ep_rank})")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is no token of a "
                f"vocabulary of {self.vocab_size}")
        self.block_diffusion        # validates the generation settings

    @property
    def experts_held(self) -> Tuple[int, int]:
        """[lo, hi): the routed experts whose weights this rank holds."""
        n = self.num_experts // self.ep_size
        return self.ep_rank * n, (self.ep_rank + 1) * n

    @property
    def block_diffusion(self) -> BlockDiffusion:
        return BlockDiffusion(
            int(self.block_length), int(self.mask_token_id),
            int(self.denoising_steps), str(self.remasking_strategy),
            float(self.confidence_threshold))


def tiny_sdar_config(**overrides) -> SdarMoeConfig:
    """Small config for tests: three layers of eight experts, top 2, a
    GQA group of 2, blocks of four."""
    cfg = SdarMoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=128,
        mask_token_id=255)
    return dataclasses.replace(cfg, **overrides)


def block_causal_mask(s: int, block: int):
    """(1, 1, s, s) bool: key j visible to query i iff j's block is no
    later than i's."""
    b = jnp.arange(s) // block
    return (b[None, :] <= b[:, None])[None, None]


class SdarAttention(Layer):
    """GQA attention with per-head q/k norms before RoPE, under the
    block-causal mask."""

    def __init__(self, config: SdarMoeConfig):
        super().__init__()
        c = config
        self.config = c
        hd, nh, nkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        init = I.Normal(std=c.initializer_range)

        def proj(name, shape, spec):
            return self.create_parameter(shape, dtype=c.dtype,
                                         initializer=init, sharding=spec,
                                         attr_name=name)
        col, row = P("sharding", "mp"), P("mp", "sharding")
        self.q_proj = proj("q_proj", (c.hidden_size, nh * hd), col)
        self.k_proj = proj("k_proj", (c.hidden_size, nkv * hd), col)
        self.v_proj = proj("v_proj", (c.hidden_size, nkv * hd), col)
        self.o_proj = proj("o_proj", (nh * hd, c.hidden_size), row)
        self.q_norm = RMSNorm(hd, epsilon=c.rms_norm_eps, dtype=c.dtype)
        self.k_norm = RMSNorm(hd, epsilon=c.rms_norm_eps, dtype=c.dtype)

    def _proj(self, x):
        """q, k (normed) and v of every token, split into heads:
        token-wise."""
        c = self.config
        b, s, _ = x.shape
        q = matmul(x, self.q_proj).reshape(b, s, c.num_attention_heads,
                                           c.head_dim)
        k = matmul(x, self.k_proj).reshape(b, s, c.num_key_value_heads,
                                           c.head_dim)
        v = matmul(x, self.v_proj).reshape(b, s, c.num_key_value_heads,
                                           c.head_dim)
        return self.q_norm(q), self.k_norm(k), v

    def _out(self, attn):
        return matmul(attn.reshape(*attn.shape[:2], -1), self.o_proj)

    def forward(self, x, rope_cache, position_ids=None):
        with jax.named_scope("attn.block"):
            q, k, v = self._proj(x)
            q, k = fused_rope(q, k, *rope_cache, position_ids)
            mask = block_causal_mask(x.shape[1], self.config.block_length)
            return self._out(flash_attention(q, k, v, attn_mask=mask))

    def decode(self, x, rope_cache, parts, cache, idx: int):
        """Decode over the paged pool
        (:func:`~paddle_tpu.models.parts.kv_attention`) under the block
        mask.  Returns (out, cache)."""
        with jax.named_scope("attn.block"):
            out, cache = kv_attention(
                "SdarAttention", x, self._proj, parts, rope_cache, cache,
                idx, block=self.config.block_length)
            return self._out(out), cache


class SdarMoE(Layer):
    """Router and this rank's share of the routed experts."""

    def __init__(self, config: SdarMoeConfig):
        super().__init__()
        c = config
        self.router = SoftmaxTopKGate(
            c.hidden_size, c.num_experts, c.num_experts_per_tok,
            norm_topk_prob=c.norm_topk_prob, dtype=c.dtype)
        self.experts = HeldExpertsMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held=c.experts_held, dtype=c.dtype)

    def forward(self, x, valid=None):
        with jax.named_scope("ffn.route"):
            idx, w = self.router.route(x.reshape(-1, x.shape[-1]))
        return self.experts(x, idx, w, valid=valid)


class SdarDecoderLayer(Layer):
    def __init__(self, config: SdarMoeConfig):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                                       dtype=c.dtype)
        self.self_attn = SdarAttention(c)
        self.post_attention_layernorm = RMSNorm(
            c.hidden_size, epsilon=c.rms_norm_eps, dtype=c.dtype)
        self.mlp = SdarMoE(c)

    def _ffn(self, h, valid=None):
        return h + self.mlp(self.post_attention_layernorm(h), valid=valid)

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


class SdarMoeModel(Layer):
    def __init__(self, config: SdarMoeConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList(
            [SdarDecoderLayer(c) for _ in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)
        cos, sin = build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    base=c.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)

    def forward(self, input_ids, position_ids=None):
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        rope = (self.rope_cos, self.rope_sin)
        for block in self.layers:
            x = block(x, rope, position_ids)
        return self.norm(x)


# the engine layouts this model cannot run, and why
# (``models.parts.ServingTraits.unsupported``)
_UNSUPPORTED = {
    "contiguous_cache":
        "its decode takes per-row positions over the paged pool only",
    "wave_prefill": "the prefill program masks causally and samples a first "
                    "token; a prompt here commits whole blocks under the "
                    "block mask and yields none",
    "prefix_cache": "no test shows a hit exact under the block mask",
    "preemption": "a victim mid-block would have to carry its block and the "
                  "forwards it has had; swap and recompute move K/V only",
    "kv_cache_dtype": "the block-masked attention path has no int8 pool",
    "mesh": "the held-experts layer has no exchange and the grouped "
            "product no sharded form",
    "spec_decode": "a row's tick is already a block of positions; drafts "
                   "have no place in it",
    "int8_weights": "quantize_for_decode knows no stacked expert weights",
}


class SdarMoeForCausalLM(CausalLMDecode, Layer):
    """Block-diffusion LM over :class:`SdarMoeModel`, served
    (:class:`~paddle_tpu.models.parts.CausalLMDecode`) over the paged
    pool."""

    def __init__(self, config: SdarMoeConfig):
        super().__init__()
        self.config = config
        self.model = SdarMoeModel(config)
        self.lm_head = self.create_parameter(
            (config.hidden_size, config.vocab_size), dtype=config.dtype,
            initializer=I.Normal(std=config.initializer_range),
            sharding=P("sharding", "mp"), attr_name="lm_head")

    def logits(self, hidden):
        return matmul(hidden, self.lm_head)

    def forward(self, input_ids, position_ids=None):
        """Logits (B, T, V) of whole sequences under the block-causal
        mask: position i's logits predict the token AT i."""
        return self.logits(self.model(input_ids, position_ids))

    @property
    def serving_traits(self) -> ServingTraits:
        c = self.config
        return ServingTraits(
            block_diffusion=c.block_diffusion,
            expert_layers=c.num_hidden_layers,
            kernel_specs=functools.partial(held_experts_kernel_specs, c),
            unsupported=_UNSUPPORTED)
