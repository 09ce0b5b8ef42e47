"""Qwen2-VL-style vision-language model (BASELINE.json config #4).

The reference side lives in PaddleMIX (Qwen2-VL on paddle.nn); in-tree here
as the multimodal benchmark workload.  Shape of the architecture:

  * **vision tower**: ViT — patch embedding over pixel values, pre-LN
    transformer blocks with full 2D attention, final projection into the
    LLM width (Qwen2-VL's PatchMerger role);
  * **language decoder**: Llama-shaped causal blocks; every
    ``cross_attn_interval``-th block carries an additional **cross-attention**
    sub-layer attending from text tokens to the projected vision features
    (the vision-conditioning path; Qwen2-VL splices vision tokens into the
    sequence — cross-attention is the equivalent framework capability this
    workload exercises, and what BASELINE.md names).

TPU mapping: vision and text batches ride (dp, sharding); vision tokens are
small, so the tower runs replicated over mp while the decoder shards heads
on mp as usual.  ZeRO-3 shards both towers' params — the config BASELINE
pins (sharding-3).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.fleet.mp_layers import constrain, vocab_parallel_lookup
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.common import LayerNorm, RMSNorm
from ..nn.layer import Layer, LayerList
from ..ops import build_rope_cache, flash_attention
from ..tensor.math import matmul
from .llama import (LlamaConfig, LlamaDecoderLayer, _batch_spec,
                    causal_lm_loss)
from .parts import DecodePart

__all__ = ["Qwen2VLConfig", "VisionTower", "Qwen2VLForConditionalGeneration",
           "tiny_qwen2_vl_config"]


@dataclasses.dataclass
class Qwen2VLConfig:
    # language side
    vocab_size: int = 32000
    hidden_size: int = 1024
    intermediate_size: int = 2816
    num_hidden_layers: int = 4
    num_attention_heads: int = 8
    num_key_value_heads: int = 8
    cross_attn_interval: int = 2          # every k-th block cross-attends
    max_position_embeddings: int = 2048
    # vision side
    image_size: int = 224
    patch_size: int = 14
    vision_hidden_size: int = 256
    vision_layers: int = 2
    vision_heads: int = 4
    in_channels: int = 3
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    dtype: str = "float32"
    recompute: bool = False

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def as_llama(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            initializer_range=self.initializer_range, dtype=self.dtype,
            context_parallel="gspmd")


def tiny_qwen2_vl_config(**overrides) -> Qwen2VLConfig:
    cfg = Qwen2VLConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        cross_attn_interval=1, image_size=16, patch_size=8,
        vision_hidden_size=32, vision_layers=1, vision_heads=2,
        max_position_embeddings=128)
    return dataclasses.replace(cfg, **overrides)


class ViTBlock(Layer):
    """Pre-LN ViT block, full bidirectional attention over patches."""

    def __init__(self, width: int, heads: int, dtype=None,
                 init_std: float = 0.02):
        super().__init__()
        self.heads = heads
        init = I.Normal(std=init_std)
        self.norm1 = LayerNorm(width, dtype=dtype)
        self.norm2 = LayerNorm(width, dtype=dtype)
        self.qkv = self.create_parameter((width, 3 * width), dtype=dtype,
                                         initializer=init, attr_name="qkv")
        self.proj = self.create_parameter((width, width), dtype=dtype,
                                          initializer=init, attr_name="proj")
        self.fc1 = self.create_parameter((width, 4 * width), dtype=dtype,
                                         initializer=init, attr_name="fc1")
        self.fc2 = self.create_parameter((4 * width, width), dtype=dtype,
                                         initializer=init, attr_name="fc2")

    def forward(self, x):
        b, n, w = x.shape
        qkv = matmul(self.norm1(x), self.qkv).reshape(b, n, 3, self.heads, -1)
        out = flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                              causal=False)
        x = x + matmul(out.reshape(b, n, w), self.proj)
        y = F.gelu(matmul(self.norm2(x), self.fc1), approximate=True)
        return x + matmul(y, self.fc2)


class VisionTower(Layer):
    """Patch embed → ViT blocks → projection into the decoder width."""

    def __init__(self, c: Qwen2VLConfig):
        super().__init__()
        self.config = c
        w = c.vision_hidden_size
        p = c.patch_size
        init = I.Normal(std=c.initializer_range)
        self.patch_proj = self.create_parameter(
            (p * p * c.in_channels, w), dtype=c.dtype, initializer=init,
            attr_name="patch_proj")
        self.pos_embed = self.create_parameter(
            (c.num_patches, w), dtype=c.dtype, initializer=init,
            attr_name="pos_embed")
        self.blocks = LayerList([
            ViTBlock(w, c.vision_heads, dtype=c.dtype,
                     init_std=c.initializer_range)
            for _ in range(c.vision_layers)])
        self.norm = LayerNorm(w, dtype=c.dtype)
        self.merger = self.create_parameter(
            (w, c.hidden_size), dtype=c.dtype, initializer=init,
            attr_name="merger")

    def forward(self, pixel_values):
        """(B, C, H, W) → (B, num_patches, hidden_size)."""
        c = self.config
        b, ch, hh, ww = pixel_values.shape
        p = c.patch_size
        x = pixel_values.reshape(b, ch, hh // p, p, ww // p, p)
        x = x.transpose(0, 2, 4, 3, 5, 1).reshape(
            b, (hh // p) * (ww // p), p * p * ch)
        x = matmul(x, self.patch_proj) + self.pos_embed[None]
        x = constrain(x, ("dp", "sharding"), None, None)
        for blk in self.blocks:
            x = blk(x)
        return matmul(self.norm(x), self.merger)


class CrossAttention(Layer):
    """Text queries attend to vision features (bidirectional over the
    feature axis)."""

    def __init__(self, c: Qwen2VLConfig):
        super().__init__()
        h = c.hidden_size
        self.heads = c.num_attention_heads
        init = I.Normal(std=c.initializer_range)
        self.norm = RMSNorm(h, epsilon=c.rms_norm_eps, dtype=c.dtype)
        self.q_proj = self.create_parameter((h, h), dtype=c.dtype,
                                            initializer=init,
                                            sharding=P("sharding", "mp"),
                                            attr_name="q_proj")
        self.kv_proj = self.create_parameter((h, 2 * h), dtype=c.dtype,
                                             initializer=init,
                                             sharding=P("sharding", "mp"),
                                             attr_name="kv_proj")
        self.o_proj = self.create_parameter((h, h), dtype=c.dtype,
                                            initializer=init,
                                            sharding=P("mp", "sharding"),
                                            attr_name="o_proj")
        # zero-init gate: the decoder starts text-only and learns to look
        self.gate = self.create_parameter((1,), dtype=c.dtype,
                                          initializer=I.Constant(0.0),
                                          attr_name="gate")

    def forward(self, x, vision):
        b, s, h = x.shape
        n = vision.shape[1]
        q = matmul(self.norm(x), self.q_proj).reshape(b, s, self.heads, -1)
        kv = matmul(vision, self.kv_proj).reshape(b, n, 2, self.heads, -1)
        q = constrain(q, ("dp", "sharding"), None, "mp", None)
        out = flash_attention(q, kv[:, :, 0], kv[:, :, 1], causal=False)
        return x + jnp.tanh(self.gate) * matmul(
            out.reshape(b, s, h), self.o_proj)


class Qwen2VLForConditionalGeneration(Layer):
    """Vision tower + cross-attending causal decoder + LM head."""

    def __init__(self, config: Qwen2VLConfig):
        super().__init__()
        c = config
        self.config = c
        self.visual = VisionTower(c)
        llama_cfg = c.as_llama()
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList([LlamaDecoderLayer(llama_cfg)
                                 for _ in range(c.num_hidden_layers)])
        self.cross = LayerList([
            CrossAttention(c)
            for i in range(c.num_hidden_layers)
            if (i + 1) % c.cross_attn_interval == 0])
        self._cross_at = [i for i in range(c.num_hidden_layers)
                          if (i + 1) % c.cross_attn_interval == 0]
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)
        self.lm_head = self.create_parameter(
            (c.hidden_size, c.vocab_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("sharding", "mp"), attr_name="lm_head")
        cos, sin = build_rope_cache(
            c.max_position_embeddings,
            c.hidden_size // c.num_attention_heads, base=c.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)

    def forward(self, input_ids, pixel_values, position_ids=None):
        c = self.config
        vision = self.visual(pixel_values)
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        x = constrain(x, *_batch_spec(x.ndim))
        rope = (self.rope_cos, self.rope_sin)
        for i, blk in enumerate(self.layers):
            def run(h, vis, blk=blk, i=i):
                h = blk(h, rope, position_ids)
                if i in self._cross_at:
                    h = self._cross_layer(i)(h, vis)
                return h
            if c.recompute and self.training:
                x = jax.checkpoint(run)(x, vision)
            else:
                x = run(x, vision)
        return matmul(self.norm(x), self.lm_head)

    def _cross_layer(self, block_idx: int) -> CrossAttention:
        return self.cross[self._cross_at.index(block_idx)]

    def compute_loss(self, input_ids, pixel_values, labels,
                     position_ids=None):
        logits = self.forward(input_ids, pixel_values, position_ids)
        return causal_lm_loss(logits, labels)

    # -- cached decode --------------------------------------------------------

    def decode_step(self, input_ids, cache, pos, vision):
        """(logits, cache).  ``vision``: precomputed tower features — they
        are position-free and fixed for the whole generation, so the cross
        layers just re-attend the new tokens to them each step (q_len ∈
        {1, prompt}); only self-attention carries the stacked KV cache."""
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        # batch-shard the gathered activations so the SPMD partitioner
        # never rematerialises the full table per device (MULTICHIP_r02)
        x = constrain(x, ("dp", "sharding"), None, None)
        rope = (self.rope_cos, self.rope_sin)
        parts = [DecodePart(input_ids, pos)]
        for i, blk in enumerate(self.layers):
            x, cache = blk.decode(x, rope, parts, cache, i)
            if i in self._cross_at:
                x = self._cross_layer(i)(x, vision)
        return matmul(self.norm(x), self.lm_head), cache

    def generate(self, input_ids, pixel_values, max_new_tokens: int = 32,
                 **kw):
        """Greedy/sampled generation conditioned on an image: the vision
        tower runs ONCE per call; its features ride the decode loop as a
        jit input (compiled program reused across prompts and images)."""
        from .generation import greedy_generate
        vision = self.visual(pixel_values)
        return greedy_generate(self, input_ids, max_new_tokens,
                               extra_inputs={"vision": vision}, **kw)
