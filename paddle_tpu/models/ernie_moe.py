"""ERNIE-4.5-style MoE decoder (BASELINE.json config #2).

The reference keeps ERNIE in a separate repo (PaddleNLP, built on the
framework's ``incubate/distributed/models/moe`` MoELayer — upstream
layout); it lives in-tree here as the expert-parallel benchmark workload.

Architecture (ERNIE-4.5 / DeepSeek-style sparse decoder): Llama-shaped
attention (GQA + RoPE + RMSNorm), the first ``moe_start_layer`` blocks use
a dense SwiGLU MLP, later blocks a :class:`~paddle_tpu.distributed.moe.
MoELayer` (GShard top-k capacity routing) plus a shared dense expert added
to every token.  Router aux + z losses accumulate into the LM loss.

TPU mapping: experts ride the EP axes of the mesh (expert dim sharded);
token batch on dp×sharding — the dispatch/combine einsums lower to the
all-to-alls the reference issues via global_scatter/global_gather.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.fleet.mp_layers import constrain, vocab_parallel_lookup
from ..distributed.moe import GShardGate, MoELayer
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..ops.rope import build_rope_cache
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP, RMSNorm,
                    _batch_spec, causal_lm_loss)
from .parts import DecodePart

__all__ = ["ErnieMoEConfig", "ErnieMoEModel", "ErnieMoEForCausalLM",
           "tiny_ernie_moe_config", "ernie45_moe_config"]


@dataclasses.dataclass
class ErnieMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 1024
    intermediate_size: int = 4096        # dense blocks + shared expert
    moe_intermediate_size: int = 1024    # per-expert FFN width
    num_hidden_layers: int = 4
    num_attention_heads: int = 8
    num_key_value_heads: int = 8
    num_experts: int = 8
    top_k: int = 2
    moe_start_layer: int = 1             # leading dense blocks (ERNIE style)
    use_shared_expert: bool = True
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    dtype: str = "float32"
    recompute: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def as_llama(self) -> LlamaConfig:
        """The attention sub-config (reused from the Llama blocks)."""
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


def ernie45_moe_config(**overrides) -> ErnieMoEConfig:
    """ERNIE-4.5-scale shape (the BASELINE.md MoE workload)."""
    cfg = ErnieMoEConfig(
        vocab_size=103424, hidden_size=8192, intermediate_size=28672,
        moe_intermediate_size=3584, num_hidden_layers=54,
        num_attention_heads=64, num_key_value_heads=8, num_experts=64,
        top_k=8, moe_start_layer=3, dtype="bfloat16")
    return dataclasses.replace(cfg, **overrides)


def tiny_ernie_moe_config(**overrides) -> ErnieMoEConfig:
    cfg = ErnieMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, num_experts=4,
        top_k=2, moe_start_layer=1, max_position_embeddings=128)
    return dataclasses.replace(cfg, **overrides)


class ErnieMoEDecoderLayer(Layer):
    def __init__(self, config: ErnieMoEConfig, layer_idx: int):
        super().__init__()
        c = config
        self.is_moe = layer_idx >= c.moe_start_layer
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                                       dtype=c.dtype)
        self.self_attn = LlamaAttention(c.as_llama())
        self.post_attention_layernorm = RMSNorm(
            c.hidden_size, epsilon=c.rms_norm_eps, dtype=c.dtype)
        if self.is_moe:
            self.moe = MoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                gate=GShardGate(c.hidden_size, c.num_experts, dtype=c.dtype),
                top_k=c.top_k, capacity_factor=c.capacity_factor,
                aux_loss_coef=c.aux_loss_coef, z_loss_coef=c.z_loss_coef,
                dtype=c.dtype)
            if c.use_shared_expert:
                llama_cfg = dataclasses.replace(
                    c.as_llama(), intermediate_size=c.intermediate_size)
                self.shared_expert = LlamaMLP(llama_cfg)
        else:
            self.mlp = LlamaMLP(c.as_llama())

    def forward(self, x, rope_cache, position_ids=None, segment_ids=None):
        h = x + self.self_attn(self.input_layernorm(x), rope_cache,
                               position_ids, segment_ids)
        return self._ffn(h, self.post_attention_layernorm(h))

    def _ffn(self, h, y):
        if self.is_moe:
            moe_out, aux = self.moe(y)
            if hasattr(self, "shared_expert"):
                moe_out = moe_out + self.shared_expert(y)
            return h + moe_out, aux
        return h + self.mlp(y), jnp.zeros((), jnp.float32)

    def decode(self, x, rope_cache, parts, cache, idx: int):
        a, cache = self.self_attn.decode(
            self.input_layernorm(x), rope_cache, parts, cache, idx)
        h = x + a
        out, _ = self._ffn(h, self.post_attention_layernorm(h))
        return out, cache


class ErnieMoEModel(Layer):
    def __init__(self, config: ErnieMoEConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList([ErnieMoEDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)
        cos, sin = build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    base=c.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)

    def forward(self, input_ids, position_ids=None, segment_ids=None
                ) -> Tuple[jax.Array, jax.Array]:
        c = self.config
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        x = constrain(x, *_batch_spec(x.ndim))
        rope = (self.rope_cos, self.rope_sin)
        aux_total = jnp.zeros((), jnp.float32)

        def run(block, h):
            return block(h, rope, position_ids, segment_ids)

        for block in self.layers:
            if c.recompute and self.training:
                x, aux = jax.checkpoint(
                    lambda h, blk=block: run(blk, h))(x)
            else:
                x, aux = run(block, x)
            aux_total = aux_total + aux
        return self.norm(x), aux_total

    def decode(self, input_ids, cache, pos):
        """Cache-carrying decode (same stacked-cache layout as LlamaModel;
        see models/generation.py).  Returns (hidden, cache)."""
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        # batch-shard the gathered activations so the SPMD partitioner
        # never rematerialises the full table per device (MULTICHIP_r02)
        x = constrain(x, ("dp", "sharding"), None, None)
        rope = (self.rope_cos, self.rope_sin)
        parts = [DecodePart(input_ids, pos)]
        for i, block in enumerate(self.layers):
            x, cache = block.decode(x, rope, parts, cache, i)
        return self.norm(x), cache


class ErnieMoEForCausalLM(Layer):
    """Causal LM over the MoE decoder; loss = CE + router aux losses."""

    def __init__(self, config: ErnieMoEConfig):
        super().__init__()
        self.config = config
        self.model = ErnieMoEModel(config)
        self.lm_head = self.create_parameter(
            (config.hidden_size, config.vocab_size), dtype=config.dtype,
            initializer=I.Normal(std=config.initializer_range),
            sharding=P("sharding", "mp"), attr_name="lm_head")

    def forward(self, input_ids, position_ids=None, segment_ids=None):
        hidden, aux = self.model(input_ids, position_ids, segment_ids)
        from ..tensor.math import matmul
        return matmul(hidden, self.lm_head), aux

    def compute_loss(self, input_ids, labels, position_ids=None,
                     segment_ids=None):
        logits, aux = self.forward(input_ids, position_ids, segment_ids)
        if segment_ids is not None:
            from .llama import mask_boundary_labels
            labels = mask_boundary_labels(labels, segment_ids)
        return causal_lm_loss(logits, labels) + aux

    def decode_step(self, input_ids, cache, pos):
        """(logits, cache) — the generation hook (router aux losses are a
        training quantity and are dropped at decode time).

        MoE routing note: expert capacity is recomputed per call from the
        token count, and decode steps see T = batch; eval-mode capacity is
        no-drop while batch·top_k ≤ ``moe.EVAL_NO_DROP_SLOTS``·num_experts
        (see ``MoELayer._capacity``), so for decode-shaped batches routing
        never drops a token that a full forward would keep.  Decode batches
        past that threshold fall back to the factor-based capacity — size
        ``eval_capacity_factor`` accordingly."""
        hidden, cache = self.model.decode(input_ids, cache, pos)
        from ..tensor.math import matmul
        return matmul(hidden, self.lm_head), cache

    def generate(self, input_ids, max_new_tokens: int = 32, **kw):
        """Greedy/sampled generation with the pre-allocated KV cache (see
        :func:`paddle_tpu.models.generation.greedy_generate`)."""
        from .generation import greedy_generate
        return greedy_generate(self, input_ids, max_new_tokens, **kw)
