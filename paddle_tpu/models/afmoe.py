"""AFMoE decoder (``model_type: afmoe``, the Trinity family): sigmoid-routed
dropless experts beside a shared expert, gated attention that alternates
sliding-window layers (with RoPE) and global layers (with no position
encoding at all), sandwich norms.

With ``x`` the residual stream and ``N(·)`` an RMS norm with its own weight::

    x0      = E[ids] · sqrt(hidden)                        (mup_enabled)
    a       = N_post_attn(Attn(N_in(x)));      h  = x + a
    m       = N_post_mlp(F(N_pre_mlp(h)));     x' = h + m
    logits  = N_f(x_L) · W_head

``Attn``: q as ``num_attention_heads`` heads of ``head_dim``, k and v as
``num_key_value_heads``, and a gate ``g = sigmoid(y W_g)`` as wide as q; q
and k take a per-head RMS norm (one weight vector of ``head_dim`` for all
heads of a kind); RoPE (rotate-half) on ``sliding_attention`` layers only;
causal softmax attention, on sliding layers restricted to the last
``sliding_window`` keys; the output is ``(attn ⊙ g) W_o``.

``F`` is a SwiGLU MLP of ``intermediate_size`` on the first
``num_dense_layers`` layers, and on the others ``Shared(y) + Σ_k w_k ·
Expert_{e_k}(y)``: :class:`~paddle_tpu.distributed.moe.SigmoidTopKGate`
chooses ``num_experts_per_tok`` of ``num_experts`` and
:class:`~paddle_tpu.distributed.moe.HeldExpertsMoE` computes the experts
this expert-parallel rank holds (``ep_rank`` of ``ep_size``; all of them at
``ep_size`` 1).  What experts held elsewhere would add is left out — the
exchange that supplies it is not part of this model — and that partial
result goes on to the next layer; the shared expert is computed whole.

The cache row is plain K and V of ``num_key_value_heads · head_dim``, so
the model decodes over the stacked caches llama uses
(:func:`~paddle_tpu.models.parts.kv_attention`): the paged pool
(``serving/kv_cache.py``) as it is and, for ``generate()``, the contiguous
cache.  Window layers pass their window to the cached-attention
ops, whose kernel neither reads nor scores blocks behind it; the allocator
frees nothing behind a window yet (ROADMAP R5).
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
from ..ops import build_rope_cache, flash_attention, fused_rope
from ..tensor.math import matmul
from .llama import swiglu_mlp
from .parts import (CausalLMDecode, ServingTraits, band_mask, join_valid,
                    kv_attention)

__all__ = ["AfmoeConfig", "AfmoeAttention", "AfmoeMoE",
           "AfmoeDecoderLayer", "AfmoeModel", "AfmoeForCausalLM",
           "tiny_afmoe_config"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class AfmoeConfig:
    """The published ``afmoe`` keys (defaults: Trinity-Large-Preview), plus
    the expert-parallel share this instance holds."""
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the leading dense layers' MLP
    moe_intermediate_size: int = 3072       # one expert, routed or shared
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    score_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"
    # this instance's share of every expert layer: rank ``ep_rank`` of
    # ``ep_size`` holds experts [rank, rank + 1) · num_experts / ep_size
    ep_size: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{SLIDING!r} or {FULL!r}, got {self.layer_types}")
        if self.score_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1:
            raise NotImplementedError(
                "AfmoeConfig: only sigmoid routing without a group limit "
                f"(score_func={self.score_func!r}, n_group={self.n_group}, "
                f"topk_group={self.topk_group})")
        if (self.num_experts % self.ep_size
                or not 0 <= self.ep_rank < self.ep_size):
            raise ValueError(
                f"{self.num_experts} experts do not split over ep_size "
                f"{self.ep_size} (ep_rank {self.ep_rank})")

    @property
    def experts_held(self) -> Tuple[int, int]:
        """[lo, hi): the routed experts whose weights this rank holds."""
        n = self.num_experts // self.ep_size
        return self.ep_rank * n, (self.ep_rank + 1) * n

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers


def tiny_afmoe_config(**overrides) -> AfmoeConfig:
    """Small config for tests: one dense layer, then a whole period of
    three window layers and a global one."""
    cfg = AfmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, sliding_window=16,
        layer_types=(SLIDING,) * 4 + (FULL,), max_position_embeddings=128)
    return dataclasses.replace(cfg, **overrides)


class AfmoeAttention(Layer):
    """Gated GQA attention with per-head q/k norms; a window and RoPE on
    sliding layers, neither on global ones."""

    def __init__(self, config: AfmoeConfig, layer_type: str):
        super().__init__()
        c = config
        self.config = c
        self.window = int(c.sliding_window) if layer_type == SLIDING else None
        self.scope = "attn.window" if self.window else "attn.global"
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
        self.gate_proj = proj("gate_proj", (c.hidden_size, nh * hd), col)
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

    def _rope(self, q, k, rope_cache, position_ids):
        if self.window is None:             # global layers: no position
            return q, k
        return fused_rope(q, k, *rope_cache, position_ids)

    def _qkv(self, x, rope_cache, position_ids):
        q, k, v = self._proj(x)
        return (*self._rope(q, k, rope_cache, position_ids), v)

    def _out(self, x, attn):
        b, s, _ = x.shape
        with jax.named_scope("attn.gate"):
            gate = jax.nn.sigmoid(matmul(x, self.gate_proj))
            return matmul(attn.reshape(b, s, -1) * gate, self.o_proj)

    def forward(self, x, rope_cache, position_ids=None):
        with jax.named_scope(self.scope):
            q, k, v = self._qkv(x, rope_cache, position_ids)
            mask = (None if self.window is None
                    else band_mask(x.shape[1], self.window))
            out = flash_attention(q, k, v, causal=True, attn_mask=mask)
            return self._out(x, out)

    def decode(self, x, rope_cache, parts, cache, idx: int):
        """Decode over the stacked cache
        (:func:`~paddle_tpu.models.parts.kv_attention`); the gate and the
        output projection once over the tokens of all ``parts``.  Returns
        (out, cache)."""
        with jax.named_scope(self.scope):
            out, cache = kv_attention(
                "AfmoeAttention", x, self._proj, parts, rope_cache, cache,
                idx, rope=self._rope, window=self.window)
            return self._out(x, out), cache


class AfmoeMoE(Layer):
    """Router, this rank's share of the routed experts, and the shared
    expert (whole)."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        c = config
        self.router = SigmoidTopKGate(
            c.hidden_size, c.num_experts, c.num_experts_per_tok,
            route_scale=c.route_scale, route_norm=c.route_norm,
            dtype=c.dtype)
        self.experts = HeldExpertsMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held=c.experts_held, dtype=c.dtype)
        self.shared_experts = swiglu_mlp(
            c, c.moe_intermediate_size * c.num_shared_experts)

    def forward(self, x, valid=None):
        with jax.named_scope("ffn.route"):
            idx, w = self.router.route(x.reshape(-1, x.shape[-1]))
        routed = self.experts(x, idx, w, valid=valid)
        with jax.named_scope("ffn.shared"):
            return self.shared_experts(x) + routed


class AfmoeDecoderLayer(Layer):
    def __init__(self, config: AfmoeConfig, index: int):
        super().__init__()
        c = config

        def norm():
            return RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                           dtype=c.dtype)
        self.input_layernorm = norm()
        self.self_attn = AfmoeAttention(c, c.layer_types[index])
        self.post_attention_layernorm = norm()
        self.pre_mlp_layernorm = norm()
        self.dense = index < c.num_dense_layers
        self.mlp = (swiglu_mlp(c, c.intermediate_size) if self.dense
                    else AfmoeMoE(c))
        self.post_mlp_layernorm = norm()

    def _ffn(self, h, valid=None):
        y = self.pre_mlp_layernorm(h)
        if self.dense:
            with jax.named_scope("ffn.dense"):
                m = self.mlp(y)
        else:
            m = self.mlp(y, valid=valid)
        return h + self.post_mlp_layernorm(m)

    def forward(self, x, rope_cache, position_ids=None):
        a = self.self_attn(self.input_layernorm(x), rope_cache, position_ids)
        return self._ffn(x + self.post_attention_layernorm(a))

    def decode(self, x, rope_cache, parts, cache, idx: int):
        with jax.named_scope("attn"):
            a, cache = self.self_attn.decode(
                self.input_layernorm(x), rope_cache, parts, cache, idx)
            h = x + self.post_attention_layernorm(a)
        with jax.named_scope("ffn"):
            return self._ffn(h, join_valid(parts)), cache


class AfmoeModel(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList(
            [AfmoeDecoderLayer(c, i) for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)
        cos, sin = build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    base=c.rope_theta)
        self.register_buffer("rope_cos", cos)
        self.register_buffer("rope_sin", sin)

    def _embed(self, input_ids):
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        if self.config.mup_enabled:
            # scaled in float32: sqrt(hidden) has no exact bf16 form
            x = (x.astype(jnp.float32)
                 * math.sqrt(self.config.hidden_size)).astype(x.dtype)
        return x

    def forward(self, input_ids, position_ids=None):
        x = self._embed(input_ids)
        rope = (self.rope_cos, self.rope_sin)
        for block in self.layers:
            x = block(x, rope, position_ids)
        return self.norm(x)


# the engine layouts this model cannot run, and why
# (``models.parts.ServingTraits.unsupported``)
_UNSUPPORTED = {
    "contiguous_cache":
        "its decode takes per-row positions over the paged pool only",
    "kv_cache_dtype": "the windowed attention path has no int8 pool",
    "mesh": "the held-experts layer has no exchange and the grouped "
            "product no sharded form",
    "spec_decode": "the model drafter keeps a contiguous cache and "
                   "draft_model_from truncates a llama",
    "int8_weights": "quantize_for_decode knows no stacked expert weights",
}


class AfmoeForCausalLM(CausalLMDecode, Layer):
    """Causal LM over :class:`AfmoeModel`, served over the stacked cache
    (:class:`~paddle_tpu.models.parts.CausalLMDecode`; the embedding's μP
    scaling stays this model's)."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = self.create_parameter(
                (config.hidden_size, config.vocab_size), dtype=config.dtype,
                initializer=I.Normal(std=config.initializer_range),
                sharding=P("sharding", "mp"), attr_name="lm_head")

    def logits(self, hidden):
        if self.config.tie_word_embeddings:
            return matmul(hidden, self.model.embed_tokens.T)
        return matmul(hidden, self.lm_head)

    def forward(self, input_ids, position_ids=None):
        return self.logits(self.model(input_ids, position_ids))

    def _embed(self, input_ids):
        return self.model._embed(input_ids)

    @property
    def serving_traits(self) -> ServingTraits:
        c = self.config
        return ServingTraits(
            expert_layers=c.num_expert_layers,
            attention_windows=tuple(block.self_attn.window
                                    for block in self.model.layers),
            kernel_specs=functools.partial(held_experts_kernel_specs, c),
            unsupported=_UNSUPPORTED)
