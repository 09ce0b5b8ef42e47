"""LFM2-MoE decoder (``model_type: lfm2_moe``, LFM2-8B-A1B): most layers
mix tokens by a gated SHORT CONVOLUTION whose only memory of a request is
the last ``conv_L_cache - 1`` gated inputs of each channel, a few by
grouped-query attention; the first layers carry a dense SwiGLU, the others
sigmoid-routed experts.

With ``x`` the residual stream and ``N(·)`` an RMS norm with its own weight::

    h       = x + Op(N_op(x));        x' = h + F(N_ffn(h))
    logits  = N_f(x_L) · Eᵀ                              (tied head)

``Op`` on ``conv`` layers: ``[B, C, X] = split_3(y W_in)``, ``u = B ⊙ X``,
``c_t = Σ_j k_j ⊙ u_{t-(L-1)+j}`` (depth-wise, causal, one ``L``-tap filter
a channel, zeros before the sequence, no bias), ``Op = (C ⊙ c) W_out``; no
position encoding.  ``Op`` on ``full_attention`` layers: q as
``num_attention_heads`` heads of ``hidden / heads``, k and v as
``num_key_value_heads``; q and k take a per-head RMS norm (one weight
vector a kind) and RoPE (rotate-half); causal softmax attention; ``W_o``.

``F`` is a SwiGLU MLP of ``intermediate_size`` on the first
``num_dense_layers`` layers, and on the others ``Σ_k w_k · Expert_{e_k}(y)``:
:class:`~paddle_tpu.distributed.moe.SigmoidTopKGate` chooses
``num_experts_per_tok`` of ``num_experts`` by score plus selection bias and
weighs them by the score alone over ``sum + 1e-6``;
:class:`~paddle_tpu.distributed.moe.HeldExpertsMoE` computes the experts
this expert-parallel rank holds (``ep_rank`` of ``ep_size``).  What experts
held elsewhere would add is left out, as in ``models/afmoe.py``.

**The decode state has two kinds of leaf** (a dict): ``"attn"``, K and V of
the attention layers alone — the stacked contiguous cache for
``generate()``, the paged pool (``serving/kv_cache.py``) for the serving
engine, with one index a KV layer — and ``"conv"``, ``(conv layers, rows,
L - 1, hidden)``: each row's last gated inputs, which no position
addresses.  So a convolution layer's cache form takes the ``valid`` mask of
the real tokens and advances a row's state only by them: the state it hands
back is the state as of the row's last valid token (a row with none keeps
what it had), and a row at position 0 starts from zeros whatever the state
holds — a slot is reused without a reset.  The engine's side of this is
``serving_traits``' ``slot_state`` / ``init_serving_cache`` below.
"""

from __future__ import annotations

import dataclasses
import functools
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
from .llama import LlamaMLP
from .parts import (CausalLMDecode, ServingTraits, carried_window,
                    join_valid, kv_attention, part_by_part)

__all__ = ["Lfm2MoeConfig", "Lfm2ShortConv", "Lfm2Attention",
           "Lfm2MoeForCausalLM", "tiny_lfm2_config"]

CONV, FULL = "conv", "full_attention"
# LFM2-8B-A1B: attention on layers 2, 6, 10, 14, 18, 21 of 24
_PUBLISHED_ATTENTION = (2, 6, 10, 14, 18, 21)


@dataclasses.dataclass
class Lfm2MoeConfig:
    """The published ``lfm2_moe`` keys (defaults: LFM2-8B-A1B), plus the
    expert-parallel share this instance holds."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168           # the leading dense layers' MLP
    moe_intermediate_size: int = 1792       # one routed expert
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"
    # this instance's share of every expert layer: rank ``ep_rank`` of
    # ``ep_size`` holds experts [rank, rank + 1) · num_experts / ep_size
    ep_size: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if i in _PUBLISHED_ATTENTION else CONV
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {CONV, FULL}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{CONV!r} or {FULL!r}, got {self.layer_types}")
        if self.conv_bias or self.conv_L_cache < 2:
            raise NotImplementedError(
                "Lfm2MoeConfig: a short convolution of at least two taps "
                f"without bias (conv_bias={self.conv_bias}, conv_L_cache="
                f"{self.conv_L_cache})")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} does not split into "
                f"{self.num_attention_heads} heads")
        if (self.num_experts % self.ep_size
                or not 0 <= self.ep_rank < self.ep_size):
            raise ValueError(
                f"{self.num_experts} experts do not split over ep_size "
                f"{self.ep_size} (ep_rank {self.ep_rank})")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def experts_held(self) -> Tuple[int, int]:
        """[lo, hi): the routed experts whose weights this rank holds."""
        n = self.num_experts // self.ep_size
        return self.ep_rank * n, (self.ep_rank + 1) * n

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The indices of the layers of one kind, in order: a layer's place
        in this tuple is its index into that kind's leaf of the state."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)


def tiny_lfm2_config(**overrides) -> Lfm2MoeConfig:
    """Small config for tests: two dense layers, then experts; the
    published period (convolutions with an attention layer among them)
    twice over, ending on a convolution as the published model does."""
    cfg = Lfm2MoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=6, num_dense_layers=2,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2,
        layer_types=(CONV, CONV, FULL, CONV, FULL, CONV),
        max_position_embeddings=128)
    return dataclasses.replace(cfg, **overrides)


class Lfm2ShortConv(Layer):
    """The gated short convolution: full-sequence form (``forward``) and
    cache form (``decode``) of the same three-line rule."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = config
        self.taps = int(c.conv_L_cache)
        init = I.Normal(std=c.initializer_range)
        self.in_proj = self.create_parameter(
            (c.hidden_size, 3 * c.hidden_size), dtype=c.dtype,
            initializer=init, sharding=P("sharding", "mp"),
            attr_name="in_proj")
        # one filter a channel, tap j weighs the input L-1-j steps back
        self.conv = self.create_parameter(
            (self.taps, c.hidden_size), dtype=c.dtype, initializer=init,
            attr_name="conv")
        self.out_proj = self.create_parameter(
            (c.hidden_size, c.hidden_size), dtype=c.dtype, initializer=init,
            sharding=P("mp", "sharding"), attr_name="out_proj")

    def _gated(self, x):
        """(C, u = B ⊙ X) of the normed block input."""
        b, c, xx = jnp.split(matmul(x, self.in_proj), 3, axis=-1)
        return c, b * xx

    def _filter(self, ext, s: int):
        """``ext`` (B, L-1+s, H): the ``s`` inputs behind their L-1
        predecessors → the s filtered outputs, summed in float32."""
        k = self.conv.astype(jnp.float32)
        ext = ext.astype(jnp.float32)
        return sum(k[j] * ext[:, j:j + s] for j in range(self.taps))

    def forward(self, x):
        with jax.named_scope("conv"):
            c, u = self._gated(x)
            ext = jnp.pad(u, ((0, 0), (self.taps - 1, 0), (0, 0)))
            y = self._filter(ext, x.shape[1]).astype(x.dtype)
            return matmul(c * y, self.out_proj)

    def decode(self, x, parts, state):
        """The tokens of ``parts`` (:mod:`~paddle_tpu.models.parts`; x
        (B, s, H), or all parts' tokens (T, 1, H)) against ``state``
        (rows, L-1, H), every row's last gated inputs.  The two
        projections run once over all tokens; each part filters its own
        tokens behind the state of ITS rows (``slots``; None: all) at ITS
        per-row (or one scalar) position.  Returns (out, state): a part's
        rows as of each row's last VALID token — ``valid`` (bool (B, s), a
        prefix of each row; None: all) marks the real tokens, a row
        without one keeps its state, and a row at position 0 starts from
        zeros."""
        def filtered(_, p, state, u):
            return carried_window(
                p, state, u,
                lambda ext, s: self._filter(ext, s).astype(x.dtype))
        with jax.named_scope("conv"):
            c, u = self._gated(x)
            y, state = part_by_part(parts, (u,), state, filtered)
            return matmul(c * y, self.out_proj), state


class Lfm2Attention(Layer):
    """GQA attention with per-head q/k norms and RoPE, over the stacked
    cache of the ATTENTION layers (``idx`` counts those alone)."""

    def __init__(self, config: Lfm2MoeConfig):
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
        self.out_proj = proj("out_proj", (nh * hd, c.hidden_size), row)
        self.q_layernorm = RMSNorm(hd, epsilon=c.norm_eps, dtype=c.dtype)
        self.k_layernorm = RMSNorm(hd, epsilon=c.norm_eps, dtype=c.dtype)

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
        return self.q_layernorm(q), self.k_layernorm(k), v

    def _qkv(self, x, rope_cache, position_ids):
        q, k, v = self._proj(x)
        return (*fused_rope(q, k, *rope_cache, position_ids), v)

    def forward(self, x, rope_cache, position_ids=None):
        with jax.named_scope("attn.global"):
            q, k, v = self._qkv(x, rope_cache, position_ids)
            out = flash_attention(q, k, v, causal=True)
            return matmul(out.reshape(*x.shape[:2], -1), self.out_proj)

    def decode(self, x, rope_cache, parts, cache, idx: int):
        """Decode over the attention layers' stacked cache
        (:func:`~paddle_tpu.models.parts.kv_attention`).  Returns (out,
        cache)."""
        with jax.named_scope("attn.global"):
            out, cache = kv_attention("Lfm2Attention", x, self._proj, parts,
                                      rope_cache, cache, idx)
            return matmul(out.reshape(*out.shape[:2], -1),
                          self.out_proj), cache


class Lfm2MoE(Layer):
    """Router and this rank's share of the routed experts (no shared
    expert)."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = config
        self.gate = SigmoidTopKGate(
            c.hidden_size, c.num_experts, c.num_experts_per_tok,
            route_scale=c.routed_scaling_factor,
            route_norm=c.norm_topk_prob, norm_eps=1e-6, dtype=c.dtype)
        # use_expert_bias False: the gate's selection bias stays at zero
        self.experts = HeldExpertsMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held=c.experts_held, dtype=c.dtype)

    def forward(self, x, valid=None):
        with jax.named_scope("ffn.route"):
            idx, w = self.gate.route(x.reshape(-1, x.shape[-1]))
        return self.experts(x, idx, w, valid=valid)


class Lfm2DecoderLayer(Layer):
    def __init__(self, config: Lfm2MoeConfig, index: int):
        super().__init__()
        c = config

        def norm():
            return RMSNorm(c.hidden_size, epsilon=c.norm_eps, dtype=c.dtype)
        self.kind = c.layer_types[index]
        # the layer's index into its kind's leaf of the decode state
        self.state_index = c.layers_of(self.kind).index(index)
        self.operator_norm = norm()
        if self.kind == FULL:
            self.self_attn = Lfm2Attention(c)
        else:
            self.conv = Lfm2ShortConv(c)
        self.ffn_norm = norm()
        self.dense = index < c.num_dense_layers
        self.feed_forward = LlamaMLP(c) if self.dense else Lfm2MoE(c)

    def _ffn(self, h, valid=None):
        y = self.ffn_norm(h)
        if self.dense:
            with jax.named_scope("ffn.dense"):
                return h + self.feed_forward(y)
        return h + self.feed_forward(y, valid=valid)

    def forward(self, x, rope_cache, position_ids=None):
        y = self.operator_norm(x)
        op = (self.self_attn(y, rope_cache, position_ids)
              if self.kind == FULL else self.conv(y))
        return self._ffn(x + op)

    def decode(self, x, rope_cache, parts, cache, index: int):
        """One layer (the model's ``index``-th, which nothing here needs)
        against the two-leaf state ``cache``: an attention layer writes and
        reads its layer of ``"attn"``, a convolution layer advances its
        layer of ``"conv"`` (each part its own rows), at the layer's place
        among its kind."""
        y, i = self.operator_norm(x), self.state_index
        if self.kind == FULL:
            with jax.named_scope("attn"):
                op, attn = self.self_attn.decode(
                    y, rope_cache, parts, cache["attn"], i)
            cache = dict(cache, attn=attn)
        else:
            op, rows = self.conv.decode(y, parts, cache["conv"][i])
            cache = dict(cache, conv=cache["conv"].at[i].set(rows))
        with jax.named_scope("ffn"):
            return self._ffn(x + op, join_valid(parts)), cache


class Lfm2MoeModel(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList(
            [Lfm2DecoderLayer(c, i) for i in range(c.num_hidden_layers)])
        self.embedding_norm = RMSNorm(c.hidden_size, epsilon=c.norm_eps,
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
        return self.embedding_norm(x)


# the engine layouts this model cannot run, and why
# (``models.parts.ServingTraits.unsupported``)
_UNSUPPORTED = {
    "contiguous_cache": "its attention layers decode per-row positions over "
                        "the paged pool only",
    "wave_prefill": "the prefill program addresses block tables, not the "
                    "slots whose convolution state a prompt must leave "
                    "behind",
    "prefix_cache": "a hit skips the positions whose convolution state the "
                    "request needs; no state is checkpointed at block "
                    "boundaries",
    "preemption": "swap, recompute and the host tier move KV blocks only "
                  "and would lose a slot's convolution state",
    "kv_cache_dtype": "the two-leaf cache has no int8 pool",
    "mesh": "the held-experts layer has no exchange and the convolution "
            "state no declared sharding",
    "spec_decode": "a rejected draft would have to roll the convolution "
                   "state back; only K/V rolls back by position",
    "int8_weights": "quantize_for_decode knows no stacked expert weights",
}


class Lfm2MoeForCausalLM(CausalLMDecode, Layer):
    """Causal LM over :class:`Lfm2MoeModel`, served
    (:class:`~paddle_tpu.models.parts.CausalLMDecode`) over ``cache =
    {"attn", "conv"}``: a part's ``slots`` are its rows of ``"conv"``, and
    ``decode_step`` is the pass over one part that addresses every row of
    it."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)
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

    def _final_norm(self, x):
        return self.model.embedding_norm(x)

    def _conv_state(self, rows: int):
        c = self.config
        return jnp.zeros((len(c.layers_of(CONV)), rows, c.conv_L_cache - 1,
                          c.hidden_size), c.dtype)

    def init_decode_state(self, batch_size: int, max_length: int):
        """``generate()``'s state: the attention layers' contiguous cache
        and the convolution layers' rows."""
        c = self.config
        return {"attn": jnp.zeros(
            (len(c.layers_of(FULL)), 2, batch_size, max_length,
             c.num_key_value_heads, c.head_dim), c.dtype),
            "conv": self._conv_state(batch_size)}

    def init_serving_cache(self, num_slots: int, num_blocks: int,
                           block_len: int):
        """The serving engine's cache for ``num_slots`` state rows and a
        pool of ``num_blocks`` blocks: the paged pool of the layers that
        hold K/V, and the convolution state a slot."""
        from ..serving.kv_cache import init_paged_kv_cache
        c = self.config
        return {"attn": init_paged_kv_cache(
            c, num_blocks, block_len, num_layers=len(c.layers_of(FULL))),
            "conv": self._conv_state(num_slots)}

    @property
    def serving_traits(self) -> ServingTraits:
        c = self.config
        return ServingTraits(
            slot_state=("conv",),
            init_serving_cache=self.init_serving_cache,
            expert_layers=c.num_expert_layers,
            kernel_specs=functools.partial(held_experts_kernel_specs, c),
            unsupported=_UNSUPPORTED)
