"""Olmo-Hybrid decoder (``model_type: olmo_hybrid``, Olmo-Hybrid-7B): three
layers in four mix tokens by a GATED DELTANET — linear attention whose only
memory of a request is one float32 matrix ``S`` (``d_k × d_v``) a head and
the last three inputs of a short convolution —, the fourth by plain
multi-head softmax attention; every layer carries a SwiGLU.

With ``x`` the residual stream and ``N(·)`` an RMS norm with its own weight
(the Olmo 2 / Olmo 3 "reordered norm": the norm follows the sub-layer)::

    h       = x + N_mix(Mixer(x));      x' = h + N_ffn(MLP(h))
    logits  = N_f(x_L) · W_head                            (untied head)

``Mixer`` on ``full_attention`` layers: ``q = N_q(y W_q)``, ``k = N_k(y
W_k)`` — the norm over the WHOLE projection, one weight vector each —, ``v
= y W_v``, split into heads of ``hidden / heads``; NO rotary embedding
(the published ``rope_theta`` is null: position reaches these layers
through the convolutions and the decayed state); causal softmax attention;
``W_o``.

``Mixer`` on ``linear_attention`` layers (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; ``ops/gated_delta.py`` has the rule)::

    [q̃, k̃, ṽ] = y W_in                  (H·d_k, H·d_k, H·d_v channels)
    c_t = silu(Σ_j w_j ⊙ u_{t-(L-1)+j})    depth-wise, causal, L taps, no bias
    q_t = c^q / ‖c^q‖ · d_k^{-1/2},   k_t = c^k / ‖c^k‖      per head
    β_t = (2 ·) σ(y W_b),   g_t = −exp(A_log) · softplus(y W_a + dt_bias)
    S̃ = e^{g_t} S_{t-1};  S_t = S̃ + β_t k_t (v_t − S̃ᵀk_t)ᵀ;  o_t = S_tᵀ q_t
    Mixer = [N_o(o_t) ⊙ silu(y W_g)]_heads W_o

(``linear_allow_neg_eigval`` doubles β, so ``I − β k kᵀ`` may reflect.)

**The decode state has three kinds of leaf** (a dict): ``"attn"``, K and V
of the attention layers alone (the contiguous cache for ``generate()``,
the paged pool for the serving engine, one index a K/V layer);  ``"conv"``,
``(linear layers, rows, (L − 1) · channels)`` in the model's dtype: each
row's last inputs of the convolution, oldest first, laid end to end (a
tap axis of 3 would be padded to a tile of 16 and relaid at the program's
edges); ``"delta"``, ``(linear layers, rows,
d_k, H · d_v)`` FLOAT32: each row's ``S``, heads side by side on the last
axis.  No position addresses the last two, so a linear layer's cache form
takes the ``valid`` mask of the real tokens and advances a row's state only
by them (a padded token is an identity step, ``β = 0`` and ``g = 0``): what
it hands back is the state as of the row's last valid token, a row without
one keeps what it had, and a row at position 0 starts from zeros whatever
the state holds — a slot is reused without a reset (``models/lfm2.py``'s
rules; the window's are the same code, ``parts.carried_window``).  ``S`` is
gigabytes over a serving engine's slots and is updated IN PLACE: a layer
hands ``ops.gated_delta.gated_delta_update`` the whole leaf, its index and
the part's rows, and gets the leaf back.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..nn import initializer as I
from ..nn.common import RMSNorm
from ..nn.layer import Layer, LayerList
from ..ops import flash_attention
from ..ops.gated_delta import gated_delta_chunked, gated_delta_update
from ..tensor.math import matmul
from .llama import LlamaMLP
from .parts import (CausalLMDecode, ServingTraits, carried_window,
                    kv_attention, part_by_part)

__all__ = ["OlmoHybridConfig", "GatedDeltaNet", "OlmoHybridAttention",
           "OlmoHybridForCausalLM", "tiny_olmo_hybrid_config"]

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass
class OlmoHybridConfig:
    """The published ``olmo_hybrid`` keys (defaults: Olmo-Hybrid-7B)."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            # three linear layers, then a full one
            self.layer_types = tuple(
                FULL if i % 4 == 3 else LINEAR
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {LINEAR, FULL}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{LINEAR!r} or {FULL!r}, got {self.layer_types}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                "OlmoHybridConfig: as many key heads as value heads on the "
                f"linear layers ({self.linear_num_key_heads} != "
                f"{self.linear_num_value_heads})")
        if self.attention_bias or self.linear_conv_kernel_dim < 2:
            raise NotImplementedError(
                "OlmoHybridConfig: no attention bias and a convolution of "
                f"at least two taps (attention_bias={self.attention_bias}, "
                f"linear_conv_kernel_dim={self.linear_conv_kernel_dim})")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} does not split into "
                f"{self.num_attention_heads} heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_channels(self) -> int:
        """q̃, k̃ and ṽ side by side: what the convolution runs over."""
        return self.linear_num_key_heads * (2 * self.linear_key_head_dim
                                            + self.linear_value_head_dim)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The indices of the layers of one kind, in order: a layer's place
        in this tuple is its index into that kind's leaves of the state."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)


def tiny_olmo_hybrid_config(**overrides) -> OlmoHybridConfig:
    """Small config for tests: one published period (three linear layers
    and a full one) and a linear layer more, so that a linear layer also
    follows an attention layer."""
    cfg = OlmoHybridConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
        layer_types=(LINEAR, LINEAR, LINEAR, FULL, LINEAR),
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=32,
        max_position_embeddings=128)
    return dataclasses.replace(cfg, **overrides)


class GatedDeltaNet(Layer):
    """The Gated DeltaNet mixer: full-sequence form (``forward``) and cache
    form (``decode``) of the same rule."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        c = config
        self.heads = h = int(c.linear_num_key_heads)
        self.dk, self.dv = int(c.linear_key_head_dim), int(
            c.linear_value_head_dim)
        self.taps = int(c.linear_conv_kernel_dim)
        self.beta_scale = 2.0 if c.linear_allow_neg_eigval else 1.0
        self.eps = float(c.rms_norm_eps)
        init = I.Normal(std=c.initializer_range)
        col, row = P("sharding", "mp"), P("mp", "sharding")

        def proj(name, shape, spec=None, dtype=c.dtype, initializer=init):
            return self.create_parameter(shape, dtype=dtype,
                                         initializer=initializer,
                                         sharding=spec, attr_name=name)
        # [q̃ | k̃ | ṽ] in one product; one filter a channel, tap j weighs
        # the input L-1-j steps back
        self.in_proj = proj("in_proj", (c.hidden_size, c.conv_channels), col)
        self.conv = proj("conv", (self.taps, c.conv_channels))
        self.gate_proj = proj("gate_proj", (c.hidden_size, h * self.dv), col)
        self.a_proj = proj("a_proj", (c.hidden_size, h))
        self.b_proj = proj("b_proj", (c.hidden_size, h))
        self.A_log = proj("A_log", (h,), dtype="float32",
                          initializer=I.Constant(0.0))
        self.dt_bias = proj("dt_bias", (h,), dtype="float32",
                            initializer=I.Constant(0.0))
        self.o_norm = RMSNorm(self.dv, epsilon=c.rms_norm_eps, dtype=c.dtype)
        self.out_proj = proj("out_proj", (h * self.dv, c.hidden_size), row)

    # -- token-wise ---------------------------------------------------------

    def _filter(self, ext, s: int):
        """``ext`` (B, L-1+s, C): the ``s`` inputs behind their L-1
        predecessors → silu of the s filtered outputs, float32."""
        k = self.conv.astype(jnp.float32)
        ext = ext.astype(jnp.float32)
        return jax.nn.silu(sum(k[j] * ext[:, j:j + s]
                               for j in range(self.taps)))

    def _gates(self, x):
        """(g (…, H) ≤ 0, β (…, H)) of the layer's input, float32."""
        f32 = jnp.float32
        a = matmul(x, self.a_proj).astype(f32)
        g = -jnp.exp(self.A_log.astype(f32)) * jax.nn.softplus(
            a + self.dt_bias.astype(f32))
        beta = self.beta_scale * jax.nn.sigmoid(
            matmul(x, self.b_proj).astype(f32))
        return g, beta

    def _heads(self, c):
        """The convolved channels (B, s, C) float32 → q (normalised, scaled),
        k (normalised) (B, s, H, d_k), v (B, s, H, d_v)."""
        h, dk, dv = self.heads, self.dk, self.dv
        q, k, v = jnp.split(c, [h * dk, 2 * h * dk], axis=-1)
        q = q.reshape(*q.shape[:2], h, dk)
        k = k.reshape(*k.shape[:2], h, dk)

        def unit(z):
            return z * jax.lax.rsqrt(
                jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)
        return (unit(q) * dk ** -0.5, unit(k),
                v.reshape(*v.shape[:2], h, dv))

    def _out(self, x, o):
        """``N_o(o) ⊙ silu(x W_g)`` a head, heads side by side, ``W_o``."""
        gate = matmul(x, self.gate_proj).astype(jnp.float32)
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + self.eps)
        o = o * self.o_norm.weight.astype(jnp.float32)
        o = o.reshape(*o.shape[:2], -1) * jax.nn.silu(gate)
        return matmul(o.astype(x.dtype), self.out_proj)

    # -- the two forms ------------------------------------------------------

    def forward(self, x):
        with jax.named_scope("gdn"):
            u = matmul(x, self.in_proj)
            ext = jnp.pad(u, ((0, 0), (self.taps - 1, 0), (0, 0)))
            q, k, v = self._heads(self._filter(ext, x.shape[1]))
            g, beta = self._gates(x)
            o, _ = jax.vmap(gated_delta_chunked)(q, k, v, g, beta)
            return self._out(x, o)

    def decode(self, x, parts, window, delta, idx: int):
        """The tokens of ``parts`` (:mod:`~paddle_tpu.models.parts`; x
        (B, s, H), or all parts' tokens (T, 1, H)) against the two WHOLE
        leaves of the linear layers' state, of which this layer is index
        ``idx``: ``window`` (linear layers, rows, (L-1)·C), every row's last
        inputs of the convolution, and ``delta`` (linear layers, rows, d_k,
        H·d_v), every row's ``S``.  The projections run once over all
        tokens; each part convolves its own tokens behind the window of ITS
        rows (``slots``; None: all) and advances ITS rows of ``delta[idx]``,
        by its real tokens (``valid``) only, from zeros at position 0.
        Returns (out, window, delta)."""
        keep = self.taps - 1

        def mix(_, p, state, u, g, beta):
            window, delta = state
            # the part's rows of this layer, cut from the leaf where it
            # lies and put back there (no copy of a layer)
            b = u.shape[0]
            at = (idx, 0 if p.slots is None else p.slots[0], 0)
            mine = jax.lax.dynamic_slice(
                window, at, (1, b, window.shape[2]))[0]
            c, mine = carried_window(
                p._replace(slots=None), mine.reshape(b, keep, -1), u,
                self._filter)
            window = jax.lax.dynamic_update_slice(
                window, mine.reshape(1, b, -1), at)
            q, k, v = self._heads(c)
            fresh = jnp.asarray(p.pos) == 0
            o, delta = gated_delta_update(delta, idx, p.slots, q, k, v, g,
                                          beta, valid=p.valid, fresh=fresh)
            return o, (window, delta)
        with jax.named_scope("gdn"):
            g, beta = self._gates(x)
            o, (window, delta) = part_by_part(
                parts, (matmul(x, self.in_proj), g, beta), (window, delta),
                mix)
            return self._out(x, o), window, delta


def _no_rope(q, k, rope_cache, ids):
    return q, k


class OlmoHybridAttention(Layer):
    """Multi-head attention with q/k norms over the whole projection and no
    rotary embedding, over the stacked cache of the ATTENTION layers
    (``idx`` counts those alone)."""

    def __init__(self, config: OlmoHybridConfig):
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
        self.q_norm = RMSNorm(nh * hd, epsilon=c.rms_norm_eps, dtype=c.dtype)
        self.k_norm = RMSNorm(nkv * hd, epsilon=c.rms_norm_eps,
                              dtype=c.dtype)

    def _proj(self, x):
        """q, k (normed) and v of every token, split into heads:
        token-wise."""
        c = self.config
        b, s, _ = x.shape
        q = self.q_norm(matmul(x, self.q_proj))
        k = self.k_norm(matmul(x, self.k_proj))
        v = matmul(x, self.v_proj)
        return (q.reshape(b, s, c.num_attention_heads, c.head_dim),
                k.reshape(b, s, c.num_key_value_heads, c.head_dim),
                v.reshape(b, s, c.num_key_value_heads, c.head_dim))

    def forward(self, x):
        with jax.named_scope("attn.global"):
            out = flash_attention(*self._proj(x), causal=True)
            return matmul(out.reshape(*x.shape[:2], -1), self.o_proj)

    def decode(self, x, parts, cache, idx: int):
        """Decode over the attention layers' stacked cache
        (:func:`~paddle_tpu.models.parts.kv_attention`).  Returns (out,
        cache)."""
        with jax.named_scope("attn.global"):
            out, cache = kv_attention("OlmoHybridAttention", x, self._proj,
                                      parts, None, cache, idx, rope=_no_rope)
            return matmul(out.reshape(*out.shape[:2], -1),
                          self.o_proj), cache


class OlmoHybridDecoderLayer(Layer):
    def __init__(self, config: OlmoHybridConfig, index: int):
        super().__init__()
        c = config

        def norm():
            return RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                           dtype=c.dtype)
        self.kind = c.layer_types[index]
        # the layer's index into its kind's leaves of the decode state
        self.state_index = c.layers_of(self.kind).index(index)
        self.mixer = (OlmoHybridAttention(c) if self.kind == FULL
                      else GatedDeltaNet(c))
        self.mixer_norm = norm()
        self.mlp = LlamaMLP(c)
        self.mlp_norm = norm()

    # the norms' placement, one line a sub-layer (the reordered norm)
    def _mixed(self, x, op):
        return x + self.mixer_norm(op)

    def _fed(self, h):
        with jax.named_scope("ffn.dense"):
            return h + self.mlp_norm(self.mlp(h))

    def forward(self, x):
        return self._fed(self._mixed(x, self.mixer(x)))

    def decode(self, x, rope_cache, parts, cache, index: int):
        """One layer (the model's ``index``-th, which nothing here needs)
        against the three-leaf state ``cache``: an attention layer writes
        and reads its layer of ``"attn"``, a linear layer advances its
        layer of ``"conv"`` and of ``"delta"`` (each part its own rows), at
        the layer's place among its kind."""
        i = self.state_index
        if self.kind == FULL:
            with jax.named_scope("attn"):
                op, attn = self.mixer.decode(x, parts, cache["attn"], i)
            cache = dict(cache, attn=attn)
        else:
            op, window, delta = self.mixer.decode(
                x, parts, cache["conv"], cache["delta"], i)
            cache = dict(cache, conv=window, delta=delta)
        with jax.named_scope("ffn"):
            return self._fed(self._mixed(x, op)), cache


class OlmoHybridModel(Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            initializer=I.Normal(std=c.initializer_range),
            sharding=P("mp", "sharding"), attr_name="embed_tokens")
        self.layers = LayerList(
            [OlmoHybridDecoderLayer(c, i)
             for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps,
                            dtype=c.dtype)

    def forward(self, input_ids):
        from ..distributed.fleet.mp_layers import vocab_parallel_lookup
        x = vocab_parallel_lookup(self.embed_tokens, input_ids)
        for block in self.layers:
            x = block(x)
        return self.norm(x)


# the engine layouts this model cannot run, and why
# (``models.parts.ServingTraits.unsupported``)
_UNSUPPORTED = {
    "contiguous_cache": "its attention layers decode per-row positions over "
                        "the paged pool only",
    "wave_prefill": "the prefill program addresses block tables, not the "
                    "slots whose window and matrix state a prompt must "
                    "leave behind",
    "prefix_cache": "a hit skips the positions whose matrix state the "
                    "request needs, and that state is the whole prefix's: "
                    "none is checkpointed at block boundaries",
    "preemption": "swap, recompute and the host tier move KV blocks only "
                  "and would lose a slot's window and matrix state",
    "kv_cache_dtype": "the three-leaf cache has no int8 pool",
    "mesh": "the window and the matrix state have no declared sharding",
    "spec_decode": "a rejected draft would have to roll the matrix state "
                   "back; only K/V rolls back by position",
    "int8_weights": "quantize_for_decode knows no convolution taps or "
                    "per-head decay parameters",
}


class OlmoHybridForCausalLM(CausalLMDecode, Layer):
    """Causal LM over :class:`OlmoHybridModel`, served
    (:class:`~paddle_tpu.models.parts.CausalLMDecode`) over ``cache =
    {"attn", "conv", "delta"}``: a part's ``slots`` are its rows of
    ``"conv"`` and of ``"delta"``."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.model = OlmoHybridModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = self.create_parameter(
                (config.hidden_size, config.vocab_size), dtype=config.dtype,
                initializer=I.Normal(std=config.initializer_range),
                sharding=P("sharding", "mp"), attr_name="lm_head")

    def logits(self, hidden):
        if self.config.tie_word_embeddings:
            return matmul(hidden, self.model.embed_tokens.T)
        return matmul(hidden, self.lm_head)

    def forward(self, input_ids):
        return self.logits(self.model(input_ids))

    def _rope_cache(self):
        return None                      # no rotary embedding

    def _slot_state(self, rows: int):
        c = self.config
        n = len(c.layers_of(LINEAR))
        return {
            "conv": jnp.zeros((n, rows, (c.linear_conv_kernel_dim - 1)
                               * c.conv_channels), c.dtype),
            "delta": jnp.zeros(
                (n, rows, c.linear_key_head_dim,
                 c.linear_num_value_heads * c.linear_value_head_dim),
                jnp.float32)}

    def init_decode_state(self, batch_size: int, max_length: int):
        """``generate()``'s state: the attention layers' contiguous cache
        and the linear layers' rows."""
        c = self.config
        return {"attn": jnp.zeros(
            (len(c.layers_of(FULL)), 2, batch_size, max_length,
             c.num_key_value_heads, c.head_dim), c.dtype),
            **self._slot_state(batch_size)}

    def init_serving_cache(self, num_slots: int, num_blocks: int,
                           block_len: int):
        """The serving engine's cache for ``num_slots`` state rows and a
        pool of ``num_blocks`` blocks: the paged pool of the layers that
        hold K/V, and the window and the matrix state a slot."""
        from ..serving.kv_cache import init_paged_kv_cache
        c = self.config
        return {"attn": init_paged_kv_cache(
            c, num_blocks, block_len, num_layers=len(c.layers_of(FULL))),
            **self._slot_state(num_slots)}

    @property
    def serving_traits(self) -> ServingTraits:
        return ServingTraits(
            slot_state=("conv", "delta"),
            init_serving_cache=self.init_serving_cache,
            kernel_specs=functools.partial(gated_delta_kernel_specs,
                                           self.config),
            unsupported=_UNSUPPORTED)


def gated_delta_kernel_specs(config, token_rows):
    """Pre-flight specs of the kernels only this model's step programs
    build (``ServingTraits.kernel_specs``): per pass of ``token_rows``
    tokens, the step kernel over that many rows (its grid's bound; the
    specs do not say which of a pass's tokens are decode rows) and the
    chunk kernel over a chunk of as many, 256 at most."""
    from ..static_analysis import gated_delta_specs
    c = config
    return [spec for rows in token_rows for spec in gated_delta_specs(
        len(c.layers_of(LINEAR)), c.linear_num_value_heads,
        c.linear_key_head_dim, c.linear_value_head_dim, rows=rows,
        chunk=min(rows, 256))]
