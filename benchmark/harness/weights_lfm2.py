"""Weights of the LFM2-MoE architecture from the seed, under the
reference's names (``benchmark/reference/lfm2_arch.py``), in the type they
are served in.  The program is given these arrays through its
``set_state_dict``; the reference reads the same arrays, so neither takes
anything the other has made.

Matrices are N(0, ``initializer_range``^2) (0.02 in the configuration's
file; the tiny test configuration widens it, because at a width of 64 a
0.02 matrix passes a sixth of its input on and the tied head then reads the
input token's own embedding and nothing else); norm weights (the two a
layer, the final one, the per-head q and k norms) are 1 + 0.1 N(0, 1), so a norm left out or
applied twice shows in the comparison; the router's selection bias is
N(0, BIAS_STD^2) in float32 and NOT left at zero (assumed: the checkpoint's
values are not in the config), so that selecting with the bias and weighing
without it can fail a comparison; the convolution's taps are N(0,
FILTER_STD^2).  ``head_dim`` is ``hidden_size / num_attention_heads``; the
head is the embedding (tied), so there is no ``head`` array.
"""

import jax
import jax.numpy as jnp

from benchmark.harness import weights

# 32 router logits of N(0, 0.9^2) (0.02 * sqrt(2048)): the top 4 start at a
# score of 0.74, where the 32 scores lie about 0.026 apart, so a bias of
# 0.005 changes the top 4 of about one token in four and an expert's load by
# about 5 %.
BIAS_STD = 0.005
# A filter of L taps of variance 1/L has unit gain on white input: each tap
# carries 1/L of the mixer's output variance, so a program that lost the
# L - 1 carried inputs loses (L - 1)/L of it.  At 0.02 the mixers would add
# a fiftieth of that to the residual stream and nothing the convolution
# does, right or wrong, would reach the logits.
FILTER_STD = 3 ** -0.5


def weight_shapes(cfg):
    """{reference name: shape} of one configuration, in groups that are
    folded into the seed's key together: {"top": {...}, "layers.<i>":
    {...}}.  ``num_experts`` is the number HELD; the router keeps
    ``num_experts_routed`` outputs."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    routed = cfg.get("num_experts_routed", held)
    groups = {"top": {"embed": (v, h), "norm": (h,)}}
    for i, kind in enumerate(cfg["layer_types"]):
        g = {"op_norm": (h,), "ffn_norm": (h,)}
        if kind == "conv":
            g.update({"conv_in": (h, 3 * h),
                      "conv_filter": (cfg["conv_L_cache"], h),
                      "conv_out": (h, h)})
        else:
            g.update({"q_norm": (hd,), "k_norm": (hd,), "q": (h, nh * hd),
                      "k": (h, nkv * hd), "v": (h, nkv * hd),
                      "o": (nh * hd, h)})
        if i < cfg["num_dense_layers"]:
            g.update({"gate": (h, f), "up": (h, f), "down": (f, h)})
        else:
            g.update({"router": (h, routed), "router_bias": (routed,),
                      "experts_gate": (held, h, fm),
                      "experts_up": (held, h, fm),
                      "experts_down": (held, fm, h)})
        groups[f"layers.{i}"] = g
    return groups


def reference_names(cfg):
    """Every reference name of one configuration, flat."""
    return [("" if group == "top" else group + ".") + n
            for group, shapes in weight_shapes(cfg).items() for n in shapes]


def program_name(name):
    """The program's ``state_dict`` key of one reference name."""
    top = {"embed": "model.embed_tokens",
           "norm": "model.embedding_norm.weight"}
    if name in top:
        return top[name]
    _, i, leaf = name.split(".")
    sub = {"op_norm": "operator_norm.weight", "ffn_norm": "ffn_norm.weight",
           "conv_in": "conv.in_proj", "conv_filter": "conv.conv",
           "conv_out": "conv.out_proj",
           "q_norm": "self_attn.q_layernorm.weight",
           "k_norm": "self_attn.k_layernorm.weight",
           "q": "self_attn.q_proj", "k": "self_attn.k_proj",
           "v": "self_attn.v_proj", "o": "self_attn.out_proj",
           "gate": "feed_forward.gate_proj", "up": "feed_forward.up_proj",
           "down": "feed_forward.down_proj",
           "router": "feed_forward.gate.weight",
           "router_bias": "feed_forward.gate.expert_bias",
           "experts_gate": "feed_forward.experts.gate_proj",
           "experts_up": "feed_forward.experts.up_proj",
           "experts_down": "feed_forward.experts.down_proj"}[leaf]
    return f"model.layers.{i}.{sub}"


def _make_one(key, shape, kind, dtype, std):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return BIAS_STD * z
    if kind == "filter":
        return (FILTER_STD * z).astype(dtype)
    if kind == "matrix":
        return (std * z).astype(dtype)
    return (1.0 + 0.1 * z).astype(dtype)


_make_one = jax.jit(_make_one, static_argnums=(1, 2, 3, 4))


def make_weights(cfg, seed, dtype):
    """{reference name: device array}, the same for the same
    ``(cfg, seed, dtype)``.  One jitted call an array, each waited for: a
    stack of 16 experts is 0.23 GB in float32 before it is cast, and calls
    left in flight hold their temporaries side by side."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype).name
    std = float(cfg.get("initializer_range", 0.02))
    made = {}
    for g, (group, shapes) in enumerate(sorted(weight_shapes(cfg).items())):
        pre = "" if group == "top" else group + "."
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            kind = ("bias" if name == "router_bias"
                    else "filter" if name == "conv_filter"
                    else "norm" if len(shape) == 1 else "matrix")
            made[pre + name] = _make_one(
                jax.random.fold_in(jax.random.fold_in(key, g), i), shape,
                kind, dtype, std).block_until_ready()
    return made
