"""Weights of the AFMoE architecture from the seed, under the reference's
names (``benchmark/reference/afmoe_arch.py``), in the type they are served
in.  The program is given these arrays through its ``set_state_dict``; the
reference reads the same arrays, so neither takes anything the other has
made.

Matrices are N(0, 0.02^2); norm weights (the four of the sandwich, the
final one, the per-head q and k norms) are 1 + 0.1 N(0, 1), so a norm left
out or applied twice shows in the comparison; the router's selection bias is
N(0, BIAS_STD^2) in float32 and NOT left at zero (assumed: the checkpoint's
values are not in the config), so that selecting with the bias and weighing
without it can fail a comparison.  Shapes use the config's ``head_dim``
(128), which is not ``hidden_size / num_attention_heads`` (64).
"""

import jax
import jax.numpy as jnp

from benchmark.harness import weights

# A checkpoint's selection bias is what aux-loss-free balancing left: it EVENS
# the load of experts whose scores are uneven.  Seeded router columns are
# statistically alike, so any bias here only un-evens it.  A router logit is
# N(0, 1.1^2) (0.02 * sqrt(3072)) and the top 4 of 256 start at a score of
# 0.915, where a score moves by 0.078 a unit of logit: a bias of 0.02 is a
# quarter of a logit's spread, gives an expert at +1 sd 1.75 times the pairs,
# changes the top 4 of three tokens in four, and moved the share of all
# pairs that falls to the 32 held experts from 0.114 to 0.134 seed by seed
# (my chip runs, PR 27; a simulation of the router alone on normal inputs
# gives the same shares seed for seed, r = 0.95-0.98) -- and with it the
# experts touched a call and the tick, by 1.1 %.  At 0.002 an expert's load
# moves by 6 %, the held share by under 0.5 %, and the bias still changes the
# top 4 of one token in eight, so leaving it out of the selection shows.
BIAS_STD = 0.002


def weight_shapes(cfg):
    """{reference name: shape} of one configuration, in groups that are
    folded into the seed's key together: {"top": {...}, "layers.<i>":
    {...}}.  ``num_experts`` is the number HELD; the router keeps
    ``num_experts_routed`` outputs."""
    h, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    routed = cfg.get("num_experts_routed", held)
    groups = {"top": {"embed": (v, h), "norm": (h,), "head": (h, v)}}
    for i in range(cfg["num_hidden_layers"]):
        g = {"in_norm": (h,), "post_attn_norm": (h,), "pre_mlp_norm": (h,),
             "post_mlp_norm": (h,), "q_norm": (hd,), "k_norm": (hd,),
             "q": (h, nh * hd), "k": (h, nkv * hd), "v": (h, nkv * hd),
             "attn_gate": (h, nh * hd), "o": (nh * hd, h)}
        if i < cfg["num_dense_layers"]:
            g.update({"gate": (h, f), "up": (h, f), "down": (f, h)})
        else:
            fs = fm * cfg["num_shared_experts"]
            g.update({
                "router": (h, routed), "router_bias": (routed,),
                "experts_gate": (held, h, fm), "experts_up": (held, h, fm),
                "experts_down": (held, fm, h),
                "shared_gate": (h, fs), "shared_up": (h, fs),
                "shared_down": (fs, h)})
        groups[f"layers.{i}"] = g
    return groups


def reference_names(cfg):
    """Every reference name of one configuration, flat."""
    return [("" if group == "top" else group + ".") + n
            for group, shapes in weight_shapes(cfg).items() for n in shapes]


def program_name(name):
    """The program's ``state_dict`` key of one reference name."""
    top = {"embed": "model.embed_tokens", "norm": "model.norm.weight",
           "head": "lm_head"}
    if name in top:
        return top[name]
    _, i, leaf = name.split(".")
    sub = {"in_norm": "input_layernorm.weight",
           "post_attn_norm": "post_attention_layernorm.weight",
           "pre_mlp_norm": "pre_mlp_layernorm.weight",
           "post_mlp_norm": "post_mlp_layernorm.weight",
           "q_norm": "self_attn.q_norm.weight",
           "k_norm": "self_attn.k_norm.weight",
           "q": "self_attn.q_proj", "k": "self_attn.k_proj",
           "v": "self_attn.v_proj", "attn_gate": "self_attn.gate_proj",
           "o": "self_attn.o_proj",
           "gate": "mlp.gate_proj", "up": "mlp.up_proj",
           "down": "mlp.down_proj",
           "router": "mlp.router.weight",
           "router_bias": "mlp.router.expert_bias",
           "experts_gate": "mlp.experts.gate_proj",
           "experts_up": "mlp.experts.up_proj",
           "experts_down": "mlp.experts.down_proj",
           "shared_gate": "mlp.shared_experts.gate_proj",
           "shared_up": "mlp.shared_experts.up_proj",
           "shared_down": "mlp.shared_experts.down_proj"}[leaf]
    return f"model.layers.{i}.{sub}"


def _make_one(key, shape, kind, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return BIAS_STD * z
    if kind == "matrix":
        return (0.02 * z).astype(dtype)
    return (1.0 + 0.1 * z).astype(dtype)


_make_one = jax.jit(_make_one, static_argnums=(1, 2, 3))


def make_weights(cfg, seed, dtype):
    """{reference name: device array}, the same for the same
    ``(cfg, seed, dtype)``.  One jitted call an array, each waited for: a
    stack of 32 experts is 1.2 GB in float32 before it is cast, and calls
    left in flight hold their temporaries side by side."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype).name
    made = {}
    for g, (group, shapes) in enumerate(sorted(weight_shapes(cfg).items())):
        pre = "" if group == "top" else group + "."
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            kind = ("bias" if name == "router_bias"
                    else "norm" if len(shape) == 1 else "matrix")
            made[pre + name] = _make_one(
                jax.random.fold_in(jax.random.fold_in(key, g), i), shape,
                kind, dtype).block_until_ready()
    return made
