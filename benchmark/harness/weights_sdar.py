"""Weights of the SDAR-MoE architecture from the seed, under the
reference's names (``benchmark/reference/sdar_arch.py``), in the type they
are served in.  The program is given these arrays through its
``set_state_dict``; the reference reads the same arrays, so neither takes
anything the other has made.

Matrices are N(0, ``initializer_range``^2) (0.02 in the configuration's
file; the tiny test configuration widens it, because at a width of 64 a
0.02 matrix passes a sixth of its input on); norm weights (the two a layer,
the final one, the per-head q and k norms) are 1 + 0.1 N(0, 1), so a norm
left out or applied twice shows in the comparison.  The router has no bias.
The head is its own array (untied).
"""

import jax
import jax.numpy as jnp

from benchmark.harness import weights


def weight_shapes(cfg):
    """{reference name: shape} of one configuration, in groups that are
    folded into the seed's key together: {"top": {...}, "layers.<i>":
    {...}}.  ``num_experts`` is the number HELD; the router keeps
    ``num_experts_routed`` outputs."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    fm, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    routed = cfg.get("num_experts_routed", held)
    groups = {"top": {"embed": (v, h), "norm": (h,), "head": (h, v)}}
    for i in range(cfg["num_hidden_layers"]):
        groups[f"layers.{i}"] = {
            "in_norm": (h,), "post_norm": (h,), "q_norm": (hd,),
            "k_norm": (hd,), "q": (h, nh * hd), "k": (h, nkv * hd),
            "v": (h, nkv * hd), "o": (nh * hd, h), "router": (h, routed),
            "experts_gate": (held, h, fm), "experts_up": (held, h, fm),
            "experts_down": (held, fm, h)}
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
           "post_norm": "post_attention_layernorm.weight",
           "q_norm": "self_attn.q_norm.weight",
           "k_norm": "self_attn.k_norm.weight",
           "q": "self_attn.q_proj", "k": "self_attn.k_proj",
           "v": "self_attn.v_proj", "o": "self_attn.o_proj",
           "router": "mlp.router.weight",
           "experts_gate": "mlp.experts.gate_proj",
           "experts_up": "mlp.experts.up_proj",
           "experts_down": "mlp.experts.down_proj"}[leaf]
    return f"model.layers.{i}.{sub}"


def _make_one(key, shape, dtype, std):
    z = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return (1.0 + 0.1 * z).astype(dtype)
    return (std * z).astype(dtype)


_make_one = jax.jit(_make_one, static_argnums=(1, 2, 3))


def make_weights(cfg, seed, dtype):
    """{reference name: device array}, the same for the same
    ``(cfg, seed, dtype)``.  One jitted call an array, each waited for: the
    embedding is 1.2 GB in float32 before it is cast, and calls left in
    flight hold their temporaries side by side."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype).name
    std = float(cfg.get("initializer_range", 0.02))
    made = {}
    for g, (group, shapes) in enumerate(sorted(weight_shapes(cfg).items())):
        pre = "" if group == "top" else group + "."
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            made[pre + name] = _make_one(
                jax.random.fold_in(jax.random.fold_in(key, g), i), shape,
                dtype, std).block_until_ready()
    return made
