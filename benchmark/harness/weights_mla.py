"""Weights of the latent-attention MoE architecture from the seed, under the
reference's names (``benchmark/reference/mla_arch.py``), in the type they
are served in.  The program is given these arrays through its
``set_state_dict``; the reference reads the same arrays, so neither takes
anything the other has made.

Matrices are N(0, 0.02^2); norm weights (two a layer, the final one, and
the three latent norms' — the query latent's and the key/value latent's)
are 1 + 0.1 N(0, 1), so a norm left out or applied twice shows in the
comparison; the router's selection bias is N(0, BIAS_STD^2) in float32 and
NOT left at zero, so that selecting with the bias and weighing without it
can fail a comparison, and small for ``weights_afmoe.py``'s reason: seeded
router columns are alike, so a bias can only un-even their load.
"""

import jax
import jax.numpy as jnp

from benchmark.harness import weights

BIAS_STD = 0.002


def weight_shapes(cfg):
    """{reference name: shape} of one configuration, in groups that are
    folded into the seed's key together: {"top": {...}, "layers.<i>":
    {...}}.  ``n_routed_experts`` is the number HELD; the router keeps
    ``n_experts_routed`` outputs."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh = cfg["num_attention_heads"]
    n, r, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    ql, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    routed = cfg.get("n_experts_routed", held)
    groups = {"top": {"embed": (v, h), "norm": (h,), "head": (h, v)}}
    for i in range(cfg["num_hidden_layers"]):
        g = {"in_norm": (h,), "post_norm": (h,),
             "q_a": (h, ql), "q_a_norm": (ql,), "q_b": (ql, nh * (n + r)),
             "kv_a": (h, c + r), "kv_a_norm": (c,),
             "kv_b": (c, nh * (n + dv)), "o": (nh * dv, h)}
        if i < cfg["first_k_dense_replace"]:
            g.update({"gate": (h, f), "up": (h, f), "down": (f, h)})
        else:
            fs = fm * cfg["n_shared_experts"]
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
           "post_norm": "post_attention_layernorm.weight",
           "q_a": "self_attn.q_a_proj",
           "q_a_norm": "self_attn.q_a_layernorm.weight",
           "q_b": "self_attn.q_b_proj",
           "kv_a": "self_attn.kv_a_proj_with_mqa",
           "kv_a_norm": "self_attn.kv_a_layernorm.weight",
           "kv_b": "self_attn.kv_b_proj", "o": "self_attn.o_proj",
           "gate": "mlp.gate_proj", "up": "mlp.up_proj",
           "down": "mlp.down_proj",
           "router": "mlp.gate.weight",
           "router_bias": "mlp.gate.expert_bias",
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
    ``(cfg, seed, dtype)``.  One jitted call an array, each waited for (a
    stack of experts in float32 before its cast, and calls left in flight,
    hold their temporaries side by side)."""
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
