"""Weights from the seed: one jitted call makes every array on the device in
the type it is served in, under the reference's names
(``benchmark/reference/llama_arch.py``).  The program is given these arrays
through its ``set_state_dict``; the reference reads the same arrays, so
neither takes anything the other has made.

Matrices are N(0, 0.02^2) (the family's published ``initializer_range``);
norm weights are 1 + 0.1 N(0, 1), so a norm weight that is left out or
applied twice shows in the comparison.
"""

import functools

import jax
import jax.numpy as jnp


def weight_shapes(cfg):
    """{reference name: shape} for one llama-architecture configuration."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    shapes = {"embed": (v, h), "norm": (h,), "head": (h, v)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        shapes.update({
            p + "in_norm": (h,), p + "post_norm": (h,),
            p + "q": (h, nh * hd), p + "k": (h, nkv * hd),
            p + "v": (h, nkv * hd), p + "o": (nh * hd, h),
            p + "gate": (h, f), p + "up": (h, f), p + "down": (f, h)})
    return shapes


def program_name(name):
    """The program's ``state_dict`` key of one reference name."""
    if name == "embed":
        return "model.embed_tokens"
    if name == "norm":
        return "model.norm.weight"
    if name == "head":
        return "lm_head"
    _, i, leaf = name.split(".")
    sub = {"in_norm": "input_layernorm.weight",
           "post_norm": "post_attention_layernorm.weight",
           "q": "self_attn.q_proj", "k": "self_attn.k_proj",
           "v": "self_attn.v_proj", "o": "self_attn.o_proj",
           "gate": "mlp.gate_proj", "up": "mlp.up_proj",
           "down": "mlp.down_proj"}[leaf]
    return f"model.layers.{i}.{sub}"


def seed_key(seed):
    """A jax key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, shapes, dtype):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = 1.0 + 0.1 * z if len(shape) == 1 else 0.02 * z
        out[name] = w.astype(dtype)
    return out


def make_weights(cfg, seed, dtype):
    """{reference name: device array of ``dtype``}, the same for the same
    ``(cfg, seed, dtype)``."""
    shapes = tuple(sorted(weight_shapes(cfg).items()))
    return _make(seed_key(seed), shapes, jnp.dtype(dtype).name)
