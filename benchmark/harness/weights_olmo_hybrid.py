"""Weights of the Olmo-Hybrid architecture from the seed, under the
reference's names (``benchmark/reference/olmo_hybrid_arch.py``), in the type
they are served in.  The program is given these arrays through its
``set_state_dict``; the reference reads the same arrays, so neither takes
anything the other has made.

Matrices are N(0, ``initializer_range``^2) (0.02 in the configuration's
file); norm weights (the two a layer, the final one, the q and k norms over
the whole projection, the gated norm of 192) are 1 + 0.1 N(0, 1), so a norm
left out or applied twice shows in the comparison; the convolution's taps
are N(0, FILTER_STD^2): four taps of variance 1/4 have unit gain on white
input, each carries a quarter of what the convolution passes on, and a
program that lost the three carried inputs loses three quarters of it (as
LFM2's N(0, 1/3) a tap).  The decay's two per-head parameters follow the
layer's published initialisation and are float32: ``A = exp(A_log)``
uniform in [1, 16] and ``dt = softplus(dt_bias)`` log-uniform in [0.001,
0.1], so a head's decay ``exp(-A dt)`` lies between about 0.2 and 0.999 a
token: heads both forget and remember.  ``head_dim`` is ``hidden_size /
num_attention_heads``; the head is untied.
"""

import jax
import jax.numpy as jnp

from benchmark.harness import weights

FILTER_STD = 0.5
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
LINEAR, FULL = "linear_attention", "full_attention"


def weight_shapes(cfg):
    """{reference name: shape} of one configuration, in groups that are
    folded into the seed's key together: {"top": {...}, "layers.<i>":
    {...}}."""
    h, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    hl, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    chan = hl * (2 * dk + dv)
    groups = {"top": {"embed": (v, h), "norm": (h,), "head": (h, v)}}
    for i, kind in enumerate(cfg["layer_types"]):
        g = {"mixer_norm": (h,), "mlp_norm": (h,), "gate": (h, f),
             "up": (h, f), "down": (f, h)}
        if kind == FULL:
            g.update({"q": (h, nh * hd), "k": (h, nkv * hd),
                      "v": (h, nkv * hd), "o": (nh * hd, h),
                      "q_norm": (nh * hd,), "k_norm": (nkv * hd,)})
        else:
            g.update({"in": (h, chan),
                      "conv": (cfg["linear_conv_kernel_dim"], chan),
                      "g": (h, hl * dv), "a": (h, hl), "b": (h, hl),
                      "A_log": (hl,), "dt_bias": (hl,), "o_norm": (dv,),
                      "out": (hl * dv, h)})
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
    sub = {"mixer_norm": "mixer_norm.weight", "mlp_norm": "mlp_norm.weight",
           "gate": "mlp.gate_proj", "up": "mlp.up_proj",
           "down": "mlp.down_proj",
           "q": "mixer.q_proj", "k": "mixer.k_proj", "v": "mixer.v_proj",
           "o": "mixer.o_proj", "q_norm": "mixer.q_norm.weight",
           "k_norm": "mixer.k_norm.weight",
           "in": "mixer.in_proj", "conv": "mixer.conv",
           "g": "mixer.gate_proj", "a": "mixer.a_proj", "b": "mixer.b_proj",
           "A_log": "mixer.A_log", "dt_bias": "mixer.dt_bias",
           "o_norm": "mixer.o_norm.weight", "out": "mixer.out_proj"}[leaf]
    return f"model.layers.{i}.{sub}"


def _make_one(key, shape, kind, dtype, std):
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    if kind == "dt_bias":
        lo, hi = (jnp.log(x) for x in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1(dt)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "filter":
        return (FILTER_STD * z).astype(dtype)
    if kind == "matrix":
        return (std * z).astype(dtype)
    return (1.0 + 0.1 * z).astype(dtype)


_make_one = jax.jit(_make_one, static_argnums=(1, 2, 3, 4))


def make_weights(cfg, seed, dtype):
    """{reference name: device array}, the same for the same
    ``(cfg, seed, dtype)``.  One jitted call an array, each waited for: the
    embedding is 1.5 GB in float32 before it is cast, and calls left in
    flight hold their temporaries side by side."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype).name
    std = float(cfg.get("initializer_range", 0.02))
    made = {}
    for g, (group, shapes) in enumerate(sorted(weight_shapes(cfg).items())):
        pre = "" if group == "top" else group + "."
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            kind = (name if name in ("A_log", "dt_bias")
                    else "filter" if name == "conv"
                    else "norm" if len(shape) == 1 else "matrix")
            made[pre + name] = _make_one(
                jax.random.fold_in(jax.random.fold_in(key, g), i), shape,
                kind, dtype, std).block_until_ready()
    return made
