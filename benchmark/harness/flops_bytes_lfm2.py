"""Operations and bytes the LFM2-MoE cell's kernels need, from shapes —
the benchmark's own count, beside ``flops_bytes.py`` (a FLOP is one
multiply or one add).  The held experts' grouped product is
``flops_bytes_afmoe.grouped_product``: the same kernel on another shape.
"""

import jax.numpy as jnp

FULL = "full_attention"


def kv_layers(cfg):
    """The layers that hold K and V: the ``full_attention`` ones (the
    others mix tokens by a short convolution and keep a fixed-size state)."""
    return sum(1 for t in cfg["layer_types"] if t == FULL)


def head_dim(cfg):
    """The catalog gives no ``head_dim``: hidden over the query heads."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_bytes_per_position(cfg):
    """K and V of one token over the K/V layers, in the served type."""
    return (2 * kv_layers(cfg) * cfg["num_key_value_heads"] * head_dim(cfg)
            * jnp.dtype(cfg["dtype"]).itemsize)


def decode_rows_attention(cfg, rows, depth_total):
    """Cached attention of one tick's decode rows over every K/V LAYER, one
    query a row.  ``depth_total``: the rows' cached positions, summed.
    Returns (flops, bytes): QK^T and PV over the positions read, and their K
    and V once a K/V layer plus q in and the output out."""
    q_width = cfg["num_attention_heads"] * head_dim(cfg)
    n = kv_layers(cfg)
    flops = 4.0 * q_width * depth_total * n
    io_bytes = 2.0 * q_width * jnp.dtype(cfg["dtype"]).itemsize * rows * n
    return flops, float(kv_bytes_per_position(cfg)) * depth_total + io_bytes


def state_bytes_per_slot(cfg):
    """The convolution layers' carried inputs of one request: L - 1 rows of
    ``hidden_size`` a layer, in the served type."""
    conv = len(cfg["layer_types"]) - kv_layers(cfg)
    return (conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]
            * jnp.dtype(cfg["dtype"]).itemsize)
