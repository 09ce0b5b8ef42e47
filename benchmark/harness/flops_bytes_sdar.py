"""Operations and bytes the SDAR-MoE cell's kernels need, from shapes — the
benchmark's own count, beside ``flops_bytes.py`` (a FLOP is one multiply or
one add).  The held experts' grouped product is
``flops_bytes_afmoe.grouped_product``: the same kernel on another shape.
"""

import jax.numpy as jnp


def kv_bytes_per_position(cfg):
    """K and V of one token over every layer, in the served type."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * jnp.dtype(cfg["dtype"]).itemsize)


def block_rows_attention(cfg, rows, depth_total):
    """Cached attention of one tick's block rows over every layer: a block
    of ``block_length`` queries a row, each seeing the row's committed
    positions and the block whole.  ``depth_total``: the rows' committed
    positions, summed.  Returns (flops, bytes): QK^T and PV of every query
    over the keys it sees, and the K and V of the rows' depths plus the
    block ONCE a layer (the block's queries share one read), q in and the
    output out.  Memory-bound."""
    b = int(cfg["block_length"])
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    keys = depth_total + b * rows               # read once a layer
    flops = 4.0 * q_width * b * keys * layers
    io_bytes = (2.0 * q_width * b * rows * layers
                * jnp.dtype(cfg["dtype"]).itemsize)
    return flops, float(kv_bytes_per_position(cfg)) * keys + io_bytes
