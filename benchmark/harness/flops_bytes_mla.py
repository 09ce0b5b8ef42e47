"""Operations and bytes the latent-attention cell's new kernel needs, from
shapes and from what the program's spans say a tick's rows read — the
benchmark's own count, beside ``flops_bytes.py`` (a FLOP is one multiply or
one add).  The held experts' grouped product is
``flops_bytes_afmoe.grouped_product``: the same kernel on another shape.
"""

import jax.numpy as jnp

LANES = 128


def entry_values(cfg):
    """Values one position holds a layer: the latent and the RoPE key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kv_bytes_per_position(cfg):
    """One position over every layer AS THE POOL STORES IT: the entry padded
    to whole lane tiles (576 -> 640), in the served type.  What the pool's
    size and the live share are counted in; the roofline's least bytes are
    ``entry_values`` wide (below)."""
    stored = -(-entry_values(cfg) // LANES) * LANES
    return (cfg["num_hidden_layers"] * stored
            * jnp.dtype(cfg["dtype"]).itemsize)


def decode_rows_attention(cfg, distinct_positions, depth_total):
    """The least work of one tick's decode rows' latent attention over
    every layer, WHATEVER implements it.  ``distinct_positions``: the
    different cached positions the rows see (a document many rows share
    counted once: a kernel that reads it once for all of them is possible);
    ``depth_total``: the rows' depths, summed (each row's query meets every
    position it sees).  Returns (flops, bytes): per (row, position, head) a
    score over the entry's values and a value sum over the latent, 2 FLOPs
    each; and every distinct position's entry, unpadded, read once a layer.
    q in and the output out are left out (a thousandth of the entries)."""
    heads = cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    flops = (2.0 * heads * (entry_values(cfg) + cfg["kv_lora_rank"])
             * depth_total * layers)
    nbytes = (float(entry_values(cfg)) * jnp.dtype(cfg["dtype"]).itemsize
              * distinct_positions * layers)
    return flops, nbytes
