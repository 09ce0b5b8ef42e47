"""Operations and bytes the AFMoE kernels need, from shapes and from what
the program's counters say was routed — the benchmark's own count, beside
``flops_bytes.py`` (a FLOP is one multiply or one add).
"""

import jax.numpy as jnp

SLIDING = "sliding_attention"


def _itemsize(cfg):
    return jnp.dtype(cfg["dtype"]).itemsize


def grouped_product(cfg, pairs, experts_touched):
    """The three grouped matrix products of the held experts' SwiGLU, over
    any number of expert-layer calls.  ``pairs``: (token, expert) pairs
    routed to held experts; ``experts_touched``: held experts with at least
    one pair, summed over the calls (an expert no pair chose is not read).
    Returns (flops, bytes): 2 * H * Fm a pair and projection, and the
    touched experts' three matrices read once plus each projection's rows
    in and out."""
    h, fm = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = _itemsize(cfg)
    flops = 3 * 2.0 * h * fm * pairs
    weight_bytes = 3.0 * h * fm * item * experts_touched
    row_bytes = 3.0 * (h + fm) * item * pairs
    return flops, weight_bytes + row_bytes


def window_layers(cfg):
    """(window layers, global layers) of the configuration as it is run."""
    n = sum(1 for t in cfg["layer_types"] if t == SLIDING)
    return n, len(cfg["layer_types"]) - n


def decode_rows_attention(cfg, rows, depth_total, window_dead):
    """Cached attention of one tick's decode rows over EVERY layer, one
    query a row.  ``depth_total``: the rows' cached positions, summed;
    ``window_dead``: the (position, window layer) pairs that lie behind
    their layer's window, summed over rows and window layers — so a window
    layer reads ``min(depth, window)`` positions a row and a global layer
    all of them.  Returns (flops, bytes): QK^T and PV over the positions
    read, and their K and V once plus q in and the output out."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    n_win, n_glob = window_layers(cfg)
    item = _itemsize(cfg)
    read = (n_win + n_glob) * depth_total - window_dead
    flops = 4.0 * nh * hd * read
    kv_bytes = 2.0 * nkv * hd * item * read
    io_bytes = 2.0 * nh * hd * item * rows * (n_win + n_glob)
    return flops, kv_bytes + io_bytes
