"""Operations and bytes the Olmo-Hybrid cell's kernels need, from shapes —
the benchmark's own count, beside ``flops_bytes.py`` (a FLOP is one
multiply or one add).  None of it depends on how a kernel is written: the
state is counted UNPADDED and read and written once, the chunked form by
the products its mathematics has.  The attention layers' K/V count is
``flops_bytes_lfm2``'s (from ``layer_types``, heads and hidden size
alone)."""

import jax.numpy as jnp

LINEAR = "linear_attention"
SUB_CHUNK = 64          # the chunked form's sub-chunk, part of the mathematics


def linear_layers(cfg):
    return sum(1 for t in cfg["layer_types"] if t == LINEAR)


def _heads(cfg):
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def state_bytes_per_slot(cfg):
    """One request's fixed-size state over the linear layers: the matrix
    ``S`` (d_k x d_v a head, float32) and the convolution's window (the
    last L - 1 inputs of its channels, in the served type)."""
    h, dk, dv = _heads(cfg)
    n = linear_layers(cfg)
    window = ((cfg["linear_conv_kernel_dim"] - 1) * h * (2 * dk + dv)
              * jnp.dtype(cfg["dtype"]).itemsize)
    return n * (h * dk * dv * 4 + window)


def delta_state_bytes(cfg):
    """``S`` of one request and ONE layer, float32, unpadded."""
    h, dk, dv = _heads(cfg)
    return h * dk * dv * 4


def gated_delta_step(cfg, rows):
    """The one-token step of ``rows`` occupied rows over every LINEAR
    layer.  Returns (flops, bytes): a row and layer decays ``S`` (1 a
    value), takes ``S~^T k`` (2), adds the rank-1 update (2) and takes
    ``S^T q`` (2): 7 a value of ``S``; reads and writes ``S`` once, reads
    q, k, v, beta, g and writes o, float32."""
    h, dk, dv = _heads(cfg)
    n = linear_layers(cfg)
    flops = 7.0 * h * dk * dv * rows * n
    io = 4.0 * h * (2 * dk + 2 * dv + 2) * rows * n
    return flops, 2.0 * delta_state_bytes(cfg) * rows * n + io


def gated_delta_chunk(cfg, tokens):
    """The chunked form over one row's ``tokens`` real tokens (whole
    sub-chunks of SUB_CHUNK) and every LINEAR layer, the part that walks the
    carried state — what the chunk kernel is: a sub-chunk and head, with c
    = SUB_CHUNK, takes ``W S`` and ``(q e^g) S`` (2 c d_k d_v each), ``P
    V'`` (2 c c d_v) and ``(k e^g)^T V'`` (2 c d_k d_v).  Returns (flops,
    bytes): those products counted ONCE (the chip's peak is a bfloat16
    pass's; a float32 product at full precision takes six, which the count
    leaves out: it is how the products are made, not what they are), and
    ``S`` read and written once a layer plus the operands in (W, q e^g, k
    e^g: d_k wide; P: c wide; U: d_v) and o out, float32."""
    h, dk, dv = _heads(cfg)
    n = linear_layers(cfg)
    c = SUB_CHUNK
    subs = -(-tokens // c)
    products = subs * h * (6.0 * c * dk * dv + 2.0 * c * c * dv)
    io = 4.0 * subs * h * c * (3 * dk + c + 2 * dv)
    return products * n, (2.0 * delta_state_bytes(cfg) + io) * n
