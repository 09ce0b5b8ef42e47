"""Operations and bytes the algorithm needs, from shapes — the benchmark's
own count, so that a PR that changes a kernel cannot change its yardstick.
A FLOP is one multiply or one add (a multiply-accumulate is 2).
"""


def _dims(cfg):
    h = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h, nh, nkv, h // nh, cfg["intermediate_size"], cfg["vocab_size"]


def layer_matmul_params(cfg):
    """Parameters of one decoder layer's seven matrices."""
    h, nh, nkv, hd, f, _ = _dims(cfg)
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h + 3 * h * f


def matmul_params(cfg):
    """Parameters every token multiplies with: the layers and the head (the
    embedding is a lookup)."""
    h, _, _, _, _, v = _dims(cfg)
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + h * v


def cached_attention(cfg, qk_pairs, kv_tokens, query_tokens, kv_itemsize=2,
                     act_itemsize=2):
    """One layer's attention over a cache.  ``qk_pairs``: the sum over query
    tokens of the cached positions each attends; ``kv_tokens``: cached
    positions read, each row's counted once; ``query_tokens``: query tokens.
    Plain decode has one query a row, so both sums are the rows' depths; a
    prefill chunk of c tokens at depth m has c*m + c*(c+1)/2 pairs over
    m + c positions.  Returns (flops, bytes): QK^T and PV, and K and V read
    once plus q in and the output out."""
    _, nh, nkv, hd, _, _ = _dims(cfg)
    flops = 4.0 * nh * hd * qk_pairs
    kv_bytes = 2.0 * kv_tokens * nkv * hd * kv_itemsize
    io_bytes = 2.0 * query_tokens * nh * hd * act_itemsize
    return flops, kv_bytes + io_bytes


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def train_token_flops(cfg, seq_len):
    """Forward plus backward FLOPs one trained token requires at sequence
    length ``seq_len`` (recomputation not counted): 6 per matmul parameter,
    plus causal attention — 2 * seq * hidden a layer forward (QK^T and PV
    over the mean causal depth seq/2), three times that with the backward
    pass."""
    h = cfg["hidden_size"]
    attn = 3 * 2 * seq_len * h * cfg["num_hidden_layers"]
    return 6 * matmul_params(cfg) + attn
