"""Operations and bytes that the ALGORITHM needs, as functions of the
configuration's shapes and stated dtypes — never of what one
implementation happens to move.  Every roofline and ``mfu`` the
benchmark reports is computed from these.

A configuration is the dict of a file under ``configs/`` (``model``
holds the published keys of the source ``config.json``).
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def shapes(model):
    """The few sizes everything below needs, by their published names."""
    h = model["num_attention_heads"]
    return dict(
        d=model["hidden_size"], h=h,
        hkv=model.get("num_key_value_heads") or h,
        dh=model.get("head_dim") or model["hidden_size"] // h,
        f=model["intermediate_size"], v=model["vocab_size"],
        layers=model["num_hidden_layers"],
        window=model.get("sliding_window") or 0,
    )


def layer_params(model):
    """Parameters of one block: q, k, v, out projections and the gated
    MLP's three matrices (norm scales are counted apart: no matmul)."""
    s = shapes(model)
    attn = s["d"] * s["dh"] * (2 * s["h"] + 2 * s["hkv"])
    mlp = 3 * s["d"] * s["f"]
    return attn + mlp


def matmul_params(model):
    """Parameters every token multiplies: all blocks and the output
    head (the input embedding is a lookup, no operation)."""
    s = shapes(model)
    return s["layers"] * layer_params(model) + s["d"] * s["v"]


def total_params(model):
    """Every stored parameter: blocks, both norms of each, the final
    norm, the input embedding and the untied output head."""
    s = shapes(model)
    return (
        s["layers"] * (layer_params(model) + 2 * s["d"])
        + s["d"] + 2 * s["d"] * s["v"]
    )


def visible_keys(position, window):
    """Keys a query at 0-based ``position`` attends to: itself and what
    lies before it, inside the sliding window when there is one."""
    n = position + 1
    return min(n, window) if window else n


def attention_pairs(seq_len, window, start=0):
    """Sum over queries ``start .. seq_len-1`` of the keys each sees."""
    if not window or seq_len <= window:
        total = seq_len * (seq_len + 1) // 2 - start * (start + 1) // 2
        return total
    return sum(visible_keys(p, window) for p in range(start, seq_len))


def forward_flops(model, seq_len, start=0):
    """Forward operations of positions ``start .. seq_len-1`` of ONE
    sequence: 2 per multiply-add in the matmuls, plus the score and
    the value products over the (query, key) pairs inside the causal
    window — 4 · head_dim · heads per pair and layer."""
    s = shapes(model)
    tokens = seq_len - start
    dense = 2 * matmul_params(model) * tokens
    pairs = attention_pairs(seq_len, s["window"], start)
    attn = 4 * s["dh"] * s["h"] * s["layers"] * pairs
    return dense + attn


def train_flops(model, seq_len):
    """Forward and backward of one training sequence: the backward
    costs twice the forward; nothing recomputed is counted."""
    return 3 * forward_flops(model, seq_len)


def flash_flops(model, seq_len, batch, backward):
    """Operations of the attention kernel alone over ``batch``
    sequences of ONE layer, per (query, key) pair inside the causal
    window and per head: the score and the value product forward
    (4 · head_dim) and, with ``backward``, the four products the
    gradient needs — dv, dp, dq, dk (8 · head_dim).  The score that a
    flash backward computes again is recomputation and is not counted."""
    s = shapes(model)
    pairs = attention_pairs(seq_len, s["window"]) * batch * s["h"]
    per_pair = 4 * s["dh"] + (8 * s["dh"] if backward else 0)
    return per_pair * pairs


def flash_bytes(model, seq_len, batch, backward, dtype="bfloat16"):
    """Least bytes the attention of one layer moves: q, k, v read and
    the output written once; the backward reads them, the output and
    its cotangent, and writes dq, dk, dv."""
    s = shapes(model)
    b = ITEMSIZE[dtype]
    q = batch * seq_len * s["h"] * s["dh"] * b
    kv = batch * seq_len * s["hkv"] * s["dh"] * b
    fwd = 2 * q + 2 * kv
    if not backward:
        return fwd
    return fwd + (3 * q + 2 * kv) + (q + 2 * kv)


def kv_bytes_per_token(model, cache_dtype="bfloat16"):
    """Key and value of one token over all layers."""
    s = shapes(model)
    return 2 * s["layers"] * s["hkv"] * s["dh"] * ITEMSIZE[cache_dtype]


def weight_bytes(model, dtype="bfloat16"):
    """Bytes of the weights a decode step reads: every matmul parameter
    once, and the norm scales (the embedding rows read are negligible
    and counted as one row a sequence)."""
    s = shapes(model)
    norms = (2 * s["layers"] + 1) * s["d"]
    return (matmul_params(model) + norms) * ITEMSIZE[dtype]


def decode_step_work(model, positions, dtype="bfloat16",
                     cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step over sequences whose new
    token sits at the 0-based ``positions``: weights read once, each
    sequence's live keys and values (inside the window) read once, one
    embedding row a sequence; operations as in :func:`forward_flops`
    for one token each."""
    s = shapes(model)
    live = sum(visible_keys(p, s["window"]) for p in positions)
    n = len(positions)
    flops = 2 * matmul_params(model) * n + (
        4 * s["dh"] * s["h"] * s["layers"] * live
    )
    nbytes = (
        weight_bytes(model, dtype)
        + live * kv_bytes_per_token(model, cache_dtype)
        + n * s["d"] * ITEMSIZE[dtype]
    )
    return flops, nbytes


def roofline_seconds(flops, nbytes, peaks, dtype="bfloat16"):
    """Least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["flops_per_s"][dtype]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
