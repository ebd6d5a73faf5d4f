"""Operations and bytes that the ALGORITHM of the window-and-full-
attention / softmax-routed-experts block needs, as functions of the
configuration's shapes (``weights_swa_moe.sizes``, ``layer_types``) and
of the program's integer counters (assignments, experts hit) — never
of what one implementation happens to move.  The companion of
``flops.py`` and ``flops_glm_dsa_moe.py``.

Counted: every projection once a token, attention over the keys a
query SEES on each layer (the last ``sliding_window`` on a sliding
layer, all on a full one), the router, one expert per assignment, the
head where a token is sampled.  Every expert is held, so every
assignment is local.
"""

from benchmarks.flops import ITEMSIZE, roofline_seconds  # noqa: F401
from benchmarks.weights_swa_moe import sizes


def windows(model):
    """Each layer's window, 0 = every earlier key."""
    return [model["sliding_window"] if t == "sliding_attention" else 0
            for t in model["layer_types"]]


def attention_params(model):
    s = sizes(model)
    return s["d"] * s["dh"] * (2 * s["h"] + 2 * s["hkv"])


def expert_params(model):
    """One expert: three matrices."""
    s = sizes(model)
    return 3 * s["d"] * s["fe"]


def token_params(model):
    """Matmul parameters every token multiplies, over all layers:
    attention and the router (experts are counted by assignment)."""
    s = sizes(model)
    return s["layers"] * (attention_params(model) + s["d"] * s["experts"])


def head_params(model):
    s = sizes(model)
    return s["d"] * s["v"]


def seen_keys(position, window):
    """Keys the query at 0-based ``position`` sees on a layer of
    ``window``."""
    return min(position + 1, window) if window else position + 1


def seen_pairs(seq_len, window, start=0):
    """Sum over queries ``start .. seq_len-1`` of the keys seen."""
    def upto(n):  # queries 0..n-1
        m = min(n, window) if window else n
        return m * (m + 1) // 2 + (n - m) * window
    return upto(seq_len) - upto(start)


def forward_flops(model, seq_len, start=0, sampled=1):
    """Forward operations of positions ``start .. seq_len-1`` of ONE
    sequence; ``sampled`` of them go through the head."""
    s = sizes(model)
    tokens = seq_len - start
    dense = 2 * token_params(model) * tokens
    routed = 2 * expert_params(model) * tokens * s["k"] * s["layers"]
    attend = 4 * s["h"] * s["dh"] * sum(
        seen_pairs(seq_len, w, start) for w in windows(model))
    return dense + routed + attend + 2 * head_params(model) * sampled


def weight_bytes(model, experts_hit, dtype="bfloat16"):
    """Bytes of the weights ONE decode step reads: everything outside
    the experts once (norm scales included), and the three matrices of
    every expert some row was routed to — ``experts_hit``, summed over
    the layers."""
    s = sizes(model)
    norms = (2 * s["layers"] + 1) * s["d"] + 2 * s["layers"] * s["dh"]
    return ITEMSIZE[dtype] * (
        token_params(model) + head_params(model) + norms
        + experts_hit * expert_params(model))


def kv_row_bytes(model, cache_dtype="bfloat16"):
    """One position's key and value on one layer."""
    s = sizes(model)
    return 2 * s["hkv"] * s["dh"] * ITEMSIZE[cache_dtype]


def seen_total(model, positions):
    """Keys seen by the queries at the 0-based ``positions``, summed
    over the layers."""
    return sum(seen_keys(p, w) for p in positions for w in windows(model))


def bank_attention_work(model, positions, dtype="bfloat16",
                        cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step's attention proper, all
    layers, over sequences whose new token sits at the 0-based
    ``positions``: a score and a weighted sum a head and seen key; the
    key and value of every seen position read once a layer (rings and
    whole banks alike), each slot's queries in and context out."""
    s = sizes(model)
    seen = seen_total(model, positions)
    flops = 4 * s["h"] * s["dh"] * seen
    nbytes = (kv_row_bytes(model, cache_dtype) * seen
              + ITEMSIZE[dtype] * 2 * len(positions) * s["h"] * s["dh"]
              * s["layers"])
    return flops, nbytes


def grouped_matmul_work(model, rows, experts_hit, dtype="bfloat16"):
    """``(flops, bytes)`` of the experts' three grouped matmuls over
    ``rows`` assignments that hit ``experts_hit`` experts (both summed
    over the layers): each hit expert's matrices read once, each row in
    and out once."""
    s = sizes(model)
    flops = 2 * expert_params(model) * rows
    nbytes = ITEMSIZE[dtype] * (
        experts_hit * expert_params(model)
        + rows * (2 * s["d"] + 3 * s["fe"]))
    return flops, nbytes


def decode_step_work(model, positions, assignments, experts_hit,
                     dtype="bfloat16", cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step over sequences whose new
    token sits at the 0-based ``positions``: weights as
    :func:`weight_bytes`, the key and value of every seen position once
    a layer."""
    s = sizes(model)
    seen = seen_total(model, positions)
    flops = (2 * (token_params(model) + head_params(model)) * len(positions)
             + 2 * expert_params(model) * assignments
             + 4 * s["h"] * s["dh"] * seen)
    return flops, (weight_bytes(model, experts_hit, dtype)
                   + kv_row_bytes(model, cache_dtype) * seen)


def prefill_work(model, bucket, dtype="bfloat16", cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE prompt of ``bucket`` tokens prefilled
    (one token sampled): its operations as :func:`forward_flops`; every
    weight read once (a prompt of a thousand tokens hits every expert),
    the hidden row of every token in and out of every layer, its key
    and value written once a layer."""
    s = sizes(model)
    nbytes = (
        weight_bytes(model, s["experts"] * s["layers"], dtype)
        + bucket * s["layers"] * (
            2 * s["d"] * ITEMSIZE[dtype] + kv_row_bytes(model, cache_dtype)))
    return forward_flops(model, bucket), nbytes
