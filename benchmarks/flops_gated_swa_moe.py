"""Operations and bytes that the ALGORITHM of the gated window-and-full-
attention / softmax-routed-experts block with a shared expert needs, as
functions of the configuration's shapes (``weights_gated_swa_moe.sizes``,
``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_layer``)
and of the program's integer counters (local assignments, experts hit)
— never of what one implementation happens to move.  The companion of
``flops_swa_moe.py``.

Counted: every projection once a token with the layer's own query heads
(q, k, v, out and the gate), attention over the keys a query SEES on
each layer (the last ``sliding_window`` on a sliding layer, all on a
full one) times that layer's query heads, the dense FFN on the layers
``mlp_layer_types`` calls dense, the router over every expert of the
layer, the shared expert, one expert per LOCAL assignment (one whose
expert this chip holds: the counter where a counter exists, else the
held share of the ``num_experts_per_tok`` choices), and the head where
a token is sampled.
"""

from benchmarks.flops import ITEMSIZE, roofline_seconds  # noqa: F401
from benchmarks.flops_swa_moe import seen_keys, seen_pairs
from benchmarks.weights_gated_swa_moe import heads, layer_kinds, sizes


def windows(model):
    """Each layer's window, 0 = every earlier key."""
    return [model["sliding_window"] if t == "sliding_attention" else 0
            for t in model["layer_types"]]


def layer_heads(model):
    return [heads(model, i) for i in range(model["num_hidden_layers"])]


def sparse_layers(model):
    return sum(layer_kinds(model, i)[1] == "sparse"
               for i in range(model["num_hidden_layers"]))


def attention_params(model, layer):
    """q, k, v and out of layer ``layer``'s heads, and its gate."""
    s = sizes(model)
    h = heads(model, layer)
    return s["d"] * s["dh"] * (2 * h + 2 * s["hkv"]) + s["d"] * h


def expert_params(model):
    """One routed expert: three matrices."""
    s = sizes(model)
    return 3 * s["d"] * s["fe"]


def ffn_params(model, layer):
    """What a token multiplies in layer ``layer``'s FFN outside the
    routed experts: the dense MLP, or the router and the shared
    expert."""
    s = sizes(model)
    if layer_kinds(model, layer)[1] == "dense":
        return 3 * s["d"] * s["f"]
    return s["d"] * s["experts"] + 3 * s["d"] * s["fs"]


def token_params(model):
    """Matmul parameters every token multiplies, over all layers
    (routed experts are counted by assignment)."""
    return sum(attention_params(model, i) + ffn_params(model, i)
               for i in range(model["num_hidden_layers"]))


def head_params(model):
    s = sizes(model)
    return s["d"] * s["v"]


def local_share(model):
    """Local assignments a token makes in one sparse layer where the
    router spreads its choices evenly: the held share of ``k``."""
    s = sizes(model)
    return s["k"] * s["held"] / s["experts"]


def head_pairs(model, seq_len, start=0):
    """Query-key pairs of positions ``start .. seq_len-1`` times each
    layer's query heads, summed over the layers."""
    return sum(h * seen_pairs(seq_len, w, start)
               for h, w in zip(layer_heads(model), windows(model)))


def forward_flops(model, seq_len, start=0, sampled=1):
    """Forward operations of positions ``start .. seq_len-1`` of ONE
    sequence on this chip; ``sampled`` of them go through the head."""
    s = sizes(model)
    tokens = seq_len - start
    dense = 2 * token_params(model) * tokens
    routed = (2 * expert_params(model) * tokens * local_share(model)
              * sparse_layers(model))
    attend = 4 * s["dh"] * head_pairs(model, seq_len, start)
    return dense + routed + attend + 2 * head_params(model) * sampled


def weight_bytes(model, experts_hit, dtype="bfloat16"):
    """Bytes of the weights ONE decode step reads: everything outside
    the routed experts once (norm scales included), and the three
    matrices of every held expert some row was routed to —
    ``experts_hit``, summed over the layers."""
    s = sizes(model)
    norms = (2 * s["layers"] + 1) * s["d"] + 2 * s["layers"] * s["dh"]
    return ITEMSIZE[dtype] * (
        token_params(model) + head_params(model) + norms
        + experts_hit * expert_params(model))


def kv_row_bytes(model, cache_dtype="bfloat16"):
    """One position's key and value on one layer."""
    s = sizes(model)
    return 2 * s["hkv"] * s["dh"] * ITEMSIZE[cache_dtype]


def seen(model, positions):
    """``(keys, head_keys)``: keys seen by the queries at the 0-based
    ``positions`` summed over the layers, and the same times each
    layer's query heads."""
    keys = head_keys = 0
    for h, w in zip(layer_heads(model), windows(model)):
        n = sum(seen_keys(p, w) for p in positions)
        keys += n
        head_keys += h * n
    return keys, head_keys


def bank_attention_work(model, positions, dtype="bfloat16",
                        cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step's attention proper, all
    layers, over sequences whose new token sits at the 0-based
    ``positions``: a score and a weighted sum a head and seen key; the
    key and value of every seen position read once a layer (rings and
    whole banks alike), each slot's queries in and context out."""
    s = sizes(model)
    keys, head_keys = seen(model, positions)
    flops = 4 * s["dh"] * head_keys
    nbytes = (kv_row_bytes(model, cache_dtype) * keys
              + ITEMSIZE[dtype] * 2 * len(positions) * s["dh"]
              * sum(layer_heads(model)))
    return flops, nbytes


def decode_step_work(model, positions, assignments, experts_hit,
                     dtype="bfloat16", cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step over sequences whose new
    token sits at the 0-based ``positions``, with ``assignments`` local
    assignments that hit ``experts_hit`` held experts (both summed over
    the layers): weights as :func:`weight_bytes`, the key and value of
    every seen position once a layer."""
    s = sizes(model)
    keys, head_keys = seen(model, positions)
    flops = (2 * (token_params(model) + head_params(model)) * len(positions)
             + 2 * expert_params(model) * assignments
             + 4 * s["dh"] * head_keys)
    return flops, (weight_bytes(model, experts_hit, dtype)
                   + kv_row_bytes(model, cache_dtype) * keys)


def prompt_attention_work(model, span, query_heads, window,
                          dtype="bfloat16"):
    """``(flops, bytes)`` of ONE layer's attention over a fresh prompt
    of ``span`` rows with ``query_heads`` heads under ``window`` (0 =
    causal): a score and a weighted sum a head and visible pair; q, k,
    v read and the output written once."""
    s = sizes(model)
    flops = 4 * s["dh"] * query_heads * seen_pairs(span, window)
    nbytes = ITEMSIZE[dtype] * span * s["dh"] * (
        2 * query_heads + 2 * s["hkv"])
    return flops, nbytes
