"""Operations and bytes that the ALGORITHM of the latent-attention /
sparse-index / sigmoid-routed-experts block needs, as functions of the
configuration's shapes (``weights_glm_dsa_moe.sizes``) and of the
program's integer counters (how many routed assignments landed on the
experts held here, how many held experts were hit) — never of what one
implementation happens to move.  The companion of ``flops.py``.

Counted: every projection once a token (the key/value expansion
``W_kvb`` among them: a token's keys are expanded once, whoever
attends to them), attention in its cheaper, non-absorbed form over the
keys a query really SELECTS, the index's dot products over every
visible key on the layers that own an index, the shared expert, one
routed expert per assignment that is local, the head over the
vocabulary slice where a token is sampled.
"""

from benchmarks.flops import ITEMSIZE, roofline_seconds  # noqa: F401
from benchmarks.weights_glm_dsa_moe import layer_kinds, sizes


def attention_params(model, indexer):
    """Matmul parameters of one layer's attention, with the index's
    three projections on a "full" layer."""
    s = sizes(model)
    d, h = s["d"], s["h"]
    n = (d * s["rq"] + s["rq"] * h * (s["dn"] + s["dr"])
         + d * (s["rkv"] + s["dr"]) + s["rkv"] * h * (s["dn"] + s["dv"])
         + h * s["dv"] * d)
    if indexer == "full":
        n += s["rq"] * s["j"] * s["di"] + d * s["di"] + d * s["j"]
    return n


def expert_params(model):
    """One routed expert: three matrices."""
    s = sizes(model)
    return 3 * s["d"] * s["fe"]


def ffn_params(model, ffn):
    """What EVERY token multiplies in a layer's FFN: the dense MLP, or
    the router and the shared expert (routed experts are counted by
    assignment)."""
    s = sizes(model)
    if ffn == "dense":
        return 3 * s["d"] * s["f"]
    return s["d"] * s["experts"] + s["shared"] * expert_params(model)


def layers(model):
    return [layer_kinds(model, i) for i in range(model["num_hidden_layers"])]


def token_params(model):
    """Matmul parameters every token multiplies, over all layers."""
    return sum(attention_params(model, ix) + ffn_params(model, ffn)
               for ffn, ix in layers(model))


def head_params(model):
    s = sizes(model)
    return s["d"] * s["v"]


def sparse_layers(model):
    return sum(ffn == "sparse" for ffn, _ in layers(model))


def index_layers(model):
    return sum(ix == "full" for _, ix in layers(model))


def selected_keys(position, topk):
    """Keys the query at 0-based ``position`` attends to."""
    return min(position + 1, topk)


def selected_pairs(seq_len, topk, start=0):
    """Sum over queries ``start .. seq_len-1`` of the keys selected."""
    def upto(n):  # queries 0..n-1
        m = min(n, topk)
        return m * (m + 1) // 2 + (n - m) * topk
    return upto(seq_len) - upto(start)


def visible_pairs(seq_len, start=0):
    return seq_len * (seq_len + 1) // 2 - start * (start + 1) // 2


def expected_local_share(model):
    """Share of a token's routed assignments that land on the held
    experts under a router that favours none."""
    s = sizes(model)
    return s["held"] / s["experts"]


def forward_flops(model, seq_len, start=0, local_share=None, sampled=1):
    """Forward operations of positions ``start .. seq_len-1`` of ONE
    sequence; ``local_share`` of their routed assignments are to held
    experts (default: the expectation); ``sampled`` of the positions
    go through the head."""
    s = sizes(model)
    tokens = seq_len - start
    share = expected_local_share(model) if local_share is None else (
        local_share)
    dense = 2 * token_params(model) * tokens
    routed = (2 * expert_params(model) * tokens * s["k"] * share
              * sparse_layers(model))
    attend = (2 * s["h"] * (s["dn"] + s["dr"] + s["dv"])
              * selected_pairs(seq_len, s["topk"], start)
              * model["num_hidden_layers"])
    index = (2 * s["j"] * s["di"] * visible_pairs(seq_len, start)
             * index_layers(model))
    return dense + routed + attend + index + 2 * head_params(model) * sampled


def weight_bytes(model, experts_hit, dtype="bfloat16"):
    """Bytes of the weights ONE decode step reads: everything outside
    the routed experts once (norm scales included, one embedding row a
    sequence is negligible), and the three matrices of every held
    expert some token was routed to — ``experts_hit``, summed over the
    sparse layers."""
    s = sizes(model)
    norms = (2 * model["num_hidden_layers"] + 1) * s["d"]
    return ITEMSIZE[dtype] * (
        token_params(model) + head_params(model) + norms
        + experts_hit * expert_params(model))


def decode_step_work(model, positions, local_assignments, experts_hit,
                     dtype="bfloat16", cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step over sequences whose new
    token sits at the 0-based ``positions``: weights as
    :func:`weight_bytes`; a layer reads the latent row (``kv_lora_rank
    + qk_rope_head_dim`` wide) of each SELECTED key, an index layer the
    index key of each visible one."""
    s = sizes(model)
    n = len(positions)
    chosen = sum(selected_keys(p, s["topk"]) for p in positions)
    seen = sum(p + 1 for p in positions)
    flops = (
        2 * (token_params(model) + head_params(model)) * n
        + 2 * expert_params(model) * local_assignments
        + 2 * s["h"] * (s["dn"] + s["dr"] + s["dv"]) * chosen
        * model["num_hidden_layers"]
        + 2 * s["j"] * s["di"] * seen * index_layers(model)
    )
    cache = ITEMSIZE[cache_dtype] * (
        (s["rkv"] + s["dr"]) * chosen * model["num_hidden_layers"]
        + s["di"] * seen * index_layers(model))
    return flops, weight_bytes(model, experts_hit, dtype) + cache


def grouped_matmul_work(model, rows, experts_hit, dtype="bfloat16"):
    """``(flops, bytes)`` of the routed experts' three grouped matmuls
    over ``rows`` local assignments that hit ``experts_hit`` experts:
    each hit expert's matrices read once, each row in and out once."""
    s = sizes(model)
    b = ITEMSIZE[dtype]
    flops = 2 * expert_params(model) * rows
    nbytes = b * (experts_hit * expert_params(model)
                  + rows * (2 * s["d"] + 3 * s["fe"]))
    return flops, nbytes


def latent_attention_work(model, positions, dtype="bfloat16",
                          cache_dtype="bfloat16"):
    """``(flops, bytes)`` of ONE decode step's attention proper, all
    layers, in the ABSORBED form the decode kernel computes: per
    selected key and head a score over the latent row (``kv_lora_rank +
    qk_rope_head_dim``) and a sum over the latent (``kv_lora_rank``);
    each selected key's row read once a layer, the query and the
    context of every head in and out once."""
    s = sizes(model)
    n_layers = model["num_hidden_layers"]
    chosen = sum(selected_keys(p, s["topk"]) for p in positions)
    row = s["rkv"] + s["dr"]
    flops = 2 * s["h"] * (row + s["rkv"]) * chosen * n_layers
    nbytes = n_layers * (
        ITEMSIZE[cache_dtype] * row * chosen
        + ITEMSIZE[dtype] * 2 * len(positions) * s["h"] * row)
    return flops, nbytes
