"""Operations and bytes that the ALGORITHM of a TRAINING step of the
latent-attention / sigmoid-routed-experts block needs, as functions of
the configuration's shapes (``weights_mla_moe_train.sizes``) and of
the step's own integer counters (how many routed assignments landed on
the experts held here, how many held experts a routing pass reached) —
never of what one implementation happens to move.  The companion of
``flops.py`` (whose counting rules these are) and of
``flops_glm_dsa_moe.py`` (the same block served).

Counted, forward: every projection once a token (the key/value
expansion ``W_kvb`` among them), attention in its non-absorbed form
over the causal (query, key) pairs at ``nope + rope`` wide scores and
``v_head_dim`` wide values, the dense MLP or the router and the shared
experts, ONE routed expert per LOCAL assignment, the head over the
vocabulary rows held at every position.  Forward and backward are three
times the forward; nothing recomputed (a block's remat, the scores a
flash backward makes again) is counted.
"""

from benchmarks.flops import ITEMSIZE, roofline_seconds  # noqa: F401
from benchmarks.weights_mla_moe_train import ffn_kind, sizes


def attention_params(model):
    """Matmul parameters of one layer's attention."""
    s = sizes(model)
    d, h = s["d"], s["h"]
    q = (d * s["rq"] + s["rq"] * h * (s["dn"] + s["dr"]) if s["rq"]
         else d * h * (s["dn"] + s["dr"]))
    return (q + d * (s["rkv"] + s["dr"])
            + s["rkv"] * h * (s["dn"] + s["dv"]) + h * s["dv"] * d)


def expert_params(model):
    """One routed expert: three matrices."""
    s = sizes(model)
    return 3 * s["d"] * s["fe"]


def ffn_params(model, kind):
    """What EVERY token multiplies in a layer's FFN: the dense MLP, or
    the router and the shared experts (routed experts are counted by
    assignment)."""
    s = sizes(model)
    if kind == "dense":
        return 3 * s["d"] * s["f"]
    return s["d"] * s["experts"] + s["shared"] * expert_params(model)


def kinds(model):
    return [ffn_kind(model, i) for i in range(model["num_hidden_layers"])]


def sparse_layers(model):
    return sum(k == "sparse" for k in kinds(model))


def token_params(model):
    """Matmul parameters every token multiplies: all layers and the
    head over the vocabulary rows held."""
    s = sizes(model)
    return sum(attention_params(model) + ffn_params(model, k)
               for k in kinds(model)) + s["d"] * s["v"]


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def expected_local(model, tokens):
    """Local assignments of ``tokens`` tokens, all sparse layers, under
    a router that favours no expert."""
    s = sizes(model)
    return tokens * s["k"] * s["held"] / s["experts"] * sparse_layers(model)


def forward_flops(model, rows, seq_len, local_assignments=None):
    """Forward operations of ONE step of ``rows`` sequences;
    ``local_assignments`` routed rows landed on held experts, summed
    over the sparse layers (default: the expectation)."""
    s = sizes(model)
    tokens = rows * seq_len
    if local_assignments is None:
        local_assignments = expected_local(model, tokens)
    dense = 2 * token_params(model) * tokens
    routed = 2 * expert_params(model) * local_assignments
    attend = (2 * s["h"] * (s["dn"] + s["dr"] + s["dv"])
              * causal_pairs(seq_len) * rows * model["num_hidden_layers"])
    return dense + routed + attend


def step_flops(model, rows, seq_len, local_assignments=None):
    """Forward and backward of one optimizer step: the backward costs
    twice the forward; nothing recomputed is counted."""
    return 3 * forward_flops(model, rows, seq_len, local_assignments)


def flash_work(model, rows, seq_len, dtype="bfloat16"):
    """``(flops, bytes)`` of the attention kernel alone, forward and
    backward, ALL layers of one step.  Per causal (query, key) pair and
    head: the score over ``nope + rope`` and the value product over
    ``v_head_dim`` forward; backward dp and dv over ``v_head_dim``, dq
    and dk over ``nope + rope`` (the score a flash backward computes
    again is recomputation and is not counted).  Bytes as
    ``flops.flash_bytes``: q, k, v read and the output written once;
    the backward reads them, the output and its cotangent, and writes
    dq, dk, dv."""
    s = sizes(model)
    b = ITEMSIZE[dtype]
    dqk, dv = s["dn"] + s["dr"], s["dv"]
    pairs = causal_pairs(seq_len) * rows * s["h"]
    flops = (2 * (dqk + dv) + 4 * (dqk + dv)) * pairs
    qk = rows * seq_len * s["h"] * dqk * b   # q, k, dq, dk: each
    vo = rows * seq_len * s["h"] * dv * b    # v, o, do, dv: each
    nbytes = (2 * qk + 2 * vo) + (2 * qk + 3 * vo) + (2 * qk + vo)
    layers = model["num_hidden_layers"]
    return layers * flops, layers * nbytes


def grouped_matmul_work(model, local_assignments, experts_hit,
                        dtype="bfloat16"):
    """``(flops, bytes)`` of a step's routed products, forward, ``dx``
    and ``dw``: ``local_assignments`` rows (summed over sparse layers)
    each through ONE expert's three matrices three times (``y``, the
    input gradient, the weight gradient); ``experts_hit`` held experts
    reached, summed over layers and routing passes — each one's
    matrices read once a pass forward and once for ``dx``, its gradient
    written once; every row's operands in and out once a product."""
    s = sizes(model)
    b = ITEMSIZE[dtype]
    flops = 3 * 2 * expert_params(model) * local_assignments
    rows_io = local_assignments * (2 * s["d"] + 3 * s["fe"])
    nbytes = b * 3 * (experts_hit * expert_params(model) + rows_io)
    return flops, nbytes
