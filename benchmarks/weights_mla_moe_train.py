"""Weights from ``--seed`` for the latent-attention / sigmoid-routed
configurations that are TRAINED (``deepseek_v3``-style keys: no sparse
index, ``q_lora_rank`` a number or ``null``, the leading
``first_k_dense_replace`` layers dense), leaf by leaf as
``weights_glm_dsa_moe.py`` draws them — whose leaf recipes, scales and
keys this file imports: every leaf has a key of its own folded from the
seed, the layer's index and the leaf's name, is drawn in float32 and
rounded once to the dtype it is stored in; a routed expert's key is
folded from its id in the WHOLE layer, so the experts a chip holds are
the same values whichever share it holds.  The tree is the one
``models/transformer.py`` names under ``attention_kind="mla"`` with
``q_lora_rank=0`` (``block_<i>/attn/q`` in place of ``q_a``, ``q_norm``,
``q_b``).

The embedding has unit variance and the router's correction bias is
drawn at 0.002, for the reasons ``weights_glm_dsa_moe.py`` gives: a
token's own embedding leads the residual stream, so every expert is
chosen, and the bias changes choices while the load stays near
balance.  The bias stays as drawn: nothing here or in the program
updates it (the configuration's ``assumed``).
"""

import jax
import jax.numpy as jnp

from benchmarks.weights import _nest, seed_key  # noqa: F401
from benchmarks.weights_glm_dsa_moe import (
    EMBEDDING_STD,
    _leaf,
    _path_key,
)


def sizes(model):
    """The sizes everything here and in ``flops_mla_moe_train`` needs,
    from the configuration's published keys; the expert share from
    ``expert_share`` (``first``, ``held``, ``of``; default: all)."""
    held = model["n_routed_experts"]
    share = model.get("expert_share") or {
        "first": 0, "held": held, "of": held}
    if share["held"] != held:
        raise ValueError("expert_share holds %d, n_routed_experts is %d" % (
            share["held"], held))
    return dict(
        d=model["hidden_size"], h=model["num_attention_heads"],
        rq=model.get("q_lora_rank") or 0, rkv=model["kv_lora_rank"],
        dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
        dv=model["v_head_dim"],
        f=model["intermediate_size"], fe=model["moe_intermediate_size"],
        shared=model["n_shared_experts"], k=model["num_experts_per_tok"],
        held=held, first=share["first"], experts=share["of"],
        v=model["vocab_size"], layers=model["num_hidden_layers"],
        dense_layers=model["first_k_dense_replace"],
    )


def ffn_kind(model, index):
    """"dense" for the leading ``first_k_dense_replace`` layers, then
    "sparse" (``moe_layer_freq`` is 1)."""
    return "dense" if index < model["first_k_dense_replace"] else "sparse"


def block_leaves(model, index):
    """``{path: (shape, how)}`` of block ``index``; ``how`` as
    ``weights_glm_dsa_moe.block_leaves`` has it."""
    s = sizes(model)
    d, h = s["d"], s["h"]
    out = {
        "ln1/scale": ((d,), "scale"), "ln2/scale": ((d,), "scale"),
        "attn/kv_a/kernel": ((d, s["rkv"] + s["dr"]), d),
        "attn/kv_norm/scale": ((s["rkv"],), "scale"),
        "attn/kv_b": ((s["rkv"], h, s["dn"] + s["dv"]), s["rkv"]),
        "attn/out/kernel": ((h, s["dv"], d), h * s["dv"]),
    }
    if s["rq"]:
        out.update({
            "attn/q_a/kernel": ((d, s["rq"]), d),
            "attn/q_norm/scale": ((s["rq"],), "scale"),
            "attn/q_b": ((s["rq"], h, s["dn"] + s["dr"]), s["rq"]),
        })
    else:
        out["attn/q"] = ((d, h, s["dn"] + s["dr"]), d)
    if ffn_kind(model, index) == "dense":
        out.update({
            "mlp/wi/kernel": ((d, s["f"]), d),
            "mlp/wg/kernel": ((d, s["f"]), d),
            "mlp/wo/kernel": ((s["f"], d), s["f"]),
        })
    else:
        fe, wide = s["fe"], s["fe"] * s["shared"]
        out.update({
            "moe/router": ((d, s["experts"]), d),
            "moe/router_bias": ((s["experts"],), "correction"),
            "moe/wi": ((s["held"], d, fe), ("experts", d)),
            "moe/wg": ((s["held"], d, fe), ("experts", d)),
            "moe/wo": ((s["held"], fe, d), ("experts", fe)),
            "moe/shared_wi/kernel": ((d, wide), d),
            "moe/shared_wg/kernel": ((d, wide), d),
            "moe/shared_wo/kernel": ((wide, d), wide),
        })
    return out


def block_params(model, key, index, dtype, kind_of=None):
    """The leaves of block ``index``.  ``index`` may be traced when
    ``kind_of`` gives a (static) layer of the same kind to take the
    leaf set from."""
    bkey = jax.random.fold_in(key, index + 1)
    first = sizes(model)["first"]
    leaves = block_leaves(model, index if kind_of is None else kind_of)
    return _nest({
        path: _leaf(_path_key(bkey, path), shape, how, dtype, first)
        for path, (shape, how) in leaves.items()
    })


def outer_params(model, key, dtype):
    """Embedding, final norm and the untied output head, over the
    vocabulary slice the configuration holds."""
    s = sizes(model)
    okey = jax.random.fold_in(key, 0)
    return {
        "embedding": (EMBEDDING_STD * jax.random.normal(
            _path_key(okey, "embedding"), (s["v"], s["d"]), jnp.float32)
        ).astype(dtype),
        "ln_f": {"scale": _leaf(
            _path_key(okey, "ln_f"), (s["d"],), "scale", dtype)},
        "lm_head": {"kernel": _leaf(
            _path_key(okey, "lm_head"), (s["d"], s["v"]), s["d"], dtype)},
    }


def total_params(model):
    """Every stored parameter of the tree."""
    n = 2 * sizes(model)["v"] * sizes(model)["d"] + sizes(model)["d"]
    for i in range(model["num_hidden_layers"]):
        for shape, _ in block_leaves(model, i).values():
            size = 1
            for dim in shape:
                size *= dim
            n += size
    return n


def make_params(model, seed, dtype):
    """The whole tree on the default device, a block a program."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    tree = jax.jit(lambda k: outer_params(model, k, dtype))(key)
    for i in range(model["num_hidden_layers"]):
        tree["block_%d" % i] = jax.jit(
            lambda k, i=i: block_params(model, k, i, dtype))(key)
    return tree
