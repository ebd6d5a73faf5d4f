"""Weights from ``--seed`` for the gated window-and-full-attention /
softmax-routed-experts configurations (Laguna's ``config.json``), leaf
by leaf as ``weights_glm_dsa_moe.py`` draws them (whose leaf and key
functions this file uses): every leaf has a key of its own folded from
the seed, the layer's index and the leaf's name, is drawn in float32
and rounded once to the dtype it is stored in; a routed expert's key is
folded from its id in the WHOLE layer, so the experts a chip holds are
the same values whichever share it holds.  The tree is the one
``models/transformer.py`` names under ``qk_norm``, ``gating`` and
``expert_dispatch="share"``: ``block_<i>/attn/q/kernel`` (the layer's
own query heads) ... ``attn/gate/kernel``, a dense ``mlp`` on the
layers ``mlp_layer_types`` calls dense, ``moe/router`` over every
expert of the layer, the held experts' ``moe/wi`` ... and the shared
expert's ``moe/shared_wi/kernel`` ...; no router bias.

The embedding has unit variance, as GLM's (a token's own embedding
leads the residual stream, so tokens differ in their experts); the
router is a plain normal matrix of variance 1 / hidden, so a normed
row's 256 logits have about unit spread and the softmax's ten largest
fall on every expert alike in expectation.  The gate's matrix is drawn
like every other (variance 1 / hidden): a normed row's gate logits
have about unit spread, so the gates spread over (0.2, 0.8) and not
near 1, where leaving them out would change little.
"""

import jax
import jax.numpy as jnp

from benchmarks.weights import _nest, seed_key  # noqa: F401
from benchmarks.weights_glm_dsa_moe import (
    EMBEDDING_STD, _leaf, _path_key,
)


def sizes(model):
    """The sizes everything here, in ``flops_gated_swa_moe`` and in the
    reference needs, from the configuration's published keys; the
    expert share from ``expert_share`` (``first``, ``held``, ``of``;
    default: all held)."""
    held = model["num_experts"]
    share = model.get("expert_share") or {
        "first": 0, "held": held, "of": held}
    if share["held"] != held:
        raise ValueError("expert_share holds %d, num_experts is %d" % (
            share["held"], held))
    return dict(
        d=model["hidden_size"], hkv=model["num_key_value_heads"],
        dh=model["head_dim"], f=model["intermediate_size"],
        fe=model["moe_intermediate_size"],
        fs=model["shared_expert_intermediate_size"],
        k=model["num_experts_per_tok"], held=held, first=share["first"],
        experts=share["of"], v=model["vocab_size"],
        layers=model["num_hidden_layers"], window=model["sliding_window"],
    )


def heads(model, index):
    """Query heads of layer ``index``."""
    return model["num_attention_heads_per_layer"][index]


def layer_kinds(model, index):
    """``(attention, ffn, heads)`` of layer ``index``: its entries of
    ``layer_types``, ``mlp_layer_types`` and
    ``num_attention_heads_per_layer``; layers of the same kinds have
    the same leaves."""
    return (model["layer_types"][index], model["mlp_layer_types"][index],
            heads(model, index))


def block_leaves(model, index):
    """``{path: (shape, how)}`` of block ``index``; ``how`` as
    ``weights_glm_dsa_moe.block_leaves``."""
    s = sizes(model)
    d, hkv, dh = s["d"], s["hkv"], s["dh"]
    _, ffn, h = layer_kinds(model, index)
    out = {
        "ln1/scale": ((d,), "scale"), "ln2/scale": ((d,), "scale"),
        "attn/q/kernel": ((d, h, dh), d),
        "attn/k/kernel": ((d, hkv, dh), d),
        "attn/v/kernel": ((d, hkv, dh), d),
        "attn/q_norm/scale": ((dh,), "scale"),
        "attn/k_norm/scale": ((dh,), "scale"),
        "attn/gate/kernel": ((d, h), d),
        "attn/out/kernel": ((h, dh, d), h * dh),
    }
    if ffn == "dense":
        out.update({
            "mlp/wi/kernel": ((d, s["f"]), d),
            "mlp/wg/kernel": ((d, s["f"]), d),
            "mlp/wo/kernel": ((s["f"], d), s["f"]),
        })
    else:
        fe, fs = s["fe"], s["fs"]
        out.update({
            "moe/router": ((d, s["experts"]), d),
            "moe/wi": ((s["held"], d, fe), ("experts", d)),
            "moe/wg": ((s["held"], d, fe), ("experts", d)),
            "moe/wo": ((s["held"], fe, d), ("experts", fe)),
            "moe/shared_wi/kernel": ((d, fs), d),
            "moe/shared_wg/kernel": ((d, fs), d),
            "moe/shared_wo/kernel": ((fs, d), fs),
        })
    return out


def block_params(model, key, index, dtype, kinds_of=None):
    """The leaves of block ``index``.  ``index`` may be traced when
    ``kinds_of`` gives a (static) layer of the same kinds to take the
    leaf set from."""
    bkey = jax.random.fold_in(key, index + 1)
    first = sizes(model)["first"]
    leaves = block_leaves(model, index if kinds_of is None else kinds_of)
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


def make_params(model, seed, dtype):
    """The whole tree on the default device, a block a program: ONE
    program for the blocks of the same kinds (the index is an
    argument)."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    tree = jax.jit(lambda k: outer_params(model, k, dtype))(key)
    programs = {}
    for i in range(model["num_hidden_layers"]):
        kinds = layer_kinds(model, i)
        if kinds not in programs:
            programs[kinds] = jax.jit(
                lambda k, j, i=i: block_params(model, k, j, dtype, i))
        tree["block_%d" % i] = programs[kinds](key, jnp.int32(i))
    return tree
