"""Weights from ``--seed`` for the window-and-full-attention /
softmax-routed-experts configurations, leaf by leaf as
``weights_glm_dsa_moe.py`` draws them (whose leaf and key functions
this file uses): every leaf has a key of its own folded from the seed,
the layer's index and the leaf's name, is drawn in float32 and rounded
once to the dtype it is stored in; an expert's key is folded from its
id in the layer.  The tree is the one ``models/transformer.py`` names
under ``qk_norm`` and ``expert_dispatch="share"``
(``block_<i>/attn/q/kernel`` ... ``attn/q_norm/scale`` ...
``block_<i>/moe/wi``): no router bias, no shared expert.

The embedding has unit variance, as GLM's (a token's own embedding
leads the residual stream, so tokens differ in their experts); the
router is a plain normal matrix of variance 1 / hidden, so a normed
row's 64 logits have about unit spread and the softmax's eight largest
fall on every expert alike in expectation: PERF.md section 4 gives the
load the 64 experts saw on the chip.
"""

import jax
import jax.numpy as jnp

from benchmarks.weights import _nest, seed_key  # noqa: F401
from benchmarks.weights_glm_dsa_moe import (
    EMBEDDING_STD, _leaf, _path_key,
)


def sizes(model):
    """The sizes everything here, in ``flops_swa_moe`` and in the
    reference needs, from the configuration's published keys."""
    return dict(
        d=model["hidden_size"], h=model["num_attention_heads"],
        hkv=model["num_key_value_heads"], dh=model["head_dim"],
        fe=model["moe_intermediate_size"], experts=model["num_experts"],
        k=model["num_experts_per_tok"], v=model["vocab_size"],
        layers=model["num_hidden_layers"], window=model["sliding_window"],
    )


def block_leaves(model):
    """``{path: (shape, how)}`` of a block (every layer has the same
    leaves: the layer's type changes its mask and its RoPE, not its
    weights); ``how`` as ``weights_glm_dsa_moe.block_leaves``."""
    s = sizes(model)
    d, h, hkv, dh, fe, e = (
        s["d"], s["h"], s["hkv"], s["dh"], s["fe"], s["experts"])
    return {
        "ln1/scale": ((d,), "scale"), "ln2/scale": ((d,), "scale"),
        "attn/q/kernel": ((d, h, dh), d),
        "attn/k/kernel": ((d, hkv, dh), d),
        "attn/v/kernel": ((d, hkv, dh), d),
        "attn/q_norm/scale": ((dh,), "scale"),
        "attn/k_norm/scale": ((dh,), "scale"),
        "attn/out/kernel": ((h, dh, d), h * dh),
        "moe/router": ((d, e), d),
        "moe/wi": ((e, d, fe), ("experts", d)),
        "moe/wg": ((e, d, fe), ("experts", d)),
        "moe/wo": ((e, fe, d), ("experts", fe)),
    }


def block_params(model, key, index, dtype):
    """The leaves of block ``index`` (which may be traced)."""
    bkey = jax.random.fold_in(key, index + 1)
    return _nest({
        path: _leaf(_path_key(bkey, path), shape, how, dtype)
        for path, (shape, how) in block_leaves(model).items()
    })


def outer_params(model, key, dtype):
    """Embedding, final norm and the untied output head."""
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
    """The whole tree on the default device, a block a program (ONE
    program for all blocks: the index is an argument)."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    tree = jax.jit(lambda k: outer_params(model, k, dtype))(key)
    block = jax.jit(lambda k, i: block_params(model, k, i, dtype))
    for i in range(model["num_hidden_layers"]):
        tree["block_%d" % i] = block(key, jnp.int32(i))
    return tree
