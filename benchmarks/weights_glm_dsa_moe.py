"""Weights from ``--seed`` for the latent-attention / sparse-index /
sigmoid-routed-experts configurations, leaf by leaf like
``weights.py``: every leaf has a key of its own folded from the seed,
the layer's index and the leaf's name, is drawn in float32 and rounded
once to the dtype it is stored in.  A routed expert's key is folded
from its id in the WHOLE layer, so the experts a chip holds are the
same values whichever share it holds.  The tree is the one
``models/transformer.py`` names under ``attention_kind="mla"``
(``block_<i>/attn/q_a/kernel`` ... ``block_<i>/moe/wi``).

``make_params`` makes the program's tree a block at a time (one chip
holds the finished tree and not much more); ``block_params`` and
``outer_params`` are traceable, for the reference to draw a block
inside its own program.

Two scales are chosen for what they do to the ROUTER, whose load this
chip's step time follows (PERF.md section 6, PR 28).  The embedding
has unit variance, like the matrices' outputs: at ``weights.py``'s
0.02 the residual stream after the first layer is the attention's
output, an average over thousands of keys that is nearly the same for
every token, so every token asked for the same few experts (at full
widths 100-150 of 256 experts were never chosen in 512 tokens and the
16 held ones drew 0.6-1.4 times their share by seed); with it a token's
own embedding leads the stream and every expert is chosen.  The
router's correction bias is drawn at 0.002, a third of the distance
between neighbouring scores at the top-8 boundary (about 0.0065 among
256 sigmoid scores): it changes which expert is chosen for a fair
share of tokens and leaves every expert's load near its share, which
is what the published bias is trained to do.
"""

import zlib

import jax
import jax.numpy as jnp

from benchmarks.weights import _nest, seed_key


#: see the module's text
EMBEDDING_STD = 1.0
ROUTER_BIAS_STD = 0.002


def sizes(model):
    """The sizes everything here and in ``flops_glm_dsa_moe`` needs,
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
        rq=model["q_lora_rank"], rkv=model["kv_lora_rank"],
        dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
        dv=model["v_head_dim"], j=model["index_n_heads"],
        di=model["index_head_dim"], topk=model["index_topk"],
        f=model["intermediate_size"], fe=model["moe_intermediate_size"],
        shared=model["n_shared_experts"], k=model["num_experts_per_tok"],
        held=held, first=share["first"], experts=share["of"],
        v=model["vocab_size"], layers=model["num_hidden_layers"],
    )


def layer_kinds(model, index):
    """``(ffn, indexer)`` of layer ``index``: "dense" | "sparse", and
    "full" | "shared"."""
    return model["mlp_layer_types"][index], model["indexer_types"][index]


def block_leaves(model, index):
    """``{path: (shape, how)}`` of block ``index``; ``how`` is a fan-in
    (a matrix: normal, variance 1 / fan-in), "scale" (1 + 0.1 normal),
    "small" (0.1 normal: a bias), "correction" (``ROUTER_BIAS_STD``
    normal: the router's) or ``("experts", fan_in)`` (one draw an
    expert, keyed by the expert's id in the whole layer)."""
    s = sizes(model)
    d, h = s["d"], s["h"]
    ffn, indexer = layer_kinds(model, index)
    out = {
        "ln1/scale": ((d,), "scale"), "ln2/scale": ((d,), "scale"),
        "attn/q_a/kernel": ((d, s["rq"]), d),
        "attn/q_norm/scale": ((s["rq"],), "scale"),
        "attn/q_b": ((s["rq"], h, s["dn"] + s["dr"]), s["rq"]),
        "attn/kv_a/kernel": ((d, s["rkv"] + s["dr"]), d),
        "attn/kv_norm/scale": ((s["rkv"],), "scale"),
        "attn/kv_b": ((s["rkv"], h, s["dn"] + s["dv"]), s["rkv"]),
        "attn/out/kernel": ((h, s["dv"], d), h * s["dv"]),
    }
    if indexer == "full":
        out.update({
            "attn/index_q": ((s["rq"], s["j"], s["di"]), s["rq"]),
            "attn/index_k/kernel": ((d, s["di"]), d),
            "attn/index_k_norm/scale": ((s["di"],), "scale"),
            "attn/index_k_norm/bias": ((s["di"],), "small"),
            "attn/index_w/kernel": ((d, s["j"]), d),
        })
    if ffn == "dense":
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


def _leaf(key, shape, how, dtype, first=0):
    if how == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif how == "small":
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif how == "correction":
        x = ROUTER_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    elif isinstance(how, tuple):
        ids = first + jnp.arange(shape[0])
        x = jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(key, e), shape[1:], jnp.float32))(ids)
        x = x * how[1] ** -0.5
    else:
        x = jax.random.normal(key, shape, jnp.float32) * how ** -0.5
    return x.astype(dtype)


def _path_key(key, path):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


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
    """The whole tree on the default device, a block a program."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    tree = jax.jit(lambda k: outer_params(model, k, dtype))(key)
    for i in range(model["num_hidden_layers"]):
        tree["block_%d" % i] = jax.jit(
            lambda k, i=i: block_params(model, k, i, dtype))(key)
    return tree
