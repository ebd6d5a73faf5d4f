"""Weights from ``--seed``, made by the benchmark — never by the
program — so that the plain reference can make the very same values
again, layer by layer, without holding the whole model.

Every leaf has a key of its own, folded from the seed, the layer's
index and the leaf's index; a leaf is drawn in float32 and rounded once
to the dtype it is stored in.  The tree is the one
``models/transformer.py`` names (``block_<i>/attn/q/kernel`` ...).
"""

import jax
import jax.numpy as jnp

from benchmarks.flops import shapes

#: leaves of one block, in key order: (path, fan_in axes)
_BLOCK_LEAVES = (
    ("ln1/scale", None), ("ln2/scale", None),
    ("attn/q/kernel", 1), ("attn/k/kernel", 1), ("attn/v/kernel", 1),
    ("attn/out/kernel", 2),
    ("mlp/wi/kernel", 1), ("mlp/wg/kernel", 1), ("mlp/wo/kernel", 1),
)


def seed_key(seed):
    """A key from any whole number (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def block_shapes(model):
    s = shapes(model)
    d, h, hkv, dh, f = s["d"], s["h"], s["hkv"], s["dh"], s["f"]
    return {
        "ln1/scale": (d,), "ln2/scale": (d,),
        "attn/q/kernel": (d, h, dh), "attn/k/kernel": (d, hkv, dh),
        "attn/v/kernel": (d, hkv, dh), "attn/out/kernel": (h, dh, d),
        "mlp/wi/kernel": (d, f), "mlp/wg/kernel": (d, f),
        "mlp/wo/kernel": (f, d),
    }


def _leaf(key, shape, fan_axes, dtype):
    """Norm scales near one; matrices normal with variance 1/fan_in."""
    x = jax.random.normal(key, shape, jnp.float32)
    if fan_axes is None:
        return (1.0 + 0.1 * x).astype(dtype)
    fan_in = 1
    for n in shape[:fan_axes]:
        fan_in *= n
    return (x * fan_in ** -0.5).astype(dtype)


def _nest(flat):
    tree = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def block_params(model, key, index, dtype):
    """The leaves of block ``index`` (traceable)."""
    bkey = jax.random.fold_in(key, index + 1)
    shp = block_shapes(model)
    return _nest({
        path: _leaf(jax.random.fold_in(bkey, j), shp[path], fan, dtype)
        for j, (path, fan) in enumerate(_BLOCK_LEAVES)
    })


def outer_params(model, key, dtype):
    """Embedding, final norm and the untied output head (traceable)."""
    s = shapes(model)
    okey = jax.random.fold_in(key, 0)
    k = [jax.random.fold_in(okey, j) for j in range(3)]
    return {
        "embedding": (0.02 * jax.random.normal(
            k[0], (s["v"], s["d"]), jnp.float32)).astype(dtype),
        "ln_f": {"scale": _leaf(k[1], (s["d"],), None, dtype)},
        "lm_head": {"kernel": _leaf(k[2], (s["d"], s["v"]), 1, dtype)},
    }


def build_tree(model, key, dtype):
    """The whole tree (traceable)."""
    tree = outer_params(model, key, dtype)
    for i in range(model["num_hidden_layers"]):
        tree["block_%d" % i] = block_params(model, key, i, dtype)
    return tree


def make_params(model, seed, dtype, shardings=None):
    """The whole tree in ONE jitted call, in the dtype it is held in:
    on the default device, or laid out by ``shardings`` — a function
    of the tree's shapes that gives each leaf its sharding — so that a
    model one chip cannot hold is never whole on one."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    build = lambda k: build_tree(model, k, dtype)  # noqa: E731
    if shardings is None:
        return jax.jit(build)(key)
    return jax.jit(
        build, out_shardings=shardings(jax.eval_shape(build, key)))(key)
