"""Weight-only int8 / int4 quantization for inference and decode.

New TPU-first capability with no reference analogue (the reference
serves f32 TF SavedModels; `/root/reference/src/main/scala/com/yahoo/
tensorflowonspark/TFModel.scala` has no quantized path).  Rationale:
single-token decode and small-batch serving are HBM-bandwidth-bound on
the *weight read*, and the MXU dequantizes
int8 operands on the fly — storing matmul weights as int8 + per-channel
scales halves their HBM traffic (whether that shows in a decode step
on this round's machine: not measured — ROADMAP S2); the
activations, cache, and numerics-sensitive small tensors stay bf16.

Scheme: symmetric per-channel int8.  For a flax kernel the contraction
axes always precede the output axes, so scales are computed over every
axis but the last — constant along all contracted axes, which is what
lets ``(x @ q) * scale`` factor out of the dot exactly.  Embedding
tables are a lookup, not a contraction, so they quantize per ROW (each
token id gets its own scale).  1-D leaves (norm gains) and tiny leaves
stay float: they are numerics-critical and contribute nothing to
bandwidth.

Usage::

    qparams = quantize_tree(params)        # QTensor leaves for weights
    tokens  = generate(model, qparams, ...)  # dequant fused per step

``generate``/serving detect :class:`QTensor` leaves and dequantize
INSIDE the decode step under ``lax.optimization_barrier`` — without the
barrier XLA may hoist the int8→bf16 convert out of the scan and
materialize full-precision weights once, silently forfeiting the
bandwidth win.

**int4 (ISSUE 12).**  Decode is bandwidth-bound on the weight read
(int8 already measured 1.64× with int8-KV at long cache), so halving
it again is a direct tok/s multiplier: :class:`QTensor4` stores matmul
weights as signed int4 codes packed TWO PER BYTE along the flattened
contraction axis, with **group-wise scales** — one f32 scale per
``group_size`` contraction rows per output channel.  Group scales are
what keep 15 levels usable: a per-channel int4 scale would clip any
channel whose magnitudes vary along the contraction.  Because the
scale varies ALONG the contraction, the dequant cannot factor out of
the dot like int8's per-channel scales — it fuses into the matmul
*epilogue* instead: the unpack + scale runs under the same
``optimization_barrier`` contract, so the weights cross HBM as packed
nibbles every decode step and XLA fuses the widening into the operand
read.  ``quantize_tree_int4`` targets the dense matmul kernels;
embedding (a gather, not a contraction) and expert-stacked MoE leaves
keep the int8 scheme — the int8 path itself is byte-for-byte untouched
(guarded in tests/test_quantize.py).
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    """Symmetric per-channel int8 weight: ``w ≈ q * scale``."""

    q: jax.Array  # int8, original shape
    scale: jax.Array  # f32, keepdims-reduced over the quantized axes


@jax.tree_util.register_pytree_node_class
class QTensor4(object):
    """Symmetric group-wise int4 weight, packed two codes per byte.

    ``packed`` is ``uint8 [Kp // 2, N]`` where ``Kp`` is the flattened
    contraction length padded up to ``group_size`` (consecutive
    contraction rows share a byte: row ``2i`` in the low nibble, row
    ``2i + 1`` in the high nibble); ``scale`` is ``f32 [Kp //
    group_size, N]``.  ``shape``/``group_size`` ride as static pytree
    aux data, so a :class:`QTensor4` traces through jit/donation like
    any array pair.
    """

    __slots__ = ("packed", "scale", "shape", "group_size")

    def __init__(self, packed, scale, shape, group_size):
        self.packed = packed
        self.scale = scale
        self.shape = tuple(shape)
        self.group_size = int(group_size)

    def tree_flatten(self):
        return (self.packed, self.scale), (self.shape, self.group_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    def __repr__(self):
        return "QTensor4(shape={0}, group_size={1})".format(
            self.shape, self.group_size
        )


def _is_q(x):
    return isinstance(x, QTensor)


def _is_q4(x):
    return isinstance(x, QTensor4)


def _is_any_q(x):
    return isinstance(x, (QTensor, QTensor4))


def quantize_leaf(w, reduce_axes):
    """Quantize one float array to int8 over ``reduce_axes``."""
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale)


def dequantize_leaf(qt, dtype=jnp.bfloat16):
    return qt.q.astype(dtype) * qt.scale.astype(dtype)


# ----------------------------------------------------------------------
# int4: group-wise scales, two codes per byte
# ----------------------------------------------------------------------


def pack_int4(q):
    """Pack signed int4 codes (int8 values in ``[-8, 7]``) along axis 0
    into ``uint8`` nibbles: row ``2i`` low, row ``2i + 1`` high.  Axis
    0 must be even (the quantizer's group padding guarantees it)."""
    q = jnp.asarray(q, jnp.int8)
    if q.shape[0] % 2:
        raise ValueError(
            "pack_int4 needs an even leading dim, got {0}".format(q.shape)
        )
    u = jnp.asarray(q, jnp.uint8) & jnp.uint8(0xF)  # two's-complement nibble
    return (u[0::2] | (u[1::2] << 4)).astype(jnp.uint8)


def unpack_int4(packed):
    """Inverse of :func:`pack_int4`: ``uint8 [K/2, ...]`` → signed int8
    codes ``[K, ...]`` in ``[-8, 7]`` (exact round trip, tested incl.
    the nibble sign boundary at -8/7)."""
    p = jnp.asarray(packed, jnp.uint8)
    lo = (p & jnp.uint8(0xF)).astype(jnp.int8)
    hi = (p >> 4).astype(jnp.int8)
    sign = lambda n: jnp.where(n >= 8, n - 16, n)  # noqa: E731
    pair = jnp.stack([sign(lo), sign(hi)], axis=1)  # [K/2, 2, ...]
    return pair.reshape((p.shape[0] * 2,) + p.shape[1:]).astype(jnp.int8)


def quantize_leaf_int4(w, group_size=64):
    """Quantize one float array to packed int4 with group-wise scales.

    The array is viewed as ``[K, N]`` (``N`` the last axis — the flax
    kernel output channels; ``K`` the flattened contraction axes) and
    split into contraction groups of ``group_size`` rows; each
    ``(group, output-channel)`` pair gets its own symmetric scale over
    the 15-level code book ``[-7, 7]``.  ``K`` pads up to a whole
    group (zero rows — odd channel counts round-trip exactly, the pad
    is sliced back off at dequant)."""
    g = int(group_size)
    if g < 2 or g % 2:
        raise ValueError(
            "group_size must be an even int >= 2, got {0}".format(group_size)
        )
    wf = jnp.asarray(w, jnp.float32)
    shape = wf.shape
    n = shape[-1]
    k = 1
    for s in shape[:-1]:
        k *= s
    w2 = wf.reshape(k, n)
    kp = ((k + g - 1) // g) * g
    if kp != k:
        w2 = jnp.concatenate(
            [w2, jnp.zeros((kp - k, n), jnp.float32)], axis=0
        )
    wg = w2.reshape(kp // g, g, n)
    amax = jnp.max(jnp.abs(wg), axis=1, keepdims=True)  # [G, 1, N]
    scale = jnp.maximum(amax, 1e-12) / 7.0
    q = jnp.clip(jnp.round(wg / scale), -7, 7).astype(jnp.int8)
    return QTensor4(
        pack_int4(q.reshape(kp, n)), scale[:, 0, :], shape, g
    )


def dequantize_leaf_int4(qt, dtype=jnp.bfloat16):
    """Unpack + group-scale a :class:`QTensor4` back to ``dtype`` at
    its original shape — the matmul-epilogue dequant (the caller pins
    it in place with ``optimization_barrier``, see
    :func:`dequantize_tree`)."""
    g = qt.group_size
    q = unpack_int4(qt.packed)  # [Kp, N] int8
    kp, n = q.shape
    w = q.reshape(kp // g, g, n).astype(jnp.float32) * qt.scale[:, None, :]
    k = 1
    for s in qt.shape[:-1]:
        k *= s
    return w.reshape(kp, n)[:k].reshape(qt.shape).astype(dtype)


def quantize_tree(params, min_size=16384, embed_key="embedding",
                  expert_keys=("wi", "wg", "wo")):
    """Quantize every matmul-sized weight in a param pytree.

    Leaves with ``ndim >= 2`` and ``size >= min_size`` become
    :class:`QTensor`; everything else passes through unchanged.  Leaves
    whose path contains ``embed_key`` reduce over the last axis
    (per-row scales — lookups have no contraction).  3-D leaves named
    in ``expert_keys`` are expert-STACKED MoE weights ``[E, D, M]``:
    axis 0 is a batch of independent matmuls, not a contraction, so
    each expert gets its own scales (reduce axis 1 only — sharing one
    scale across experts would inflate the error of any expert whose
    magnitudes sit below the loudest one's).  All others reduce over
    every axis but the last (constant along the contracted axes of any
    flax kernel, where contraction axes precede output axes).
    """

    def _one(path, w):
        if _is_any_q(w):
            # already quantized: pass through unchanged (descending into
            # the QTensor would re-quantize large float scale leaves —
            # e.g. an embedding's [V, 1] scales — nesting QTensors and
            # breaking dequantize later); double application is a no-op
            return w
        if not hasattr(w, "ndim") or w.ndim < 2:
            return w
        if w.size < min_size or not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        names = [str(getattr(k, "key", k)) for k in path]
        if any(embed_key in n for n in names):
            return quantize_leaf(w, reduce_axes=(w.ndim - 1,))
        if w.ndim == 3 and names and names[-1] in expert_keys:
            return quantize_leaf(w, reduce_axes=(1,))
        return quantize_leaf(w, reduce_axes=tuple(range(w.ndim - 1)))

    # is_leaf=_is_any_q: QTensor is itself a pytree (NamedTuple) —
    # without the leaf predicate, tree_map would descend into an
    # already-quantized tree and hand _one the raw q/scale children (a
    # large float scale, e.g. an embedding's [V, 1], would then
    # re-quantize into a NESTED QTensor that crashes dequantize)
    return jax.tree_util.tree_map_with_path(_one, params, is_leaf=_is_any_q)


def quantize_tree_int4(params, group_size=64, min_size=16384,
                       embed_key="embedding", expert_keys=("wi", "wg", "wo")):
    """int4 twin of :func:`quantize_tree` (the ``weights="int4"``
    deployment): dense matmul kernels become packed group-wise
    :class:`QTensor4`; embedding leaves (a gather — per-row int8 stays
    the right scheme) and expert-stacked MoE leaves (per-expert scales)
    keep the int8 path; everything else passes through.  A mixed
    int4/int8 tree dequantizes through the one :func:`dequantize_tree`.
    """

    def _one(path, w):
        if _is_any_q(w):
            return w
        if not hasattr(w, "ndim") or w.ndim < 2:
            return w
        if w.size < min_size or not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        names = [str(getattr(k, "key", k)) for k in path]
        if any(embed_key in n for n in names):
            return quantize_leaf(w, reduce_axes=(w.ndim - 1,))
        if w.ndim == 3 and names and names[-1] in expert_keys:
            return quantize_leaf(w, reduce_axes=(1,))
        return quantize_leaf_int4(w, group_size=group_size)

    return jax.tree_util.tree_map_with_path(_one, params, is_leaf=_is_any_q)


def is_quantized(params):
    """True if any leaf of ``params`` is a :class:`QTensor` /
    :class:`QTensor4`."""
    return any(
        _is_any_q(x) for x in jax.tree.leaves(params, is_leaf=_is_any_q)
    )


def quantization_of(params):
    """The tree's weight scheme: ``"int4"`` when any packed leaf is
    present (mixed trees count as int4 — that's the deployment that
    produced them), ``"int8"`` for pure :class:`QTensor` trees, else
    ``None``.  The hot-swap ingest path re-quantizes with the SAME
    scheme the live decoder serves."""
    leaves = jax.tree.leaves(params, is_leaf=_is_any_q)
    if any(_is_q4(x) for x in leaves):
        return "int4"
    if any(_is_q(x) for x in leaves):
        return "int8"
    return None


def dequantize_tree(params, dtype=jnp.bfloat16, barrier=True):
    """Materialize a float param tree from a (partially) quantized one.

    With ``barrier=True`` each quantized leaf passes through
    ``lax.optimization_barrier`` first, pinning the dequant to the
    surrounding trace position (inside a decode scan body) so XLA
    cannot hoist it out and cache bf16 weights — the int8 (or packed
    int4) HBM read IS the optimization.
    """

    def _one(x):
        if _is_q4(x):
            if barrier:
                packed, scale = jax.lax.optimization_barrier(
                    (x.packed, x.scale)
                )
                x = QTensor4(packed, scale, x.shape, x.group_size)
            return dequantize_leaf_int4(x, dtype)
        if not _is_q(x):
            return x
        if barrier:
            x = QTensor(*jax.lax.optimization_barrier(tuple(x)))
        return dequantize_leaf(x, dtype)

    return jax.tree.map(_one, params, is_leaf=_is_any_q)


def quantization_error(params, qparams):
    """Max relative error per quantized leaf (diagnostics/tests)."""
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(
        qparams, is_leaf=_is_any_q
    )[0]
    orig = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, leaf in flat:
        if _is_any_q(leaf):
            w = jnp.asarray(orig[path], jnp.float32)
            deq = (
                dequantize_leaf_int4(leaf, jnp.float32) if _is_q4(leaf)
                else dequantize_leaf(leaf, jnp.float32)
            )
            err = jnp.max(jnp.abs(deq - w))
            denom = jnp.max(jnp.abs(w))
            out[jax.tree_util.keystr(path)] = float(err / denom)
    return out
