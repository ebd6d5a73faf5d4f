"""Latent attention's two Pallas kernels: the one-token decode step
over a LATENT bank, and a span's attention over its own tokens.

Absorbed multi-head latent attention (models/mla.py) scores every head
against the same ``[L, W]`` rows of a slot's bank and sums the same
rows into the context: written as two einsums, XLA reads the bank
twice a layer (0.59 ms each at 17 slots of 20415 x 640: my chip run,
PR 28) and writes the ``[B, H, L]`` float32 logits between them.  Here
a grid step takes one block of a slot's rows through both products
with an online softmax, so a row is read once — and only the blocks
between the slot's pad region and its own position are read at all
(the index maps clamp to that span, a step outside it copies nothing
and computes nothing).  Which keys inside a block count is the
caller's additive ``bias`` (0 for a selected key, ``MASKED`` for any
other): the sparse index's selection, the pad region and the causal
horizon all arrive that way.

A span of tokens (a prefill) attends non-absorbed, per head, over its
own expanded keys and values (:func:`latent_span_attention`): written
as einsums a block of 128 queries, XLA carries the ``[H, 128, K]``
float32 scores through HBM four times (~40 bytes a score: my chip
run, PR 28).  Here a grid step takes one block of queries against one
block of keys with the online softmax, the scores never leave VMEM,
and which keys count arrives as the ``[B, S, K]`` int8 selection —
the index's exact top-k, the causal horizon and the pad region in
one operand.  Key blocks after a query block's last row, or wholly
inside the pad region, are neither copied nor computed.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tensorflowonspark_tpu import compat

#: bias of a key that does not count.  Finite: a block with no counted
#: key leaves junk sums that the next real maximum scales to nought,
#: where -inf would leave NaN
MASKED = -1e30
#: rows a grid step, the largest that divides the bank
BLOCKS = (1024, 512, 256, 128)


def block_rows(bank_len, width):
    """Rows a grid step for a bank ``[B, bank_len, width]``, or None
    where no tile-legal block divides it (the caller keeps its
    einsums)."""
    if width % 128:
        return None
    return next((t for t in BLOCKS if bank_len % t == 0), None)


def _kernel(first_ref, last_ref, q_ref, bank_ref, bias_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, blocks):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(jnp.logical_and(j >= first_ref[b], j <= last_ref[b]))
    def _():
        rows = bank_ref[0]                                   # [T, W]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[0]                              # [H, T]
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_ref[...] - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(rows.dtype), rows, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == blocks - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_decode_attention(q, bank, bias, first, last, *, scale,
                            interpret=None):
    """``softmax(q · bank^T · scale + bias) · bank`` a slot:
    ``q [B, H, W]``, ``bank [B, L, W]``, ``bias [B, 1, L]`` float32,
    ``first``/``last`` ``[B]`` int32 — the bank positions between which
    a slot's counted keys lie (whole blocks outside are skipped; inside,
    ``bias`` decides).  Every slot must count at least one key in that
    span.  Returns ``[B, H, W]`` in ``q``'s dtype: the probabilities
    times the WHOLE row (the caller drops the columns that are no
    values).  ``L`` must be a multiple of :func:`block_rows`."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = compat.pallas_interpret()
    b, h, w = q.shape
    length = bank.shape[1]
    t = block_rows(length, w)
    if t is None:
        raise ValueError(
            "no block of %s rows divides a bank of %d x %d" % (
                BLOCKS, length, w))
    blocks = length // t

    def span(j, first, last, b):
        return jnp.clip(j, first[b], last[b])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda b, j, f, la: (b, 0, 0)),
            pl.BlockSpec(
                (1, t, w), lambda b, j, f, la: (b, span(j, f, la, b), 0)),
            pl.BlockSpec(
                (1, 1, t), lambda b, j, f, la: (b, 0, span(j, f, la, b))),
        ],
        out_specs=pl.BlockSpec((1, h, w), lambda b, j, f, la: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, w), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, blocks=blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_decode_attention",
    )(first // t, last // t, q, bank, bias)


#: queries x keys a grid step of the span kernel, the first pair that
#: divides the span.  At 64 heads of 256 over 12288 / 16384 tokens
#: (my chip run, PR 29): 2048 x 512 36.4 / 62.2 ms a layer, 1024 x
#: 1024 38.0 / 64.9, 1024 x 512 37.7 / 65.6, 512 x 512 43.2 / 75.7,
#: 256 x 1024 51.2 / 88.0 — the queries stay, the keys stream.  Inside
#: the cell's prefill program 2048 x 512 and 1024 x 1024 read alike
#: (38.2 / 37.7 ms a layer at 12288)
SPAN_BLOCKS = (
    (2048, 512), (1024, 512), (512, 512), (256, 256), (128, 128))
#: the span kernel's VMEM: float32 score and probability blocks of
#: 2048 x 512 beside the double-buffered operands pass the 16 MB a
#: kernel gets unasked
SPAN_VMEM_BYTES = 64 * 1024 * 1024


def span_blocks(span):
    """``(queries, keys)`` a grid step for a span of ``span`` tokens,
    or None where no pair divides it (the caller keeps its
    einsums)."""
    return next(
        ((tq, tk) for tq, tk in SPAN_BLOCKS
         if span % tq == 0 and span % tk == 0), None)


def _key_blocks(i, first, tq, tk):
    """First and last key block that query block ``i`` reads (keys in
    the queries' order): up to the block of its own last row, from the
    block of the first key that counts (``first``: the pad region ends
    there) — or from the block of its own first row where that comes
    sooner, since a pad query counts itself."""
    return (jnp.minimum(first // tk, i * tq // tk),
            ((i + 1) * tq - 1) // tk)


def _span_kernel(first_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, scale, tq, tk, blocks):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    lo, hi = _key_blocks(i, first_ref[b], tq, tk)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(jnp.logical_and(j >= lo, j <= hi))
    def _():
        v = v_ref[0, 0]                                      # [Tk, dv]
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # [Tq, Tk]
        s = jnp.where(mask_ref[0] != 0, s * scale, MASKED)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_ref[...] - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == blocks - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "blocks", "interpret"))
def latent_span_attention(q, k, v, mask, first, *, scale, blocks=None,
                          interpret=None):
    """``softmax(q · k^T · scale, over the keys that count) · v`` a
    head: ``q [B, H, S, d]`` (``q_nope | q_rope``), ``k [B, H, S, d]``
    (``k_nope`` beside the one rotary key, repeated a head), ``v [B, H,
    S, dv]``, ``mask [B, S, S]`` int8 — non-zero where query ``s``
    counts key ``k``, the same for every head, and nowhere for a key
    after the query (the span attends over its own tokens, in order) —
    and ``first [B]`` int32, the first key any query but a pad query
    itself counts (key blocks wholly before it are skipped but for a
    query block's own).  Every query must count at least one key.
    Forward only.  Returns ``[B, H, S, dv]`` in ``v``'s dtype.
    ``blocks`` = ``(queries, keys)`` a grid step, both dividing ``S``:
    :func:`span_blocks` of it unless given."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = compat.pallas_interpret()
    b, h, s, d = q.shape
    dv = v.shape[-1]
    if blocks is None:
        blocks = span_blocks(s)
    if blocks is None or s % blocks[0] or s % blocks[1]:
        raise ValueError(
            "blocks of %s tokens do not divide a span of %d" % (
                blocks or SPAN_BLOCKS, s))
    tq, tk = blocks

    def key(j, i, first, b):
        return jnp.clip(j, *_key_blocks(i, first[b], tq, tk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, s // tq, s // tk),
        in_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda b, h, i, j, f: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, tk, d),
                lambda b, h, i, j, f: (b, h, key(j, i, f, b), 0)),
            pl.BlockSpec(
                (1, 1, tk, dv),
                lambda b, h, i, j, f: (b, h, key(j, i, f, b), 0)),
            pl.BlockSpec(
                (1, tq, tk),
                lambda b, h, i, j, f: (b, i, key(j, i, f, b))),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tq, dv), lambda b, h, i, j, f: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _span_kernel, scale=scale, tq=tq, tk=tk, blocks=s // tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SPAN_VMEM_BYTES),
        interpret=interpret,
        name="latent_span_attention",
    )(first, q, k, v, mask)
