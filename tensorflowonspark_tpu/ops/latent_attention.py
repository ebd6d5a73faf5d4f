"""One-token decode attention over a LATENT bank, as one Pallas kernel.

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
