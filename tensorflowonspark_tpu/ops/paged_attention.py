"""Paged (block-gather) decode attention over a physical KV page pool.

The continuous-batching engine's decode hot loop used to read
*contiguous per-slot banks* ``[slots, bank_len, heads, dim]``: every
cached-prefix admit paid a physical segment copy into the admitted
lane, and a block shared by N slots occupied N copies of HBM.  This
kernel makes attention consume the prefix cache's block pool DIRECTLY:

- K/V live in ONE physical pool per layer, ``[num_pages, page_tokens,
  kv_heads, head_dim]`` (:class:`~tensorflowonspark_tpu.prefix_cache.
  PagePool` allocates the page indices);
- each slot addresses the pool through a per-slot **block table**
  ``[slots, blocks_per_slot]`` of page indices — a cached admit
  *installs indices* (host bookkeeping, zero device copies) and one
  physical page serves every table that references it;
- the kernel is a flash-style online softmax whose k/v grid dimension
  walks the slot's block table via scalar-prefetch index maps (the
  same Mosaic mechanism :mod:`.gmm` uses for expert tiles): block j of
  slot b fetches physical page ``table[b, j]`` through the BlockSpec,
  so the gather IS the DMA schedule — no materialized contiguous copy.

Handles GQA (grouped queries reshape per kv head), sliding-window
attention (whole pages behind the horizon are skipped, in-page
positions masked), int8-KV dequant scales (logit/probability scaling,
the same factored identities ``dot_attention`` uses), and ragged final
pages (positions past the slot's live length masked via the prefetched
``lengths``).

Two entry points:

- :func:`paged_attention` — the pallas kernel for single-token decode
  steps (``q [B, H, D]``), the bandwidth-bound hot loop.  Off-TPU it
  runs under ``interpret=True`` (via the :mod:`~tensorflowonspark_tpu.
  compat` pallas shims) so CPU tier-1 exercises the real kernel path;
  tiny test shapes are legal there — hardware callers own Mosaic tile
  legality for their head/page geometry, like the gmm kernels.
- :func:`paged_gather_attention` — the jnp fallback for MULTI-token
  query spans (suffix prefill at canonical positions, speculative
  verify blocks): gathers the table's pages into a transient
  contiguous view and reuses :func:`..attention.dot_attention`'s
  masked einsums.  Those paths are compute-bound (prefill) or
  verify-batched, so the transient gather costs what the contiguous
  layout *stored permanently*.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tensorflowonspark_tpu import compat

NEG_INF = -1e30  # finite mask sentinel: exp() underflows to 0, no NaNs

#: Mosaic's minimum tile is (sublane, lane) with lane fixed at 128 and
#: the sublane minimum set by element width: 4-byte types pack 8
#: sublanes, 2-byte 16, 1-byte 32.
LANE = 128
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}


class TileLegalityError(ValueError):
    """A paged-KV geometry that Mosaic cannot tile on hardware.

    Raised by :func:`check_tiles` at *build* time (``serving_builder``
    with ``kv_layout="paged"``) so an off-bar ``page_tokens`` /
    ``head_dim`` choice fails with a named, actionable error instead of
    a Mosaic lowering failure deep inside the first decode dispatch.
    """


def min_tile(dtype):
    """Mosaic minimum ``(sublane, lane)`` tile for ``dtype``."""
    itemsize = jnp.dtype(dtype).itemsize
    try:
        return (_SUBLANE_BY_ITEMSIZE[itemsize], LANE)
    except KeyError:
        raise TileLegalityError(
            "no Mosaic tile rule for dtype {0} (itemsize {1})".format(
                jnp.dtype(dtype).name, itemsize
            )
        )


def check_tiles(page_tokens, head_dim, dtype):
    """Validate a paged-KV page geometry against Mosaic tile minimums.

    The kernel's per-page K/V block is ``[page_tokens, kv_heads,
    head_dim]``; Mosaic tiles the trailing two dims of each 2D slice as
    (sublane, lane) = (page_tokens, head_dim) after the head dim is
    folded, so hardware legality requires ``head_dim`` to be a multiple
    of the 128-wide lane and ``page_tokens`` a multiple of the dtype's
    sublane minimum (8 for 4-byte, 16 for 2-byte, 32 for 1-byte
    elements).  CPU interpret mode accepts anything — this preflight
    exists so builds destined for TPU fail early with a named error.

    Returns ``{"sublane": S, "lane": L}`` (the minimums checked
    against) when legal; raises :class:`TileLegalityError` otherwise.
    """
    sub, lane = min_tile(dtype)
    page_tokens = int(page_tokens)
    head_dim = int(head_dim)
    problems = []
    if page_tokens <= 0 or page_tokens % sub != 0:
        problems.append(
            "page_tokens={0} must be a positive multiple of the "
            "{1}-dtype sublane minimum {2}".format(
                page_tokens, jnp.dtype(dtype).name, sub
            )
        )
    if head_dim <= 0 or head_dim % lane != 0:
        problems.append(
            "head_dim={0} must be a positive multiple of the lane "
            "width {1}".format(head_dim, lane)
        )
    if problems:
        raise TileLegalityError(
            "paged-KV geometry illegal for Mosaic: " + "; ".join(problems)
        )
    return {"sublane": sub, "lane": lane}


def _grid_spec(num_scalar_prefetch, grid, in_specs, out_specs,
               scratch_shapes=()):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=list(scratch_shapes),
    )


def _scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  num_blocks, page_tokens, hkv, group, scale, window,
                  int8_scales):
    """One (slot, page) grid step of the online softmax.  ``rest`` is
    ``[ks_ref, vs_ref,] o_ref, acc_ref, m_ref, l_ref``."""
    if int8_scales:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    h = hkv * group
    t = page_tokens

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    base = j * t
    relevant = base < length
    if window:
        # the query sits at position length-1; pages entirely behind
        # the horizon (base + t <= length - window) contribute nothing
        relevant = jnp.logical_and(relevant, base + t > length - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0]  # [H, D]
        k = k_ref[0].astype(q.dtype)  # [T, Hkv, D] (int8 converts bare)
        v = v_ref[0].astype(q.dtype)
        d = q.shape[-1]
        q3 = q.reshape(hkv, group, d)
        kh = jnp.swapaxes(k, 0, 1)  # [Hkv, T, D]
        logits = jax.lax.dot_general(
            q3, kh, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, T]
        if int8_scales:
            ks = jnp.swapaxes(ks_ref[0][:, :, 0], 0, 1)  # [Hkv, T]
            logits = logits * ks[:, None, :]
        logits = logits * scale
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, t), 2
        )
        keep = pos < length
        if window:
            keep = jnp.logical_and(keep, pos >= length - window)
        logits = jnp.where(keep, logits, NEG_INF)
        lg = logits.reshape(h, t)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(lg, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(lg - m_new)  # [H, T]
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        p3 = p.reshape(hkv, group, t)
        if int8_scales:
            vs = jnp.swapaxes(vs_ref[0][:, :, 0], 0, 1)  # [Hkv, T]
            p3 = p3 * vs[:, None, :]
        vh = jnp.swapaxes(v, 0, 1)  # [Hkv, T, D]
        pv = jax.lax.dot_general(
            p3.astype(v.dtype), vh, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, D]
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(h, d)

    @pl.when(j == num_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    scale=None, window=0, k_scale_pool=None,
                    v_scale_pool=None, interpret=None):
    """Single-token decode attention over a paged KV pool.

    Args:
      q: ``[B, H, D]`` — one query per slot (the token being decoded,
        whose K/V the caller already wrote at position
        ``lengths[b] - 1`` of slot ``b``'s table span).
      k_pool, v_pool: ``[P, T, Hkv, D]`` physical page pools; ``Hkv``
        divides ``H`` (GQA).  int8 pools compose with the scale pools.
      block_tables: ``[B, NB]`` int32 page indices — slot ``b``'s
        logical block ``j`` lives in physical page
        ``block_tables[b, j]``.  Entries past the live length must
        still be VALID indices (the engine points idle/unused entries
        at the reserved trash page); they are masked, not skipped.
      lengths: ``[B]`` int32 — tokens visible to slot ``b``'s query
        (``>= 1``; the query attends positions ``[0, lengths[b])``,
        its own slot included).
      scale: logit scale (default ``D ** -0.5``).
      window: sliding-window width (0 = full causal) — pages fully
        behind the horizon are skipped, partial pages masked.
      k_scale_pool, v_scale_pool: ``[P, T, Hkv, 1]`` f32 dequant
        scales for int8 pools (per-position/per-head, the int8-KV
        cache layout).
      interpret: force/deny interpret mode (default: off-TPU).
    Returns ``[B, H, D]`` in ``q.dtype``.
    """
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = compat.pallas_interpret()
    b, h, d = q.shape
    p, t, hkv, dk = k_pool.shape
    assert dk == d, (q.shape, k_pool.shape)
    assert v_pool.shape == k_pool.shape, (k_pool.shape, v_pool.shape)
    if h % hkv != 0:
        raise ValueError(
            "query heads ({0}) must be a multiple of kv heads "
            "({1})".format(h, hkv)
        )
    nb = block_tables.shape[1]
    assert block_tables.shape == (b, nb), block_tables.shape
    assert lengths.shape == (b,), lengths.shape
    int8_scales = k_scale_pool is not None
    if int8_scales and v_scale_pool is None:
        raise ValueError("k_scale_pool needs v_scale_pool (and vice versa)")
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5

    kernel = functools.partial(
        _paged_kernel,
        num_blocks=nb, page_tokens=t, hkv=hkv, group=group,
        scale=scale, window=int(window), int8_scales=int8_scales,
    )
    page_map = lambda bi, j, tbl, ln: (tbl[bi, j], 0, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, h, d), lambda bi, j, tbl, ln: (bi, 0, 0)),
        pl.BlockSpec((1, t, hkv, d), page_map),
        pl.BlockSpec((1, t, hkv, d), page_map),
    ]
    operands = [q, k_pool, v_pool]
    if int8_scales:
        in_specs += [
            pl.BlockSpec((1, t, hkv, 1), page_map),
            pl.BlockSpec((1, t, hkv, 1), page_map),
        ]
        operands += [k_scale_pool, v_scale_pool]
    grid_spec = _grid_spec(
        2,
        (b, nb),
        in_specs,
        pl.BlockSpec((1, h, d), lambda bi, j, tbl, ln: (bi, 0, 0)),
        scratch_shapes=[
            _scratch((h, d), jnp.float32),
            _scratch((h, 1), jnp.float32),
            _scratch((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32), *operands)


def gather_pool(pool, block_tables, span=None):
    """Materialize per-slot contiguous banks from a paged pool:
    ``[P, T, Hkv, Dx]`` gathered through ``[B, NB]`` tables →
    ``[B, NB*T, Hkv, Dx]`` (sliced to ``span`` positions when given,
    so downstream einsum shapes match the contiguous layout's banks
    exactly — bit-identical masks and reductions)."""
    b, nb = block_tables.shape
    t = pool.shape[1]
    g = jnp.take(pool, block_tables.reshape(-1), axis=0)
    g = g.reshape((b, nb * t) + pool.shape[2:])
    return g[:, :span] if span is not None else g


def paged_gather_attention(q, k_pool, v_pool, block_tables, positions, *,
                           span=None, scale=None, window=0,
                           k_scale_pool=None, v_scale_pool=None):
    """Multi-token-query paged attention via gather + masked einsums.

    The canonical-position prefill and speculative-verify paths feed
    ``S > 1`` contiguous query rows per slot; they are compute-bound,
    so a transient gather of the slot's pages into contiguous banks
    (what the contiguous layout stored *permanently*) plus
    :func:`..attention.dot_attention` is the right tool — and reusing
    the exact einsum/mask graph keeps those paths bit-identical to the
    contiguous layout (the paged-vs-contiguous token-exactness tests
    rely on it).

    ``q`` is ``[B, S, H, D]``; ``positions`` ``[B, S]`` gives each
    query row's absolute cache position (its causal horizon).
    """
    from tensorflowonspark_tpu.ops.attention import dot_attention

    k = gather_pool(k_pool, block_tables, span)
    v = gather_pool(v_pool, block_tables, span)
    ks = (
        gather_pool(k_scale_pool, block_tables, span)
        if k_scale_pool is not None else None
    )
    vs = (
        gather_pool(v_scale_pool, block_tables, span)
        if v_scale_pool is not None else None
    )
    kpos = jnp.arange(k.shape[1])
    qpos = positions  # [B, S]
    vis = kpos[None, None, :] <= qpos[:, :, None]
    if window:
        vis = jnp.logical_and(
            vis, kpos[None, None, :] > qpos[:, :, None] - window
        )
    mask = jnp.where(vis, 0.0, -jnp.inf)[:, None]
    return dot_attention(
        q, k, v, causal=False, scale=scale, mask=mask,
        k_scale=ks, v_scale=vs,
    )
