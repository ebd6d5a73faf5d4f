"""Block-walking decode attention: one token a slot over a physical
KV page pool, or over contiguous per-slot banks read as one.

The continuous-batching engine's decode hot loop used to read
*contiguous per-slot banks* ``[slots, bank_len, heads, dim]`` whole:
every cached-prefix admit paid a physical segment copy into the
admitted lane, a block shared by N slots occupied N copies of HBM, and
every step read every bank's whole length under a mask.  This kernel
reads blocks, and only the live ones:

- K/V live in ONE physical pool per layer, ``[num_pages, page_tokens,
  kv_heads, head_dim]`` (:class:`~tensorflowonspark_tpu.prefix_cache.
  PagePool` allocates the page indices);
- each slot addresses the pool through a per-slot **block table**
  ``[slots, blocks_per_slot]`` of page indices — a cached admit
  *installs indices* (host bookkeeping, zero device copies) and one
  physical page serves every table that references it;
- a contiguous bank ``[slots, bank_len, ...]`` IS such a pool by a
  free reshape (block ``j`` of slot ``b`` at page ``b * blocks + j``,
  the identity table), so both layouts share the one kernel body;
- the kernel is a flash-style online softmax that leaves the pools in
  HBM and copies a slot's LIVE blocks itself — the blocks its span
  ``[start, length)`` touches, cut by the window — through a double
  buffer: block ``i + 1`` (or the next slot's first) is in flight
  while block ``i`` is computed.  The table lookup IS the copy's
  source, so the gather is the DMA schedule, and a block outside the
  span is neither fetched nor computed.

Handles GQA (every query head meets every (token, kv head) row of a
block in one matmul; other heads' columns are masked), sliding-window
attention (whole pages behind the horizon are skipped, in-page
positions masked), a per-slot first visible position (the pad region
of a left-padded admit), int8-KV dequant scales (logit/probability
scaling, the same factored identities ``dot_attention`` uses), and
ragged final pages (positions past the slot's live length masked via
the prefetched ``lengths``).

Three entry points:

- :func:`paged_attention` — the pallas kernel for single-token decode
  steps (``q [B, H, D]``), the bandwidth-bound hot loop.  Off-TPU it
  runs under ``interpret=True`` (via the :mod:`~tensorflowonspark_tpu.
  compat` pallas shims) so CPU tier-1 exercises the real kernel path;
  tiny test shapes are legal there — hardware callers own Mosaic tile
  legality for their head/page geometry, like the gmm kernels.
- :func:`bank_attention` — the same kernel over contiguous banks
  (``positions``, ``pad_start``), in blocks of :func:`bank_block`
  tokens; the per-slot decode branch of ``models/transformer.py``
  calls it where :func:`bank_block` finds a block size.
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


def _live_blocks(length, start, page_tokens, window):
    """``(lo, first, count)``: the first position a slot's query can
    see, and the blocks it can see — the query sits at ``length - 1``
    and attends ``[start, length)``, cut to the last ``window``
    positions.  Scalar arithmetic only: what is copied and what is
    masked are reckoned from the one place."""
    lo = start
    if window:
        lo = jnp.maximum(lo, length - window)
    first = lo // page_tokens
    return lo, first, (length - 1) // page_tokens - first + 1


def _decode_kernel(tbl_ref, len_ref, start_ref, q_ref, k_hbm, v_hbm, *rest,
                   slots, page_tokens, hkv, group, scale, window,
                   int8_scales, ring=False):
    """The online softmax of ``slots`` slots a grid step, each over its
    own live blocks.  ``rest`` is ``[ks_hbm, vs_hbm,] o_ref, kbuf,
    vbuf, [ksbuf, vsbuf,] sem, par, own_ref``.

    The pools stay in HBM; the kernel walks a slot's live blocks
    itself, block ``i + 1`` copied into one half of a double buffer
    while block ``i`` is computed from the other, and the NEXT slot's
    first block started under this slot's last — so a dead block is
    neither fetched nor a step of anything.  ``par`` (SMEM) carries
    the buffer half from one grid step to the next; several slots
    share a grid step so that going from one slot to the next costs a
    loop iteration, not a pipeline stage with no copy in flight.

    A K/V block arrives as ``[T*Hkv, D]``, one row per (token, kv
    head) — the free view of ``[T, Hkv, D]`` (the stored layout tiles
    its last two dims, so folding the heads into the lane dim instead
    would copy the pool).  Every query head meets every row in ONE
    matmul each way; the columns of another kv head are masked like
    positions outside the span (``own_ref``: 0 on a row's own head,
    ``NEG_INF`` elsewhere, built once), so their probabilities are
    zero and ``p @ v`` sums a head's own rows only — no per-head
    slicing or transposes of the block.

    ``ring``: a slot's table is a RING — logical block ``j`` (positions
    ``[j*T, (j+1)*T)``) lives in entry ``j % NB``.  The span, the
    blocks walked and the masks are reckoned in logical positions as
    ever; only the copy's source wraps.  The caller keeps the ring
    long enough that no two live blocks share an entry and no visible
    position has been overwritten."""
    from jax.experimental.pallas import tpu as pltpu

    if int8_scales:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, par,
         own_ref) = rest
        streams = ((k_hbm, kbuf), (v_hbm, vbuf), (ks_hbm, ksbuf),
                   (vs_hbm, vsbuf))
    else:
        o_ref, kbuf, vbuf, sem, par, own_ref = rest
        streams = ((k_hbm, kbuf), (v_hbm, vbuf))
    g = pl.program_id(0)
    total = pl.num_programs(0) * slots
    h = hkv * group
    t = page_tokens
    n = t * hkv
    d = q_ref.shape[-1]

    def live(slot):
        return _live_blocks(len_ref[slot], start_ref[slot], t, window)

    def copies(slot, block, half):
        page = tbl_ref[slot, block % tbl_ref.shape[1] if ring else block]
        return [
            pltpu.make_async_copy(
                hbm.at[page], buf.at[half], sem.at[i, half]
            )
            for i, (hbm, buf) in enumerate(streams)
        ]

    @pl.when(g == 0)
    def _prologue():
        col = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0)
        own_ref[...] = jnp.where(
            col % hkv == row // group, 0.0, NEG_INF
        )
        par[0] = 0
        for c in copies(0, live(0)[1], 0):
            c.start()

    tok = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // hkv

    def slot_step(s, half0):
        b = g * slots + s
        q = q_ref[s]  # [H, D]
        length = len_ref[b]
        lo, first, count = live(b)

        def block_step(i, carry):
            m_prev, l_prev, acc = carry
            half = (half0 + i) % 2

            @pl.when(i + 1 < count)
            def _next_block():
                for c in copies(b, first + i + 1, 1 - half):
                    c.start()

            @pl.when(jnp.logical_and(i + 1 == count, b + 1 < total))
            def _next_slot():
                nxt = jnp.minimum(b + 1, total - 1)
                for c in copies(nxt, live(nxt)[1], 1 - half):
                    c.start()

            for c in copies(b, first + i, half):
                c.wait()
            k = kbuf[half].astype(q.dtype)  # [T*Hkv, D]
            v = vbuf[half].astype(q.dtype)
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, T*Hkv]
            if int8_scales:
                # q·(k*ks) == (q·k)*ks: one scale a column, [1, T*Hkv]
                logits = logits * ksbuf[half][:, :n]
            base = (first + i) * t
            seen = jnp.logical_and(tok >= lo - base, tok < length - base)
            # a select, not a sum, for the positions: what lies outside
            # the span may be anything, NaN included
            logits = jnp.where(seen, logits * scale, NEG_INF) + own_ref[...]
            m_cur = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            # every live block holds a visible position, so m_new is a
            # real logit and the masked columns underflow to exactly 0
            p = jnp.exp(logits - m_new)  # [H, T*Hkv]
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if int8_scales:
                p = p * vsbuf[half][:, :n]  # Σ p·(v*vs) == Σ (p*vs)·v
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, D]
            return m_new, l_new, acc * alpha + pv

        _, l_fin, acc = jax.lax.fori_loop(
            0, count, block_step,
            (jnp.full((h, 1), NEG_INF, jnp.float32),
             jnp.zeros((h, 1), jnp.float32),
             jnp.zeros((h, d), jnp.float32)),
        )
        o_ref[s] = (acc / l_fin).astype(o_ref.dtype)
        return (half0 + count) % 2

    par[0] = jax.lax.fori_loop(0, slots, slot_step, par[0])


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    starts=None, scale=None, window=0, k_scale_pool=None,
                    v_scale_pool=None, interpret=None, ring=False):
    """Single-token decode attention over a paged KV pool.

    Args:
      q: ``[B, H, D]`` — one query per slot (the token being decoded,
        whose K/V the caller already wrote at position
        ``lengths[b] - 1`` of slot ``b``'s table span).
      k_pool, v_pool: ``[P, T, Hkv, D]`` physical page pools; ``Hkv``
        divides ``H`` (GQA).  int8 pools compose with the scale pools.
      block_tables: ``[B, NB]`` int32 page indices — slot ``b``'s
        logical block ``j`` lives in physical page
        ``block_tables[b, j]``.  Entries outside the live span are
        never dereferenced (neither fetched nor computed).
      lengths: ``[B]`` int32 — slot ``b``'s query sits at position
        ``lengths[b] - 1`` (``>= 1``) and attends
        ``[starts[b], lengths[b])``, its own position included.
      starts: ``[B]`` int32 first visible position of each slot
        (``< lengths[b]``; default 0) — the pad region of a
        left-padded admit lies below it.  Blocks wholly below it are
        skipped like blocks past the length.
      scale: logit scale (default ``D ** -0.5``).
      window: sliding-window width (0 = full causal) — pages fully
        behind the horizon are skipped, partial pages masked.
      k_scale_pool, v_scale_pool: ``[P, T, Hkv, 1]`` f32 dequant
        scales for int8 pools (per-position/per-head, the int8-KV
        cache layout).
      interpret: force/deny interpret mode (default: off-TPU).
      ring: each table row is a ring of ``NB`` blocks: logical block
        ``j`` is entry ``j % NB`` (``lengths`` and ``starts`` stay
        logical and may pass ``NB * T``; needs a ``window`` that, with
        a block's slack, fits the ring).
    Returns ``[B, H, D]`` in ``q.dtype``.
    """
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
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)
    assert starts.shape == (b,), starts.shape
    if ring and not 0 < window <= (nb - 1) * t:
        raise ValueError(
            "a ring of {0} blocks of {1} holds a window of at most "
            "{2}, got {3}".format(nb, t, (nb - 1) * t, window))
    return _decode_call(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(lengths, jnp.int32), jnp.asarray(starts, jnp.int32),
        k_scale_pool, v_scale_pool, scale=float(scale),
        window=int(window), interpret=bool(interpret), ring=bool(ring),
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "interpret", "ring"))
def _decode_call(q, k_pool, v_pool, block_tables, lengths, starts,
                 k_scale_pool, v_scale_pool, *, scale, window, interpret,
                 ring=False):
    """The kernel's ``pallas_call``, under a jit of its own: a model
    calls it once a layer with the same shapes, and is then traced and
    lowered for Mosaic once, not once a layer (1.6 s at 16 layers)."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    p, t, hkv, _ = k_pool.shape
    group = h // hkv
    int8_scales = k_scale_pool is not None
    # slots a grid step: enough that the step's fixed cost is shared
    slots = max(g for g in (8, 4, 2, 1) if b % g == 0)
    kernel = functools.partial(
        _decode_kernel,
        slots=slots, page_tokens=t, hkv=hkv, group=group,
        scale=scale, window=window, int8_scales=int8_scales, ring=ring,
    )
    slot_map = lambda gi, tbl, ln, st: (gi, 0, 0)  # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # rows = (token, kv head): merges major dims only, a free view
    operands = [
        q, k_pool.reshape(p, t * hkv, d), v_pool.reshape(p, t * hkv, d),
    ]
    scratch = [
        _scratch((2, t * hkv, d), k_pool.dtype),
        _scratch((2, t * hkv, d), v_pool.dtype),
    ]
    if int8_scales:
        # one scale per row of a block, as a lane vector the logits
        # broadcast against (this view copies the small scale pools),
        # padded to whole lanes: a copy's slice may not split one
        lanes = -(-t * hkv // LANE) * LANE
        operands += [
            jnp.pad(sp.reshape(p, 1, t * hkv),
                    ((0, 0), (0, 0), (0, lanes - t * hkv)))
            for sp in (k_scale_pool, v_scale_pool)
        ]
        scratch += [_scratch((2, 1, lanes), jnp.float32)] * 2
    grid_spec = _grid_spec(
        3,
        (b // slots,),
        [pl.BlockSpec((slots, h, d), slot_map)]
        + [hbm] * (len(operands) - 1),
        pl.BlockSpec((slots, h, d), slot_map),
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((len(operands) - 1, 2)),
            pltpu.SMEM((1,), jnp.int32),
            _scratch((h, t * hkv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential: a slot starts the next slot's first copy
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="block_decode_attention",
    )(block_tables, lengths, starts, *operands)


#: bank block sizes, in order of preference.  A K+V block of 256
#: tokens is 1 MB at 8 kv heads of 128 (double-buffered: 2 MB of VMEM);
#: in the serving cell 256 read 47% of the banks and gave the shortest
#: decode step, 128 read 42% but paid for twice the copies, 512 read
#: 55% (PERF.md section 6, PR 27)
BANK_BLOCKS = (256, 128)


def bank_block(bank_len, head_dim, dtype):
    """Tokens a copy of :func:`bank_attention` reads from a
    contiguous bank: the first of :data:`BANK_BLOCKS` that divides
    ``bank_len`` and is tile-legal (:func:`check_tiles`), or None —
    the caller keeps its einsum path then."""
    for t in BANK_BLOCKS:
        if bank_len % t == 0:
            try:
                check_tiles(t, head_dim, dtype)
            except TileLegalityError:
                break
            return t
    return None


def bank_attention(q, k_bank, v_bank, positions, pad_start, *, scale=None,
                   window=0, k_scale=None, v_scale=None, interpret=None,
                   ring=False):
    """Single-token decode attention over contiguous per-slot banks
    ``[B, S, Hkv, D]``, reading only each slot's live span.

    A bank is a page pool by a free reshape — block ``j`` of slot
    ``b`` is page ``b * (S // T) + j`` — so this is
    :func:`paged_attention` over the identity table with
    ``lengths = positions + 1`` and ``starts = pad_start``: blocks
    outside ``[pad_start, position]`` (and behind the window) are
    neither fetched nor computed.  ``positions [B]`` is where each
    slot's query sits (its K/V already written there); a slot always
    sees its own position, so an idle lane (``pad_start`` past its
    position) reads one block and attends itself alone.  Requires
    :func:`bank_block` to find a block size for the bank.

    ``ring``: the bank is a ring of ``S`` rows, position ``p`` in row
    ``p % S`` (``positions`` may pass ``S``); ``S`` is at least the
    window rounded out to whole blocks plus one block.
    """
    b, s, hkv, d = k_bank.shape
    t = bank_block(s, d, k_bank.dtype)
    if t is None:
        raise TileLegalityError(
            "no block of {0} divides a bank of {1} tokens legally for "
            "{2}".format(BANK_BLOCKS, s, jnp.dtype(k_bank.dtype).name)
        )
    nb = s // t

    def pool(bank):
        return None if bank is None else bank.reshape(
            (b * nb, t) + bank.shape[2:]
        )

    return paged_attention(
        q, pool(k_bank), pool(v_bank),
        jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb),
        positions + 1, starts=jnp.minimum(pad_start, positions),
        scale=scale, window=window, k_scale_pool=pool(k_scale),
        v_scale_pool=pool(v_scale), interpret=interpret, ring=ring,
    )


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
