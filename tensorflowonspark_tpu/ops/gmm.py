"""Grouped (ragged) matmul pallas kernels — the dropless-MoE engine.

New TPU-first capability with no reference analogue (the reference has
no expert parallelism at all; SURVEY.md §2.3).  Capacity-factor routing
(`ops/moe.top_k_routing`) pays for static shapes twice: ``CF``× padded
tokens through every expert matmul AND dropped tokens when a group
overflows.  The standard fix (Megablox / MaxText's grouped matmul) is a
kernel that multiplies a *sorted, group-contiguous* token matrix
``[N, D]`` against per-expert weights ``[E, D, F]`` where each row tile
reads exactly its own expert's weights — zero drops, and the only
padding is rounding each group up to one row tile.

Layout contract (produced by ``ops.moe.dropless_layout``): tokens are
sorted by expert; each expert's run starts at a multiple of the row
tile ``bm`` so no tile straddles two experts; ``tile_expert[t]`` names
the owning expert of row tile ``t``.  Pad rows are zero and their
outputs are never gathered back.

Kernel shapes (grid ``(F//bf, T)`` — row tiles innermost so that
consecutive tiles of the same expert reuse the resident weight block;
the full weight matrix is DMA'd exactly once per ``bf`` stripe, and
each row tile of ``x`` once per stripe, ``F / bf`` times):

- forward  ``y[t] = x[t] @ w[tile_expert[t]]``, over ONE stripe of the
  whole width where its blocks and float32 product fit VMEM (each row
  tile and each expert's weights read once), else narrow stripes
- dx       ``dx[t] = dy[t] @ w[tile_expert[t]].T`` with ``w`` read in
  its STORED ``[E, D, F]`` layout (lane-dim contraction, full-``F``
  resident blocks); falls back to a transposed HBM copy + the forward
  kernel only when ``F`` is too wide for VMEM residency
- dw       ``dw[e] = sum_{t: te[t]=e} x[t].T @ dy[t]`` — an output
  block revisited across the contiguous run of ``t`` for each expert,
  zeroed at the first visit (f32 accumulation in VMEM).

Off-TPU the kernels run under ``interpret=True`` (CPU tests), same
posture as ``ops/flash_attention.py``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tensorflowonspark_tpu import compat

#: per-call working-set budget under Mosaic's 16MB scoped-VMEM limit
_VMEM_BUDGET = 14 * 1024 * 1024


def _compiler_params(ndim=2):
    from jax.experimental.pallas import tpu as pltpu

    # weight-dim stripes are independent; the row-tile dim must run in
    # order so (a) weight blocks stay resident across a group's tiles
    # and (b) the dw output block accumulates across its visits.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (ndim - 1) + ("arbitrary",)
    )


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


def _gmm_kernel(te_ref, x_ref, w_ref, y_ref):
    del te_ref  # consumed by the index maps
    y_ref[...] = jnp.dot(
        x_ref[...], w_ref[0], preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)


def _gmm_live_kernel(te_ref, live_ref, x_ref, w_ref, y_ref):
    # tiles past the live ones hold no routed row: no product, and the
    # index maps keep them on the last live tile's blocks (no copy)
    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        _gmm_kernel(te_ref, x_ref, w_ref, y_ref)


def _pick_bf(bm, d, f, bf=None, itemsize=2):
    """Pick a legal f-stripe width.

    Mosaic requires the LAST block dim to be a multiple of 128 or the
    full array dim, and wider stripes amortize per-step overhead — so:
    the largest 128·2^k divisor of ``f`` whose double-buffered working
    set (``itemsize``-byte operands) fits the scoped-VMEM budget,
    capped at ``bf`` when the caller pins one (else 2048), falling
    back to the full width when ``f`` has no such divisor (odd widths
    like 576) or is ≤128 (legality trumps the cap there).
    """
    if bf is not None and f % bf == 0:
        # caller pinned a legal divisor — honor it exactly (tests pin
        # sub-128 stripes to exercise the multi-stripe index maps in
        # interpret mode; hardware callers own their legality)
        return min(bf, f)
    cap = 2048 if bf is None else max(128, bf)

    def working(c):
        return 2 * itemsize * (bm * d + d * c + bm * c)

    best = 0
    c = 128
    while c <= min(f // 2, cap):
        if f % c == 0 and working(c) <= _VMEM_BUDGET:
            best = c
        c *= 2
    return best if best else f


def _forward_bf(bm, d, f, bf=None, itemsize=2):
    """The forward's stripe: the whole width ``f`` where its
    double-buffered blocks and the float32 product fit the budget — one
    stripe, so each row tile of ``x`` is read once and not ``f / bf``
    times — else :func:`_pick_bf`'s, which also keeps a pinned ``bf``
    the caller's."""
    whole = 2 * itemsize * (bm * d + d * f + bm * f) + 4 * bm * f
    if bf is None and whole <= _VMEM_BUDGET:
        return f
    return _pick_bf(bm, d, f, bf, itemsize=itemsize)


def gmm_call(x, w, tile_expert, *, bm=256, bf=None, interpret=None,
             live_tiles=None):
    """Raw forward: ``y[N, F]`` for sorted ``x[N, D]``, ``w[E, D, F]``.

    ``N`` must be ``T*bm`` with ``tile_expert`` of shape ``[T]`` int32;
    differentiate through :func:`grouped_matmul` instead (this primal
    has no registered gradient).  With ``live_tiles`` (``[1]`` int32,
    ``ops.moe.share_layout``) only the first so many row tiles are
    multiplied; the rows of the others are left unwritten and must
    never be read.
    """
    if interpret is None:
        interpret = compat.pallas_interpret()
    n, d = x.shape
    e, dw_, f = w.shape
    assert d == dw_, (x.shape, w.shape)
    assert n % bm == 0, (n, bm)
    t = n // bm
    assert tile_expert.shape == (t,), (tile_expert.shape, t)
    bf = _forward_bf(bm, d, f, bf, itemsize=x.dtype.itemsize)
    assert f % bf == 0, (f, bf)
    # the scalars the index maps see: tile_expert, and live_tiles when
    # only the first so many row tiles hold rows (the others stay on
    # the last live tile's blocks: nothing is copied for them)
    scalars = (tile_expert,) if live_tiles is None else (
        tile_expert, live_tiles)

    def row(ti, s):
        if len(s) == 1:
            return ti
        return jnp.maximum(jnp.minimum(ti, s[1][0] - 1), 0)

    grid_spec = _grid_spec(
        len(scalars),
        (f // bf, t),
        [
            pl.BlockSpec((bm, d), lambda fi, ti, *s: (row(ti, s), 0)),
            pl.BlockSpec((1, d, bf), lambda fi, ti, *s: (s[0][ti], 0, fi)),
        ],
        pl.BlockSpec((bm, bf), lambda fi, ti, *s: (row(ti, s), fi)),
    )
    return pl.pallas_call(
        _gmm_kernel if live_tiles is None else _gmm_live_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, f), x.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="grouped_matmul",
    )(*scalars, x, w)


def _gmm_dxt_kernel(te_ref, dy_ref, w_ref, dx_ref):
    del te_ref  # consumed by the index maps
    # contract the LANE dim of both operands: dy[bm, F] x w[bd, F]^T
    # -> dx[bm, bd]; reads w in its stored [E, D, F] layout
    dx_ref[...] = jax.lax.dot_general(
        dy_ref[...], w_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dx_ref.dtype)


def _pick_bd(bm, d, f, bd, itemsize=2):
    """Output-dim block for the dx kernel: largest 128·2^k divisor of
    ``d`` (or full ``d``) whose double-buffered working set with a
    FULL-``f`` block fits the scoped-VMEM budget.  Full-width f blocks
    mean no stripe loop, so a group's weight block stays resident
    across its consecutive row tiles exactly like the forward.  Returns
    0 when ``f`` is too wide for any resident block (caller falls back
    to the transposed-copy path).  ``itemsize`` is the operand byte
    width (a hardcoded 2 undercounts float32 working sets 2x, so a
    near-budget block fails Mosaic VMEM allocation)."""

    def fits(c):
        return 2 * itemsize * (bm * f + c * f + bm * c) <= _VMEM_BUDGET

    if bd is not None and d % bd == 0 and fits(bd):
        return min(bd, d)
    best = 0
    c = 128
    while c <= min(d, 2048):
        if d % c == 0 and fits(c):
            best = c
        c *= 2
    if not best and fits(d):
        best = d  # small or non-128-divisible d: one full-width block
    return best


def _gmm_dxt_live_kernel(te_ref, live_ref, dy_ref, w_ref, dx_ref):
    # as _gmm_live_kernel: a dead tile is neither multiplied nor copied
    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        _gmm_dxt_kernel(te_ref, dy_ref, w_ref, dx_ref)


def _live_row(ti, scalars):
    """Row-tile index a block map fetches for grid step ``ti``: itself,
    or — where the scalars carry ``live_tiles`` — the last live tile's
    for every dead one, so that nothing is copied for them (as
    ``gmm_call``'s ``row``, which stays where it is: the serving
    cell's kernel text holds its lines)."""
    if len(scalars) == 1:
        return ti
    return jnp.maximum(jnp.minimum(ti, scalars[1][0] - 1), 0)


def gmm_dxt_call(dy, w, tile_expert, *, bm=256, bd=None, interpret=None,
                 live_tiles=None):
    """``dx[N, D] = dy[N, F] @ w[te].T`` reading ``w[E, D, F]`` in its
    STORED layout — the backward's input gradient without materializing
    ``swapaxes(w, 1, 2)`` (a full transposed weight copy in HBM every
    step; ADVICE r4 #4).  Returns None when no resident block exists
    for this ``f`` (then the caller takes the transposed-copy path).
    With ``live_tiles`` the rows of the dead tiles are left unwritten,
    as :func:`gmm_call` leaves them."""
    if interpret is None:
        interpret = compat.pallas_interpret()
    n, f = dy.shape
    e, d, f2 = w.shape
    assert f == f2, (dy.shape, w.shape)
    assert n % bm == 0, (n, bm)
    t = n // bm
    assert tile_expert.shape == (t,), (tile_expert.shape, t)
    bd = _pick_bd(bm, d, f, bd, itemsize=dy.dtype.itemsize)
    if not bd:
        return None
    scalars = (tile_expert,) if live_tiles is None else (
        tile_expert, live_tiles)
    grid_spec = _grid_spec(
        len(scalars),
        (d // bd, t),
        [
            pl.BlockSpec(
                (bm, f), lambda di, ti, *s: (_live_row(ti, s), 0)),
            pl.BlockSpec(
                (1, bd, f), lambda di, ti, *s: (s[0][ti], di, 0)),
        ],
        pl.BlockSpec((bm, bd), lambda di, ti, *s: (_live_row(ti, s), di)),
    )
    return pl.pallas_call(
        _gmm_dxt_kernel if live_tiles is None else _gmm_dxt_live_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), dy.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="grouped_matmul_dx",
    )(*scalars, dy, w)


def _tgmm_kernel(te_ref, x_ref, dy_ref, dw_ref, acc_ref, live_ref=None):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)
    prev = jnp.maximum(ti - 1, 0)
    first = jnp.logical_or(ti == 0, te_ref[ti] != te_ref[prev])

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        acc_ref[...] += jnp.dot(
            x_ref[...].T, dy_ref[...], preferred_element_type=jnp.float32
        )

    if live_ref is None:
        _accumulate()
    else:
        # the dead tiles repeat the last live tile's expert
        # (``ops.moe.share_layout``): they add nothing to its sum, and
        # the last of them flushes it
        pl.when(ti < live_ref[0])(_accumulate)
    nxt = jnp.minimum(ti + 1, nt - 1)
    last = jnp.logical_or(ti == nt - 1, te_ref[nxt] != te_ref[ti])

    @pl.when(last)
    def _flush():
        dw_ref[...] = acc_ref[...][None].astype(dw_ref.dtype)


def _tgmm_live_kernel(te_ref, live_ref, x_ref, dy_ref, dw_ref, acc_ref):
    _tgmm_kernel(te_ref, x_ref, dy_ref, dw_ref, acc_ref, live_ref)


def tgmm_call(x, dy, tile_expert, num_experts, *, bm=256, bd=None,
              bf=None, interpret=None, live_tiles=None):
    """``dw[E, D, F] = segment-sum over row tiles of x[t].T @ dy[t]``.

    The per-expert sum accumulates in an f32 VMEM scratch and flushes
    to the output (in ``x.dtype``) once per expert block — writing an
    f32 ``[E, D, F]`` then casting cost two extra full passes of HBM
    traffic per weight.  Both weight dims are blocked (``bd`` × ``bf``):
    a full-``D`` f32 accumulator at MoE widths exceeds the 16MB
    scoped-VMEM budget.  An expert that owns no row tile this batch
    never has its output block visited (uninitialized memory), so
    absent experts are zeroed explicitly after the kernel.  With
    ``live_tiles`` only the first so many row tiles are read and
    multiplied (what the others hold is never looked at), and an expert
    with no LIVE tile is absent.
    """
    if interpret is None:
        interpret = compat.pallas_interpret()
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    n2, f = dy.shape
    assert n == n2 and n % bm == 0
    t = n // bm
    # both weight dims appear as a LAST block dim here (x's bd, dy's
    # bf, and dw's bf) — legalize each with the same 128-rule picker,
    # then shrink until the (bd, bf) f32 accumulator scratch ALSO fits
    # (the picker budgets the double-buffered blocks only)
    itemsize = x.dtype.itemsize
    bd = _pick_bf(bm, min(bf or 512, f), d, bd, itemsize=itemsize)
    bf = _pick_bf(bm, bd, f, bf, itemsize=itemsize)
    while (
        2 * itemsize * (bm * bd + bm * bf + bd * bf) + 4 * bd * bf
        > _VMEM_BUDGET
    ):
        side = "bd" if bd >= bf else "bf"
        cur = bd if side == "bd" else bf
        # halving a 128·2^k divisor stays legal; full-width (odd) or
        # minimum-width blocks can't shrink further
        if cur < 256 or cur % 256 != 0:
            break
        if side == "bd":
            bd //= 2
        else:
            bf //= 2
    assert d % bd == 0, (d, bd)
    assert f % bf == 0, (f, bf)
    scalars = (tile_expert,) if live_tiles is None else (
        tile_expert, live_tiles)
    grid_spec = _grid_spec(
        len(scalars),
        (d // bd, f // bf, t),
        [
            pl.BlockSpec(
                (bm, bd), lambda di, fi, ti, *s: (_live_row(ti, s), di)),
            pl.BlockSpec(
                (bm, bf), lambda di, fi, ti, *s: (_live_row(ti, s), fi)),
        ],
        pl.BlockSpec(
            (1, bd, bf), lambda di, fi, ti, *s: (s[0][ti], di, fi)
        ),
        scratch_shapes=[pltpu.VMEM((bd, bf), jnp.float32)],
    )
    dw = pl.pallas_call(
        _tgmm_kernel if live_tiles is None else _tgmm_live_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_experts, d, f), x.dtype),
        compiler_params=_compiler_params(ndim=3),
        interpret=interpret,
        name="grouped_matmul_dw",
    )(*scalars, x, dy)
    # zero the rows of experts that own no tile this batch (their output
    # block was never visited and holds uninitialized memory)
    owners = tile_expert if live_tiles is None else jnp.where(
        jnp.arange(t) < live_tiles[0], tile_expert, num_experts)
    present = (
        jnp.zeros((num_experts,), jnp.bool_).at[owners].set(
            True, mode="drop")
    )
    return jnp.where(present[:, None, None], dw, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, tile_expert, bm=256, bf=None):
    """Differentiable grouped matmul on a group-aligned sorted layout.

    ``x [N, D]`` (N = T*bm, tokens sorted+padded by expert),
    ``w [E, D, F]``, ``tile_expert [T]`` → ``y [N, F]``.
    """
    return gmm_call(x, w, tile_expert, bm=bm, bf=bf)


def _grouped_matmul_fwd(x, w, tile_expert, bm, bf):
    return gmm_call(x, w, tile_expert, bm=bm, bf=bf), (x, w, tile_expert)


def _grouped_matmul_bwd(bm, bf, res, dy):
    x, w, tile_expert = res
    dx = gmm_dxt_call(dy, w, tile_expert, bm=bm)
    if dx is None:
        # F too wide for a resident full-width block: pay the HBM
        # transpose copy and reuse the striped forward kernel
        wt = jnp.swapaxes(w, 1, 2)  # [E, F, D]
        dx = gmm_call(dy, wt, tile_expert, bm=bm, bf=bf)
    dw = tgmm_call(
        x, dy, tile_expert, w.shape[0], bm=bm, bf=bf
    ).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul_live(x, w, tile_expert, live_tiles, bm=256):
    """:func:`grouped_matmul` over the first ``live_tiles`` row tiles
    alone (``ops.moe.share_layout``: a chip's share of a layer sizes
    its layout for every row landing here, and about ``held /
    router_experts`` of them do).  Forward, ``dx`` and ``dw`` each skip
    the dead tiles — no product, no copy — so their rows of ``y`` and
    ``dx`` are never written and must never be read (the layout's
    gathers never do), and what ``x`` and ``dy`` hold there is never
    looked at; ``dw`` of an expert with no live tile is zero."""
    return gmm_call(x, w, tile_expert, bm=bm, live_tiles=live_tiles)


def _grouped_matmul_live_fwd(x, w, tile_expert, live_tiles, bm):
    y = gmm_call(x, w, tile_expert, bm=bm, live_tiles=live_tiles)
    return y, (x, w, tile_expert, live_tiles)


def _grouped_matmul_live_bwd(bm, res, dy):
    x, w, tile_expert, live_tiles = res
    dx = gmm_dxt_call(dy, w, tile_expert, bm=bm, live_tiles=live_tiles)
    if dx is None:
        dx = gmm_call(dy, jnp.swapaxes(w, 1, 2), tile_expert, bm=bm,
                      live_tiles=live_tiles)
    dw = tgmm_call(
        x, dy, tile_expert, w.shape[0], bm=bm, live_tiles=live_tiles
    ).astype(w.dtype)
    return dx, dw, None, None


grouped_matmul_live.defvjp(
    _grouped_matmul_live_fwd, _grouped_matmul_live_bwd)


def gmm_reference(x, w, tile_expert, bm=256):
    """Pure-jnp numerics reference: per-tile dense dot against the
    owning expert's weights (tests compare the kernels to this)."""
    n, d = x.shape
    t = n // bm
    xt = x.reshape(t, bm, d)
    wt = w[tile_expert]  # [T, D, F]
    y = jnp.einsum(
        "tbd,tdf->tbf",
        xt.astype(jnp.float32),
        wt.astype(jnp.float32),
    )
    return y.reshape(n, -1).astype(x.dtype)
