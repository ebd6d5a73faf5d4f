"""Mixture-of-Experts routing: top-k gating with capacity.

New TPU-first capability; the reference has no expert parallelism
(SURVEY.md §2.3 'Tensor/Pipeline/Sequence/Expert/Context parallelism:
absent').

Design (Switch/GShard-style dense dispatch): routing produces a
``dispatch`` one-hot tensor ``[G, E, C]`` (token -> expert slot) and a
``combine`` tensor of gate weights.  Expert compute is then two einsums
against expert-stacked weights ``[E, ...]`` — *static shapes*, which is
the whole trick on TPU: token counts per expert vary at runtime, but
capacity ``C`` fixes the tensor shapes so XLA can tile the MXU and
insert the expert-axis all-to-alls itself when ``E`` is sharded on the
``expert`` mesh axis.  Tokens over capacity are dropped (standard
Switch behavior); the auxiliary load-balancing loss pushes the router
toward uniform load so drops stay rare.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


def _router_probs_and_aux(router_logits, rng, jitter_eps):
    """Shared routing head: optional multiplicative logit jitter, f32
    softmax, and the Switch load-balance aux loss (eq. 4:
    ``E * sum_e f_e * p_e`` with f_e the top-1 fraction, p_e the mean
    prob).  Every routing variant MUST use this so the paths the
    parity tests compare can never diverge."""
    g, e = router_logits.shape
    if rng is not None and jitter_eps > 0:
        noise = jax.random.uniform(
            rng, router_logits.shape, minval=1.0 - jitter_eps,
            maxval=1.0 + jitter_eps,
        )
        router_logits = router_logits * noise
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(f * p)
    return probs, aux_loss


def top_k_gating(router_logits, num_experts, capacity, k=2, rng=None,
                 jitter_eps=0.0):
    """Compute dispatch/combine tensors for top-k routing.

    Args:
      router_logits: ``[G, E]`` per-token expert scores (G = flattened
        tokens).
      capacity: per-expert slot count ``C``.
      k: number of experts per token (1 = Switch, 2 = GShard default).
      rng, jitter_eps: optional multiplicative logit jitter for
        exploration during training.

    Returns ``(dispatch [G, E, C] float, combine [G, E, C] float,
    aux_loss scalar)``.  ``sum(combine, axis=(1, 2))`` is each token's
    total gate weight (< 1 when some of its experts overflowed).
    """
    g, e = router_logits.shape
    probs, aux_loss = _router_probs_and_aux(
        router_logits, rng, jitter_eps
    )

    dispatch = jnp.zeros((g, e, capacity), jnp.float32)
    combine = jnp.zeros((g, e, capacity), jnp.float32)
    remaining = probs
    # experts fill in priority order: k-th choices only take slots the
    # earlier choices left (cumsum position accounting per expert)
    used = jnp.zeros((e,), jnp.int32)  # slots consumed by earlier choices
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)  # [G]
        gate = jnp.take_along_axis(
            remaining, choice[:, None], axis=-1
        )[:, 0]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [G, E]
        # position of each token within its chosen expert's queue
        pos_within = (
            jnp.cumsum(onehot, axis=0) - onehot
        )  # [G, E]: tokens ahead of me with same choice
        pos = jnp.sum(pos_within * onehot, axis=-1).astype(jnp.int32) + (
            used[choice]
        )
        fits = pos < capacity
        slot = jnp.clip(pos, 0, capacity - 1)
        slot_onehot = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
        mask = (fits[:, None, None].astype(jnp.float32) *
                onehot[..., None] * slot_onehot[:, None, :])  # [G, E, C]
        dispatch = dispatch + mask
        combine = combine + mask * gate[:, None, None]
        used = used + jnp.sum(
            onehot * fits[:, None].astype(jnp.float32), axis=0
        ).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)  # mask chosen expert out

    # renormalize combine over the k gates a token actually landed
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine, aux_loss


def top_k_routing(router_logits, num_experts, capacity, k=2, rng=None,
                  jitter_eps=0.0):
    """Index-based top-k routing: the same slot assignment as
    :func:`top_k_gating` but returned as per-token indices instead of
    ``[G, E, C]`` one-hot tensors.

    The dense dispatch/combine einsums cost ``G*E*C*D`` MXU FLOPs each —
    at bench shapes that approached the expert FFN compute itself for
    what is semantically a permutation.  With indices, dispatch is ONE
    row-gather (``[E*C, D]``) through an inverse slot→token map and
    combine is a ``[G, k, D]`` gather times gate weights: O(tokens·D)
    memory movement, zero matmul FLOPs.

    Returns ``(experts [G,k] i32, slots [G,k] i32, gates [G,k] f32
    (0 where dropped; renormalized over landed choices), aux_loss)``.
    Slot assignments are identical to the dense path: within a choice
    round tokens take their expert's slots in order, later rounds start
    after earlier rounds' claims, overflow drops.
    """
    g, e = router_logits.shape
    probs, aux_loss = _router_probs_and_aux(
        router_logits, rng, jitter_eps
    )

    remaining = probs
    used = jnp.zeros((e,), jnp.int32)
    experts, slots, gates = [], [], []
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)  # [G]
        gate = jnp.take_along_axis(
            remaining, choice[:, None], axis=-1
        )[:, 0]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)
        pos_within = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(pos_within * onehot, axis=-1).astype(jnp.int32) + (
            used[choice]
        )
        fits = pos < capacity
        experts.append(choice.astype(jnp.int32))
        slots.append(jnp.clip(pos, 0, capacity - 1))
        gates.append(gate * fits.astype(jnp.float32))
        used = used + jnp.sum(
            onehot * fits[:, None].astype(jnp.float32), axis=0
        ).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)

    experts = jnp.stack(experts, axis=1)
    slots = jnp.stack(slots, axis=1)
    gates = jnp.stack(gates, axis=1)
    denom = jnp.sum(gates, axis=1, keepdims=True)
    gates = gates / jnp.maximum(denom, 1e-9)
    return experts, slots, gates, aux_loss


def dispatch_gather(x, experts, slots, gates, num_experts, capacity):
    """Build expert batches ``[E, C, D]`` from ``x [G, D]`` with one
    row-gather through the inverse slot→token map (no ``[G,E,C]``
    tensor, no matmul).  Dropped/unfilled slots read a zero row."""
    g, d = x.shape
    flat = (experts * capacity + slots).reshape(-1)  # [G*k]
    valid = (gates > 0.0).reshape(-1)
    # inverse map: slot -> source token (sentinel g = the zero row);
    # valid (expert, slot) pairs are unique by construction, invalid
    # entries park on a dummy slot that gets trimmed
    flat = jnp.where(valid, flat, num_experts * capacity)
    token_ids = jnp.repeat(
        jnp.arange(g, dtype=jnp.int32), experts.shape[1]
    )
    slot_token = jnp.full(
        (num_experts * capacity + 1,), g, jnp.int32
    ).at[flat].set(token_ids)[:-1]
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    return xpad[slot_token].reshape(num_experts, capacity, d)


def combine_gather(ye, experts, slots, gates, out_dtype=None):
    """Return expert outputs to token order: ``y[g] = sum_k gate *
    ye[expert, slot]`` — a ``[G, k, D]`` gather and a weighted sum."""
    e, c, d = ye.shape
    flat = experts * c + slots  # [G, k]; dropped entries have gate 0
    rows = ye.reshape(e * c, d)[flat]  # [G, k, D]
    y = jnp.sum(rows * gates[..., None].astype(ye.dtype), axis=1)
    return y if out_dtype is None else y.astype(out_dtype)


class DroplessLayout(NamedTuple):
    """Group-aligned sorted token layout for the pallas grouped matmul
    (``ops/gmm.py``).  ``NP`` rows = tokens sorted by expert, each
    expert's run padded to a multiple of the row tile ``bm``."""

    #: [NP] i32: slot -> source token row (sentinel G = the zero row)
    slot_token: jnp.ndarray
    #: [G, k] i32: (token, choice) -> slot in the sorted layout
    dest: jnp.ndarray
    #: [T] i32: row tile -> owning expert
    tile_expert: jnp.ndarray


def dropless_topk(router_logits, k=2, rng=None, jitter_eps=0.0):
    """Top-k expert choice WITHOUT capacity: nothing is ever dropped.

    Returns ``(experts [G,k] i32, gates [G,k] f32 renormalized over the
    k choices, aux_loss)`` — the routing half of the dropless MoE path;
    :func:`dropless_layout` turns it into a sorted gmm layout.
    """
    probs, aux_loss = _router_probs_and_aux(
        router_logits, rng, jitter_eps
    )
    gates, experts = jax.lax.top_k(probs, k)  # sorted desc, ties by index
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9
    )
    return experts.astype(jnp.int32), gates, aux_loss


def dropless_layout(experts, num_experts, bm=256):
    """Build the sorted, tile-aligned layout for ``experts [G, k]``.

    Each expert's tokens occupy a contiguous run starting at a multiple
    of ``bm`` (so no gmm row tile straddles two experts); runs are
    ordered by expert id.  Static size ``NP = round_up(G*k, bm) +
    num_experts*bm`` upper-bounds any group split; pad slots point at
    the sentinel zero row and tail tiles are clamped to the last expert
    (their rows are zero — no dw contribution, outputs never gathered).
    """
    g, k = experts.shape
    n = g * k
    ef = experts.reshape(-1).astype(jnp.int32)
    counts = jnp.bincount(ef, length=num_experts)
    padded = ((counts + bm - 1) // bm) * bm
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(padded)[:-1].astype(jnp.int32)]
    )
    unaligned = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    order = jnp.argsort(ef, stable=True)
    sorted_e = ef[order]
    rank_sorted = (
        jnp.arange(n, dtype=jnp.int32) - unaligned[sorted_e]
    )
    dest_flat = (
        jnp.zeros((n,), jnp.int32)
        .at[order]
        .set(starts[sorted_e] + rank_sorted)
    )
    np_rows = ((n + bm - 1) // bm) * bm + num_experts * bm
    t = np_rows // bm
    ends = starts + padded
    tile_expert = jnp.clip(
        jnp.searchsorted(
            ends, jnp.arange(t, dtype=jnp.int32) * bm, side="right"
        ),
        0, num_experts - 1,
    ).astype(jnp.int32)
    token_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), k)
    slot_token = (
        jnp.full((np_rows,), g, jnp.int32).at[dest_flat].set(token_ids)
    )
    return DroplessLayout(
        slot_token=slot_token,
        dest=dest_flat.reshape(g, k),
        tile_expert=tile_expert,
    )


def dispatch_sorted(x, layout):
    """Gather ``x [G, D]`` into the sorted layout ``[NP, D]`` (pad
    slots read a zero row)."""
    g, d = x.shape
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    return xpad[layout.slot_token]


def combine_sorted(ys, layout, gates, out_dtype=None):
    """Return sorted expert outputs to token order:
    ``y[g] = sum_k gates[g,k] * ys[dest[g,k]]``."""
    rows = ys[layout.dest]  # [G, k, D]
    y = jnp.sum(rows * gates[..., None].astype(ys.dtype), axis=1)
    return y if out_dtype is None else y.astype(out_dtype)


def sigmoid_topk(scores, bias, k, scaling=1.0, masked_pick=False):
    """Bias-corrected top-k over sigmoid scores (``noaux_tc``): the
    ``k`` experts with the largest ``scores + bias`` are chosen, ties
    to the lower id; ``bias`` moves the choice and never the weight,
    which is the chosen expert's own score over the sum of the chosen
    scores, times ``scaling``.  ``scores [G, E]`` float32 in (0, 1).
    Returns ``(experts [G, k] i32, gates [G, k] f32)``.  The gates
    carry the gradient to ``scores`` (through the normalisation over
    the chosen ``k``); ``bias`` enters the choice alone — integers, so
    no gradient comes back through it, and a caller that trains stops
    it outright (``models.moe.SigmoidMoE``).

    ``masked_pick`` reads the chosen scores as a sum over the experts
    under the choice's mask — the same numbers to the bit — where the
    default gathers them: over a span's thousands of rows the gather
    and the scatter-add that is its transpose are each 1.5 MB of a
    v5e program and milliseconds of a step, the masked sum one fused
    pass either way."""
    _, experts = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
    if masked_pick:
        chosen = jnp.sum(
            jnp.where(experts[..., None] == jnp.arange(scores.shape[-1]),
                      scores[:, None, :], 0.0), axis=-1)
    else:
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling
    return experts.astype(jnp.int32), gates


class ShareLayout(NamedTuple):
    """:class:`DroplessLayout` over the experts one chip HOLDS of a
    layer whose router scores more: only the (token, choice) pairs
    routed to a held expert take a row."""

    #: [NP] i32: slot -> source token row (sentinel G = the zero row)
    slot_token: jnp.ndarray
    #: [G, k] i32: (token, choice) -> slot; NP (no row) where the
    #: choice is an expert held elsewhere
    dest: jnp.ndarray
    #: [T] i32: row tile -> owning held expert (0-based among the
    #: held); tiles past ``live_tiles`` repeat the last live tile's
    tile_expert: jnp.ndarray
    #: [1] i32: row tiles that hold a routed row
    live_tiles: jnp.ndarray
    #: [G, k] bool: the choice is an expert held here
    local: jnp.ndarray


def share_layout(experts, first, held, bm=256):
    """The sorted, tile-aligned layout of ``experts [G, k]`` over the
    held experts ``first .. first + held - 1``.  Static size ``NP =
    round_up(G*k, bm) + held*bm`` holds every split — all choices
    local included, so nothing is ever dropped; what is routed
    elsewhere takes no row, and the row tiles past ``live_tiles`` hold
    nothing (the grouped matmul skips them)."""
    g, k = experts.shape
    n = g * k
    ef = experts.reshape(-1).astype(jnp.int32)
    local = jnp.logical_and(ef >= first, ef < first + held)
    el = jnp.where(local, ef - first, held)  # elsewhere sorts last
    counts = jnp.bincount(el, length=held + 1)[:held]
    padded = ((counts + bm - 1) // bm) * bm
    zero = jnp.zeros((1,), jnp.int32)
    ends = jnp.cumsum(padded).astype(jnp.int32)
    starts = jnp.concatenate([zero, ends])          # [held + 1]
    unaligned = jnp.concatenate(
        [zero, jnp.cumsum(counts).astype(jnp.int32)])
    np_rows = ((n + bm - 1) // bm) * bm + held * bm
    order = jnp.argsort(el, stable=True)
    sorted_e = el[order]
    slot_sorted = jnp.where(
        sorted_e < held,
        starts[sorted_e] + jnp.arange(n, dtype=jnp.int32)
        - unaligned[sorted_e],
        np_rows,
    )
    dest_flat = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted)
    t = np_rows // bm
    live_tiles = ends[-1:] // bm
    tile = jnp.arange(t, dtype=jnp.int32)
    tile_expert = jnp.clip(
        jnp.searchsorted(ends, tile * bm, side="right"), 0, held - 1
    ).astype(jnp.int32)
    last = tile_expert[jnp.maximum(live_tiles[0] - 1, 0)]
    tile_expert = jnp.where(tile < live_tiles[0], tile_expert, last)
    token_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), k)
    slot_token = jnp.full((np_rows,), g, jnp.int32).at[dest_flat].set(
        token_ids, mode="drop")
    return ShareLayout(
        slot_token=slot_token, dest=dest_flat.reshape(g, k),
        tile_expert=tile_expert, live_tiles=live_tiles,
        local=local.reshape(g, k),
    )


def combine_share(ys, layout, gates, out_dtype=None):
    """``y[g] = sum over the LOCAL choices k of gates[g, k] ·
    ys[dest[g, k]]``: this chip's part of the routed sum."""
    rows = jnp.take(ys, layout.dest, axis=0, mode="fill", fill_value=0)
    # weights and their sum in float32: a gate rounded to the rows'
    # bf16 is a 0.4% error on every routed term
    y = jnp.sum(
        jnp.where(layout.local[..., None],
                  rows.astype(jnp.float32) * gates[..., None], 0.0),
        axis=1,
    )
    return y.astype(ys.dtype if out_dtype is None else out_dtype)


class SpanLayout(NamedTuple):
    """:class:`ShareLayout` of a whole span for :func:`share_span`:
    every ``(token, choice)`` pair that landed on a held expert in ONE
    sorted, tile-aligned order — by expert and then by token, each
    expert's run rounded up to whole row tiles — as 1-D integers: no
    array of rows is built for it."""

    #: [NP] i32: sorted row -> its pair ``token * k + choice``
    #: (sentinel ``G * k``: a pad row, or a row of a tile past the live
    #: ones); NP is a whole number of chunks
    pairs: jnp.ndarray
    #: [NP // bm] i32: row tile -> owning held expert, as
    #: :class:`ShareLayout` has it
    tile_expert: jnp.ndarray
    #: [1] i32: row tiles that hold a routed row (a prefix)
    live_tiles: jnp.ndarray
    #: [G, k] bool: the choice is an expert held here
    local: jnp.ndarray


def span_layout(experts, first, held, bm, chunk_rows):
    """:func:`share_layout` over ALL of a span's pairs at once: one
    stable sort by held expert (elsewhere last), each held expert's run
    rounded up to whole row tiles ONCE, and every sorted row's pair read
    from the sorted order here, once a layer (a loop that worked its
    chunk's pairs out for itself was traced, lowered and run three
    times a layer).  Only 1-D integers are sized for every pair landing
    here."""
    g, k = experts.shape
    n = g * k
    ef = experts.reshape(-1).astype(jnp.int32)
    local = jnp.logical_and(ef >= first, ef < first + held)
    el = jnp.where(local, ef - first, held)
    counts = jnp.sum(
        el[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None],
        axis=1, dtype=jnp.int32)
    padded = ((counts + bm - 1) // bm) * bm
    ends = jnp.cumsum(padded).astype(jnp.int32)
    np_rows = ((n + bm - 1) // bm) * bm + held * bm
    np_rows = -(-np_rows // chunk_rows) * chunk_rows
    # a tile's expert: the runs that end at or before its first row
    # (:func:`share_layout`'s search as one comparison: a prefill program
    # a bucket and a layer each trace and lower this, and the search's
    # scan was the dearest line of it); the tiles past the live ones
    # repeat the last live tile's expert, as there
    live_tiles = ends[-1:] // bm
    tile = jnp.arange(np_rows // bm, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile[:, None] * bm, axis=1,
                dtype=jnp.int32), held - 1)
    tile_expert = jnp.where(
        tile < live_tiles[0], tile_expert,
        tile_expert[jnp.maximum(live_tiles[0] - 1, 0)])
    order = jnp.argsort(el, stable=True).astype(jnp.int32)
    # sorted row s of expert e's run is pair order[unaligned[e] + s -
    # starts[e]] while that offset is under counts[e]; a dead tile
    # repeats the last live expert's id and lies past its run, pads
    # included: its offsets are over the count
    e = jnp.repeat(tile_expert, bm)
    unaligned = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    r = jnp.arange(np_rows, dtype=jnp.int32) - (ends - padded)[e]
    pairs = jnp.where(
        r < counts[e], order[jnp.minimum(unaligned[e] + r, n - 1)], n)
    return SpanLayout(pairs=pairs, tile_expert=tile_expert,
                      live_tiles=live_tiles, local=local.reshape(g, k))


def span_chunks(layout, bm, chunk_rows):
    """``(chunks, experts_hit)`` of a :class:`SpanLayout`: the chunks
    of ``chunk_rows`` sorted rows that hold a live tile (the trip count
    of :func:`share_span`'s loops), and the held experts a chunk's live
    tiles belong to, summed over the chunks — an expert whose run
    straddles a chunk's edge is read by both and counts twice."""
    per = chunk_rows // bm
    live = layout.live_tiles[0]
    tile = jnp.arange(layout.tile_expert.shape[0], dtype=jnp.int32)
    owner = jnp.where(tile < live, layout.tile_expert, -1).reshape(-1, per)
    # a live prefix's owners never fall: a chunk's experts are its
    # first live tile's and one more wherever the owner changes
    fresh = jnp.concatenate(
        [owner[:, :1] >= 0,
         jnp.logical_and(owner[:, 1:] >= 0, owner[:, 1:] != owner[:, :-1])],
        axis=1)
    return _live_chunks(layout, bm, chunk_rows), jnp.sum(
        fresh.astype(jnp.int32))


def _live_chunks(layout, bm, chunk_rows):
    per = chunk_rows // bm
    return (layout.live_tiles[0] + per - 1) // per


def _act(hg, hi):
    return jax.nn.silu(hg) * hi


def _span_chunk(c, x, gates, weights, layout, bm, chunk_rows):
    """Chunk ``c`` of the sorted rows: its pairs, tokens and gates, its
    rows of ``x``, and the three products over its live tiles.  Pad
    rows read zeros and weigh nothing; the rows of the tiles past the
    live ones are never written (``gmm``'s live kernels) and never
    read back: their token is the sentinel, which every scatter drops
    and every weight masks."""
    from tensorflowonspark_tpu.ops import gmm

    wi, wg, wo = weights
    per = chunk_rows // bm
    pair = jax.lax.dynamic_slice(
        layout.pairs, (c * chunk_rows,), (chunk_rows,))
    te = jax.lax.dynamic_slice(layout.tile_expert, (c * per,), (per,))
    live = jnp.clip(layout.live_tiles - c * per, 0, per)
    tok = pair // layout.local.shape[1]
    gate = jnp.take(gates.reshape(-1), pair, mode="fill", fill_value=0)
    xs = jnp.take(x, tok, axis=0, mode="fill", fill_value=0)
    mm = lambda a, w: gmm.gmm_call(  # noqa: E731
        a, w, te, bm=bm, live_tiles=live)
    hi, hg = mm(xs, wi), mm(xs, wg)
    return dict(pair=pair, tok=tok, gate=gate, te=te, live=live, xs=xs,
                hi=hi, hg=hg, ys=mm(_act(hg, hi), wo))


def _share_span(x, gates, weights, layout, bm, chunk_rows):
    g, d = x.shape
    chunks = _live_chunks(layout, bm, chunk_rows)
    n = layout.local.size

    def body(c, y):
        ch = _span_chunk(c, x, gates, weights, layout, bm, chunk_rows)
        rows = jnp.where(
            (ch["pair"] < n)[:, None],
            ch["ys"].astype(jnp.float32) * ch["gate"][:, None], 0.0)
        return y.at[ch["tok"]].add(rows, mode="drop")

    y = jax.lax.fori_loop(0, chunks, body, jnp.zeros((g, d), jnp.float32))
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def share_span(x, gates, weights, layout, bm, chunk_rows):
    """This chip's part of a span's routed sum, ``y[g] = sum over the
    LOCAL choices k of gates[g, k] · expert(x[g])``, moving only the
    rows that land here: a loop over the live prefix of the sorted rows
    (:func:`span_layout`) in chunks of ``chunk_rows`` — gather the
    chunk's rows of ``x``, the three grouped products on its live
    tiles, the gate applied a row in float32, the result added to the
    tokens' rows — under a trip count read from the layout, so the
    device time follows the rows that landed on the held experts and
    not ``G · k``.  ``x [G, D]``, ``gates [G, k]`` float32, ``weights =
    (wi, wg, wo)`` stacked over the held experts.

    The backward is the same loop: a chunk's products are made again,
    ``dx`` and the gathered cotangent carry ``chunk_rows`` rows a
    chunk (no cotangent of a dead row is added anywhere), and ``dw``
    sums over the chunks in float32 (an expert's run may straddle a
    chunk's edge).  The layout's integers take no gradient."""
    return _share_span(x, gates, weights, layout, bm, chunk_rows)


def _share_span_fwd(x, gates, weights, layout, bm, chunk_rows):
    y = _share_span(x, gates, weights, layout, bm, chunk_rows)
    return y, (x, gates, weights, layout)


def _share_span_bwd(bm, chunk_rows, res, dy):
    from tensorflowonspark_tpu.ops import gmm

    x, gates, weights, layout = res
    wi, wg, wo = weights
    held = wi.shape[0]
    chunks = _live_chunks(layout, bm, chunk_rows)
    n = layout.local.size

    def body(c, carry):
        dx, dgate, dwi, dwg, dwo = carry
        ch = _span_chunk(c, x, gates, weights, layout, bm, chunk_rows)
        te, live, xs = ch["te"], ch["live"], ch["xs"]
        dyr = jnp.take(dy, ch["tok"], axis=0, mode="fill",
                       fill_value=0).astype(jnp.float32)
        dgate = dgate.at[ch["pair"]].add(
            jnp.sum(ch["ys"].astype(jnp.float32) * dyr, axis=-1),
            mode="drop")
        dys = (dyr * ch["gate"][:, None]).astype(xs.dtype)
        a, act_vjp = jax.vjp(_act, ch["hg"], ch["hi"])

        def dxt(dz, w):
            out = gmm.gmm_dxt_call(dz, w, te, bm=bm, live_tiles=live)
            if out is None:
                out = gmm.gmm_call(dz, jnp.swapaxes(w, 1, 2), te, bm=bm,
                                   live_tiles=live)
            return out

        def dw(acc, a, dz):
            return acc + gmm.tgmm_call(
                a, dz, te, held, bm=bm, live_tiles=live
            ).astype(jnp.float32)

        dhg, dhi = act_vjp(dxt(dys, wo))
        dxs = dxt(dhg, wg) + dxt(dhi, wi)
        dx = dx.at[ch["tok"]].add(dxs.astype(jnp.float32), mode="drop")
        return (dx, dgate, dw(dwi, xs, dhi), dw(dwg, xs, dhg),
                dw(dwo, a, dys))

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
    dx, dgate, dwi, dwg, dwo = jax.lax.fori_loop(0, chunks, body, (
        zeros(x), jnp.zeros((n,), jnp.float32),
        zeros(wi), zeros(wg), zeros(wo)))
    return (dx.astype(x.dtype), dgate.reshape(gates.shape).astype(
        gates.dtype), (dwi.astype(wi.dtype), dwg.astype(wg.dtype),
                       dwo.astype(wo.dtype)), None)


share_span.defvjp(_share_span_fwd, _share_span_bwd)


def expert_capacity(num_tokens, num_experts, capacity_factor=1.25, k=2):
    """Standard capacity formula: ``ceil(k * G / E * factor)``, rounded
    up to a multiple of 8 (TPU sublane alignment)."""
    cap = int(num_tokens * k * capacity_factor / num_experts) + 1
    return ((cap + 7) // 8) * 8
