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


def sigmoid_topk(scores, bias, k, scaling=1.0):
    """Bias-corrected top-k over sigmoid scores (``noaux_tc``): the
    ``k`` experts with the largest ``scores + bias`` are chosen, ties
    to the lower id; ``bias`` moves the choice and never the weight,
    which is the chosen expert's own score over the sum of the chosen
    scores, times ``scaling``.  ``scores [G, E]`` float32 in (0, 1).
    Returns ``(experts [G, k] i32, gates [G, k] f32)``.  The gates
    carry the gradient to ``scores`` (through the normalisation over
    the chosen ``k``); ``bias`` enters the choice alone — integers, so
    no gradient comes back through it, and a caller that trains stops
    it outright (``models.moe.SigmoidMoE``)."""
    _, experts = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
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


def expert_capacity(num_tokens, num_experts, capacity_factor=1.25, k=2):
    """Standard capacity formula: ``ceil(k * G / E * factor)``, rounded
    up to a multiple of 8 (TPU sublane alignment)."""
    cap = int(num_tokens * k * capacity_factor / num_experts) + 1
    return ((cap + 7) // 8) * 8
