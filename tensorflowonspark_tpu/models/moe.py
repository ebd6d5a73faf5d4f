"""Mixture-of-Experts feed-forward layer (expert parallelism).

New TPU-first capability with no reference analogue (SURVEY.md §2.3).
Expert weights are *stacked* ``[E, ...]`` and annotated with the
``expert`` logical axis; under a mesh with an ``expert`` axis the
dispatch/combine einsums against those weights make XLA insert the
expert all-to-alls over ICI — no hand-written routing collectives.
Composes with TP (``expert_mlp`` logical axis → ``model`` mesh axis)
and DP/FSDP through the same rule sets as every other layer.

Aux losses are reported through flax's ``sow`` under the ``"losses"``
collection; :func:`moe_loss_fn` collects them.
"""

import functools
import logging

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensorflowonspark_tpu.ops import moe as moe_ops

logger = logging.getLogger(__name__)

#: drop-rate honesty threshold: above this
#: fraction of dropped (token, choice) assignments, a throughput
#: number is buying speed with unexamined model-quality loss and must
#: say so wherever it is reported
DROP_RATE_WARN = 0.02


def check_drop_rate(drop_rate, capacity_factor=None, where="MoE"):
    """Honesty guard on router capacity overflow: returns a warning
    string (and logs it loudly) when ``drop_rate`` exceeds
    :data:`DROP_RATE_WARN`, else ``None``.

    Callers that PUBLISH a throughput number (benchmark results,
    training logs) attach the returned string to the same record, so a reader
    of the headline sees the quality caveat next to it — the CF=1.0
    vs CF=1.25 convergence smoke in tests/test_moe.py quantifies
    what the drops cost.  Raise
    ``capacity_factor`` (1.25 keeps drops rare on balanced routers) or
    switch ``dispatch="dropless"`` to eliminate them.  Only
    :class:`MoEMLP`'s ``gather``/``einsum`` dispatches have a capacity;
    :class:`SigmoidMoE` (``router_scoring="sigmoid"``, the serving
    path's expert layer) routes without one and never drops.
    """
    rate = float(drop_rate)
    if rate <= DROP_RATE_WARN:
        return None
    msg = (
        "%s drop_rate %.1f%% exceeds %.0f%% (capacity_factor=%s): "
        "throughput at this setting silently drops token updates — "
        "raise capacity_factor (e.g. 1.25) or use dispatch='dropless' "
        "(router_scoring='sigmoid' has no capacity at all); "
        "see the CF convergence smoke in tests/test_moe.py"
        % (
            where, 100.0 * rate, 100.0 * DROP_RATE_WARN,
            capacity_factor if capacity_factor is not None else "?",
        )
    )
    logger.warning(msg)
    return msg


class MoEMLP(nn.Module):
    """Gated-SiLU expert FFN with top-k capacity routing.

    Drop-in for the dense MLP on ``[B, S, D]`` activations; sows the
    load-balancing aux loss as ``losses/moe_aux``.
    """

    num_experts: int
    mlp_dim: int
    embed_dim: int
    k: int = 2
    capacity_factor: float = 1.25
    dtype: str = "bfloat16"
    #: "gather" (index-based dispatch/combine — O(tokens·D) movement,
    #: no permutation matmuls), "einsum" (dense [G,E,C] one-hot
    #: contractions; the numerics reference and GSPMD fallback), or
    #: "dropless" (NO capacity: tokens sorted by expert into a
    #: tile-aligned layout and multiplied by the pallas grouped-matmul
    #: kernel — zero drops, padding only rounds each expert's run up to
    #: one ``gmm_block_rows`` tile instead of the CF× slack).
    #:
    #: SHARDING CONSTRAINT for "dropless": the gmm pallas call is
    #: opaque to GSPMD, so the expert weights [E, D, M] must be fully
    #: REPLICATED on every device that runs this module.  If they are
    #: sharded on any mesh axis — via ``TransformerConfig.mesh`` (the
    #: Block-level guard catches that case) or via EXTERNAL
    #: ``jit``/``in_shardings`` specs built from ``logical_axes()``
    #: (which the guard cannot see: tracer shardings are not
    #: inspectable at apply time) — XLA silently all-gathers the full
    #: expert stack onto every device, defeating EP/TP.  Use "gather"
    #: for expert- or model-sharded deployments.
    dispatch: str = "gather"
    #: gmm row-tile size for dispatch="dropless" (per-expert padding
    #: quantum; must be a multiple of the MXU's 8-row sublane)
    gmm_block_rows: int = 256

    @nn.compact
    def __call__(self, x):
        if self.dispatch not in ("gather", "einsum", "dropless"):
            raise ValueError(
                "dispatch must be 'gather', 'einsum', or 'dropless', "
                "got %r" % (self.dispatch,)
            )
        e, m, d = self.num_experts, self.mlp_dim, self.embed_dim
        jdtype = jnp.dtype(self.dtype)
        b, s, _ = x.shape
        g = b * s
        xf = x.reshape(g, d)

        # router runs in f32: tiny matmul, and routing decisions are
        # sensitive to logit precision
        router = self.param(
            "router", nn.initializers.normal(stddev=0.02), (d, e)
        )
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        cap = moe_ops.expert_capacity(
            g, e, capacity_factor=self.capacity_factor, k=self.k
        )

        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        wi = self.param("wi", init, (e, d, m))
        wg = self.param("wg", init, (e, d, m))
        wo = self.param("wo", init, (e, m, d))

        if self.dispatch == "dropless":
            # no capacity at all: sort tokens by expert into a
            # tile-aligned layout and run the pallas grouped matmul —
            # zero drops; per-expert padding is one row tile, not CF×.
            # (Single-mesh path: the gmm kernel is opaque to GSPMD, so
            # the expert-axis EP sharding keeps using "gather".)
            from tensorflowonspark_tpu.ops import gmm

            # wi/wg stay separate params (a fused [E, D, 2M] would
            # halve token-tile reads but costs a per-step weight
            # concat — weights change every step — and breaks param
            # compatibility with the other dispatch modes)
            bm = self.gmm_block_rows
            experts, gates, aux = moe_ops.dropless_topk(
                logits, k=self.k
            )
            self.sow("losses", "moe_aux", aux)
            # dropless by construction; sown for a uniform telemetry
            # surface across dispatch modes (read via
            # mutable=["moe_stats"])
            self.sow(
                "moe_stats", "drop_rate", jnp.zeros((), jnp.float32)
            )
            layout = moe_ops.dropless_layout(experts, e, bm=bm)
            xs = moe_ops.dispatch_sorted(xf.astype(jdtype), layout)
            h = gmm.grouped_matmul(
                xs, wi.astype(jdtype), layout.tile_expert, bm
            )
            hg = gmm.grouped_matmul(
                xs, wg.astype(jdtype), layout.tile_expert, bm
            )
            ys = gmm.grouped_matmul(
                nn.silu(hg) * h, wo.astype(jdtype), layout.tile_expert,
                bm,
            )
            y = moe_ops.combine_sorted(ys, layout, gates)
            return y.reshape(b, s, d).astype(x.dtype)

        if self.dispatch == "gather":
            experts, slots, gates, aux = moe_ops.top_k_routing(
                logits, e, cap, k=self.k
            )
            self.sow("losses", "moe_aux", aux)
            # a dropped (token, choice) has its gate zeroed by the
            # capacity overflow mask in top_k_routing (router probs are
            # strictly positive post-softmax, so gate==0 <=> dropped)
            self.sow(
                "moe_stats", "drop_rate",
                jnp.mean((gates == 0.0).astype(jnp.float32)),
            )
            xe = moe_ops.dispatch_gather(
                xf.astype(jdtype), experts, slots, gates, e, cap
            )  # [E, C, D], one row-gather
        elif self.dispatch == "einsum":
            dispatch, combine, aux = moe_ops.top_k_gating(
                logits, e, cap, k=self.k
            )
            self.sow("losses", "moe_aux", aux)
            g_tok = logits.shape[0]
            self.sow(
                "moe_stats", "drop_rate",
                1.0 - jnp.sum(dispatch.astype(jnp.float32))
                / (g_tok * self.k),
            )
            # dispatch: [G,E,C] x [G,D] -> expert batches [E,C,D]
            xe = jnp.einsum(
                "gec,gd->ecd", dispatch.astype(jdtype), xf.astype(jdtype)
            )
        h = jnp.einsum("ecd,edm->ecm", xe, wi.astype(jdtype))
        hg = jnp.einsum("ecd,edm->ecm", xe, wg.astype(jdtype))
        ye = jnp.einsum(
            "ecm,emd->ecd", nn.silu(hg) * h, wo.astype(jdtype)
        )
        if self.dispatch == "gather":
            y = moe_ops.combine_gather(ye, experts, slots, gates)
        else:
            # combine: weighted return to token order [G,D]
            y = jnp.einsum("gec,ecd->gd", combine.astype(jdtype), ye)
        return y.reshape(b, s, d).astype(x.dtype)


#: rows from which :class:`SigmoidMoE` takes a call for a span (a
#: prompt, a training batch: row tiles of the MXU's 256, the sorted
#: rows gone through a chunk at a time); fewer are a decode step's
#: handful, one pass over tiles of 16
SPAN_ROWS = 512
#: most float32 bytes of the sorted rows a span moves and multiplies at
#: a time (:func:`..ops.moe.share_span`).  A chunk is sized for the rows
#: a balanced router sends here — ``tokens x k x held / experts`` and
#: two row tiles a held expert, one for the rounding and one for a load
#: that leans here: ONE chunk a layer then (a second costs ~1% of a v5e
#: step, and seeds that met one now and then read that much apart) —
#: and no larger than this.  Adding a chunk's rows to the tokens' costs
#: a pass over the whole output besides the rows, so few large chunks
#: beat many small ones (a v5e step of 16384 tokens x 2048: 663 ms at
#: 4096 rows, 643 at 8192, 639 at 16384)
SPAN_CHUNK_BYTES = 256 * 2 ** 20
#: names (``jax.ad_checkpoint.checkpoint_name``) of a span's routing
#: that a rematerialised block keeps for its backward
#: (``Transformer``'s ``remat_policy="block"`` saves them beside flash's
#: ``FLASH_SAVED``): the experts chosen and the sorted rows' pairs, 4 bytes a
#: pair each (393 + 401 KB a Moonlight layer).  The backward then
#: starts from the saved routing: no second top-k, no second sort — a
#: fifth of what the span adds to a v5e step's executable
SPAN_SAVED = ("moe_experts", "moe_pairs")
#: what a training span of :class:`SigmoidMoE` sows beside
#: ``held_choices``, and :func:`sigmoid_moe_loss_fn` hands out of the
#: step as ``moe_<name>``, summed over the sparse layers
MOE_STEP_COUNTS = ("local_assignments", "experts_hit", "rows_multiplied",
                   "rows_moved")


class SigmoidMoE(nn.Module):
    """Sigmoid-routed gated-SiLU experts with a shared expert, as ONE
    chip's share of an expert-parallel layer.

    The router scores all ``router_experts`` experts of the layer
    (``s = sigmoid(x W_r)``), chooses the ``k`` with the largest ``s +
    b`` (``b`` = ``router_bias``: it corrects the choice, never the
    weight) and weighs each by ``s_e / sum of the chosen s``, times
    ``scaling`` (:func:`..ops.moe.sigmoid_topk`).  This program holds
    experts ``expert_first .. expert_first + num_experts - 1`` and
    computes ``shared(x) + sum over chosen AND held e of w_e ·
    expert_e(x)``: its own part of the result.  What the experts held
    elsewhere would add is theirs to add (over the all-to-all that one
    chip does not have); nothing here stands in for them.

    Never a drop, at any number of tokens, and no capacity.  A SPAN
    (:data:`SPAN_ROWS` rows or more: a prompt, a training batch) is
    routed ONCE: one layout over all of its ``(token, choice)`` pairs
    (:func:`..ops.moe.span_layout`: one sort, each held expert's run
    rounded up to row tiles of 256 once), of which only 1-D integers
    are sized for every pair landing here, then a loop over the LIVE
    prefix of the sorted rows a chunk at a time
    (:func:`..ops.moe.share_span`, :data:`SPAN_CHUNK_BYTES`): the
    chunk's rows of ``x`` gathered, the three grouped products on its
    tiles, the gate applied a row in float32, the result added to the
    tokens' rows.  The trip count is read from the layout, so the
    routed part's device time follows the rows that landed on the held
    experts and not ``tokens x k``; any span length goes through.  A
    span reads its chosen scores by a masked sum over the experts
    (``sigmoid_topk(masked_pick=True)``: the gathered numbers to the
    bit), and names the chosen experts and the sorted rows' pairs
    (:data:`SPAN_SAVED`) for a rematerialised block to keep.  A decode
    step's handful of rows takes one pass sized for every choice
    landing here, over row tiles of 16
    (:func:`..ops.moe.share_layout`).

    Sows ``moe_stats/held_choices``: ``[tokens, num_experts]`` int8,
    1 where the token chose that held expert (the serving engine
    counts assignments and experts hit from it).

    ``differentiable`` (a training span: ``Block`` passes ``not
    decode``) sows four more integers (:data:`MOE_STEP_COUNTS`), a
    "pass" being one chunk of sorted rows: ``local_assignments``
    (routed rows that landed on held experts), ``experts_hit`` (held
    experts a chunk's tiles belong to, summed over the chunks: one
    whose run straddles a chunk's edge is read twice and counts
    twice), ``rows_multiplied`` (live tiles x tile rows) and
    ``rows_moved`` (rows the chunks' gathers carried: chunks run x
    chunk rows).  A span's backward is the same loop
    (``share_span``'s own rule: a chunk's products are made again,
    ``dw`` summed over the chunks in float32); the decode-sized pass
    under a gradient sends its products through
    :func:`..ops.gmm.grouped_matmul_live`, whose ``dx`` and ``dw``
    skip the dead tiles as the forward does.  The gate's gradient
    reaches ``router`` through the sigmoid and the normalisation over
    the chosen ``k``; ``router_bias`` enters the choice alone and gets
    none.  The serving path's decode step (``differentiable=False``)
    is the raw forward kernel, as it was.
    """

    router_experts: int
    num_experts: int
    mlp_dim: int
    embed_dim: int
    expert_first: int = 0
    k: int = 8
    scaling: float = 1.0
    shared_experts: int = 1
    dtype: str = "bfloat16"
    #: "sigmoid" (above) | "softmax": ``s = softmax(x W_r)`` over all
    #: ``router_experts`` in float32, the ``k`` largest, weights
    #: normalised over the chosen (``norm_topk_prob``) times
    #: ``scaling``; no correction bias (the parameter is not made).
    #: Layout, products and counts are the same code either way
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x, differentiable=False):
        from tensorflowonspark_tpu.ops import gmm

        e, held, first = self.router_experts, self.num_experts, (
            self.expert_first)
        if not 0 <= first <= e - held:
            raise ValueError(
                "held experts %d..%d are not among the router's %d" % (
                    first, first + held - 1, e))
        m, d = self.mlp_dim, self.embed_dim
        jdtype = jnp.dtype(self.dtype)
        b, s, _ = x.shape
        g = b * s
        xf = x.reshape(g, d)
        router = self.param(
            "router", nn.initializers.normal(stddev=0.02), (d, e))
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError("scoring %r is not built" % (self.scoring,))
        score = (jax.nn.sigmoid if self.scoring == "sigmoid"
                 else functools.partial(jax.nn.softmax, axis=-1))
        bias = (self.param("router_bias", nn.initializers.zeros, (e,))
                if self.scoring == "sigmoid" else jnp.zeros((e,)))
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        wi = self.param("wi", init, (held, d, m)).astype(jdtype)
        wg = self.param("wg", init, (held, d, m)).astype(jdtype)
        wo = self.param("wo", init, (held, m, d)).astype(jdtype)

        # the correction bias moves the choice and never the weight:
        # it takes no gradient (stopped where it enters the choice; on
        # the serving path there is none to stop)
        choice_bias = jax.lax.stop_gradient(bias) if differentiable else bias

        def route(xc, span=False):
            scores = score(jnp.dot(
                xc, router.astype(xc.dtype),
                preferred_element_type=jnp.float32))
            return moe_ops.sigmoid_topk(
                scores, choice_bias.astype(jnp.float32), self.k,
                self.scaling, masked_pick=span)

        def chosen(experts, local):
            return jnp.any(
                jnp.logical_and(
                    local[..., None],
                    (experts - first)[..., None] == jnp.arange(held)),
                axis=1,
            )

        def routed(xc):
            # a decode step's handful of rows: one pass, sized for
            # every choice landing here, over the smallest row tile
            bm = 16
            experts, gates = route(xc)
            lay = moe_ops.share_layout(experts, first, held, bm=bm)
            xs = moe_ops.dispatch_sorted(xc.astype(jdtype), lay)

            def mm(a, w):
                if differentiable:
                    return gmm.grouped_matmul_live(
                        a, w, lay.tile_expert, lay.live_tiles, bm)
                return gmm.gmm_call(
                    a, w, lay.tile_expert, bm=bm,
                    live_tiles=lay.live_tiles)

            ys = mm(nn.silu(mm(xs, wg)) * mm(xs, wi), wo)
            y = moe_ops.combine_share(ys, lay, gates, out_dtype=x.dtype)
            chose = chosen(experts, lay.local)
            return y, chose, differentiable and (
                jnp.sum(lay.local.astype(jnp.int32)),
                jnp.sum(jnp.any(chose, axis=0).astype(jnp.int32)),
                lay.live_tiles[0] * bm, xs.shape[0])

        def span(xc):
            # routed once, moved and multiplied a chunk of the sorted
            # rows at a time: as many chunks as hold a row that landed
            # here, whatever the span's length
            bm = 256
            n = xc.shape[0] * self.k
            # a chunk: a balanced router's rows for the held experts
            # and two tiles each, under the byte budget
            rows = min(-(-n * held // e // bm) * bm + 2 * held * bm,
                       max(SPAN_CHUNK_BYTES // (4 * d) // bm, 1) * bm)
            experts, gates = route(xc, span=True)
            experts = checkpoint_name(experts, SPAN_SAVED[0])
            lay = moe_ops.span_layout(experts, first, held, bm, rows)
            lay = lay._replace(
                pairs=checkpoint_name(lay.pairs, SPAN_SAVED[1]))
            y = moe_ops.share_span(
                xc.astype(jdtype), gates, (wi, wg, wo), lay, bm, rows)
            chose = chosen(experts, lay.local)
            if not differentiable:
                return y.astype(x.dtype), chose, None
            chunks, hit = moe_ops.span_chunks(lay, bm, rows)
            return y.astype(x.dtype), chose, (
                jnp.sum(lay.local.astype(jnp.int32)), hit,
                lay.live_tiles[0] * bm, chunks * rows)

        with jax.named_scope("moe"):
            y, chose, counts = (span if g >= SPAN_ROWS else routed)(xf)
            self.sow("moe_stats", "held_choices", chose.astype(jnp.int8))
            if differentiable:
                for name, count in zip(MOE_STEP_COUNTS, counts):
                    self.sow("moe_stats", name, jnp.asarray(count))
            if self.shared_experts:
                dense = lambda name, feats: nn.Dense(  # noqa: E731
                    feats, use_bias=False, dtype=jdtype, name=name)
                width = m * self.shared_experts
                gate = nn.silu(dense("shared_wg", width)(xf))
                y = y + dense("shared_wo", d)(
                    gate * dense("shared_wi", width)(xf))
        return y.reshape(b, s, d).astype(x.dtype)


#: path-regex → logical axes for MoE params (merged into the
#: transformer's rules by models.transformer.LOGICAL_AXES_RULES)
MOE_LOGICAL_AXES_RULES = (
    (r"router$", ("embed", None)),
    (r"moe/(wi|wg)$", ("expert", "embed", "expert_mlp")),
    (r"moe/wo$", ("expert", "expert_mlp", "embed")),
)


def _next_token_ce(logits, tokens):
    """Mean cross-entropy of ``logits[:, t]`` against ``tokens[:, t +
    1]``."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def moe_loss_fn(model, aux_weight=0.01):
    """Next-token CE + weighted MoE load-balance aux losses.

    Same contract as ``transformer.loss_fn`` (batch = dict(tokens));
    works for any model that sows into the ``"losses"`` collection.
    """

    def _loss(params, batch, rng):
        tokens = batch["tokens"]
        logits, variables = model.apply(
            {"params": params}, tokens, mutable=["losses"]
        )
        ce = _next_token_ce(logits, tokens)
        aux_leaves = jax.tree.leaves(variables.get("losses", {}))
        aux = (
            sum(jnp.sum(a) for a in aux_leaves)
            if aux_leaves else jnp.zeros((), jnp.float32)
        )
        return ce + aux_weight * aux, {"ce": ce, "moe_aux": aux}

    return _loss


def sigmoid_moe_loss_fn(model):
    """Next-token cross-entropy for a model whose sparse layers are
    :class:`SigmoidMoE` shares (they sow ``moe_stats``), over the
    vocabulary rows the model holds.  Same contract as
    ``transformer.loss_fn`` (batch = dict(tokens)), for a trainer built
    with ``has_aux=True``: the aux is the step's integer counts
    ``moe_local_assignments``, ``moe_experts_hit`` and
    ``moe_rows_multiplied`` (:data:`MOE_STEP_COUNTS`), each summed over
    the sparse layers.  No balance loss: the correction bias's update
    and the sequence-wise balance loss are training procedure that the
    program does not run (ROADMAP M3)."""
    from flax import traverse_util

    def _loss(params, batch, rng):
        tokens = batch["tokens"]
        logits, variables = model.apply(
            {"params": params}, tokens, mutable=["moe_stats"]
        )
        sown = traverse_util.flatten_dict(variables.get("moe_stats", {}))
        aux = {
            "moe_" + name: sum(
                jnp.sum(jnp.stack(v)) for path, v in sown.items()
                if path[-1] == name)
            for name in MOE_STEP_COUNTS
        }
        return _next_token_ce(logits, tokens), aux

    return _loss


def leave_router_bias(optimizer):
    """``optimizer`` on every leaf but the routers' correction bias
    (``.../router_bias``), which gets a zero update — no step, no
    weight decay, no moments kept: the bias moves by its own balance
    rule or not at all (:func:`..ops.moe.sigmoid_topk`), never by the
    loss.  An optax mask for the call site that builds the trainer."""
    import optax

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "frozen" if str(
                getattr(path[-1], "key", path[-1])) == "router_bias"
            else "trained", params)

    return optax.multi_transform(
        {"trained": optimizer, "frozen": optax.set_to_zero()}, labels)
