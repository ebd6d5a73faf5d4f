"""Faults a test plants UNDER the timed path of the latent-attention /
sigmoid-routed training cell, in the process that runs it, to see
``correct`` come out false (``faults.py`` for the dense cell).  Reached
only through a test's rehearsal (``run.main(..., rehearse={"fault":
name})``); the command the driver runs has no way to name one."""


def half_batch():
    """Half of every batch is left out and the mean taken over the
    rest."""
    from tensorflowonspark_tpu.models import moe

    loss_fn = moe.sigmoid_moe_loss_fn

    def halved(model):
        inner = loss_fn(model)

        def loss(params, batch, rng):
            tokens = batch["tokens"]
            return inner(
                params, {"tokens": tokens[: tokens.shape[0] // 2]}, rng)

        return loss

    moe.sigmoid_moe_loss_fn = halved


def expert_rows_dropped():
    """The rows routed to the first held expert are dropped in the
    backward pass: they hand no gradient back to their tokens and add
    nothing to that expert's weight gradient.  The forward, and so the
    loss of the first step, is untouched."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.ops import gmm

    dxt, tgmm = gmm.gmm_dxt_call, gmm.tgmm_call

    def dx_without(dy, w, tile_expert, *, bm=256, **kw):
        dx = dxt(dy, w, tile_expert, bm=bm, **kw)
        keep = jnp.repeat(tile_expert != 0, bm)
        live = kw.get("live_tiles")
        if live is not None:
            # what the dead tiles hold is never read: leave it alone
            keep = jnp.logical_or(
                keep, jnp.arange(keep.shape[0]) >= live[0] * bm)
        return jnp.where(keep[:, None], dx, 0)

    def dw_without(x, dy, tile_expert, num_experts, **kw):
        dw = tgmm(x, dy, tile_expert, num_experts, **kw)
        return dw.at[0].set(0)

    gmm.gmm_dxt_call, gmm.tgmm_call = dx_without, dw_without


FAULTS = {
    "half_batch": half_batch,
    "expert_rows_dropped": expert_rows_dropped,
}


def plant(name):
    FAULTS[name]()
