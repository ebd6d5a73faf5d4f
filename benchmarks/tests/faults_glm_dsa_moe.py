"""A fault a test plants UNDER the timed path of the latent-attention
cell, to see ``correct`` come out false (reached only through a test's
rehearsal, as ``faults.py``'s are)."""


def recent_keys_only():
    """The learned selection dropped: every query attends to the most
    recent ``index_topk`` visible keys, whatever the index scored (a
    sliding window is the shortcut a sparse-attention path is most
    tempted by)."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import mla

    def recent(scores, visible, k):
        # position of each key counted from the newest visible one
        newer = jnp.cumsum(visible[..., ::-1].astype(jnp.int32),
                           axis=-1)[..., ::-1]
        return jnp.logical_and(visible, newer <= k)

    mla.topk_mask = recent


FAULTS = {"recent_keys_only": recent_keys_only}


def plant(name):
    FAULTS[name]()
