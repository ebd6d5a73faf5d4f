"""Faults a test plants UNDER the timed path of the window-and-full-
attention / softmax-routed-experts cell, to see ``correct`` come out
false (reached only through a test's rehearsal, as ``faults.py``'s
are).  Each is a shortcut the mechanism it breaks is most tempted by;
the reference imports nothing from the program and is untouched."""


def window_plus_one():
    """Every sliding layer sees one key more than its window (the
    off-by-one of ``j >= i - W`` for ``j > i - W``); the ring is sized
    for what the layer sees, so nothing else gives it away."""
    from tensorflowonspark_tpu.models import transformer as tr

    window_of = tr.TransformerConfig.window_of

    def wider(self, layer):
        w = window_of(self, layer)
        return w + 1 if w else 0

    tr.TransformerConfig.window_of = wider


def ring_one_short():
    """A ring one row shorter than the layer needs: the append
    overwrites a key that is still inside the window."""
    from tensorflowonspark_tpu.models import transformer as tr

    rows = tr.ring_rows
    tr.ring_rows = lambda cfg, window: rows(cfg, window) - 1


def no_yarn():
    """The full layers rotated like the sliding ones: the default
    frequencies, no factor on cos and sin."""
    from tensorflowonspark_tpu.models import transformer as tr

    rope_of = tr.TransformerConfig.rope_of
    tr.TransformerConfig.rope_of = lambda self, layer: (
        rope_of(self, layer)[0], None, 1.0)


def gates_not_renormalised():
    """The chosen experts weighted by their softmax probabilities as
    they are, not normalised over the chosen eight."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.ops import moe as moe_ops

    def raw(scores, bias, k, scaling=1.0, masked_pick=False):
        _, experts = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        return experts.astype(jnp.int32), chosen * scaling

    moe_ops.sigmoid_topk = raw


FAULTS = {
    "window_plus_one": window_plus_one,
    "ring_one_short": ring_one_short,
    "no_yarn": no_yarn,
    "gates_not_renormalised": gates_not_renormalised,
}


def plant(name):
    FAULTS[name]()
