"""Faults a test plants UNDER the timed path of the gated window-and-
full-attention / softmax-routed-experts cell, to see ``correct`` come
out false (reached only through a test's rehearsal, as ``faults.py``'s
are).  Each is a shortcut the mechanism it breaks is most tempted by;
the reference imports nothing from the program and is untouched."""


def _with(module_cls, **fields):
    """``module_cls.__call__`` with ``fields`` set on the module first
    (the weights it does not read are still in the tree, unused)."""
    call = module_cls.__call__

    def patched(self, *args, **kwargs):
        for name, value in fields.items():
            object.__setattr__(self, name, value(self))
        return call(self, *args, **kwargs)

    module_cls.__call__ = patched


def gate_skipped():
    """The heads' outputs go to the output projection ungated."""
    import dataclasses

    from tensorflowonspark_tpu.models import transformer as tr

    _with(tr.Attention,
          cfg=lambda m: dataclasses.replace(m.cfg, gating=""))


def full_rotation():
    """The full layers rotate all of ``head_dim``, as if the published
    ``partial_rotary_factor`` of 0.5 were not there (YaRN's frequencies
    reckoned over the whole head too)."""
    from tensorflowonspark_tpu.models import transformer as tr

    tr.TransformerConfig.rotary_of = lambda self, layer: self.head_dim


def scaling_left_at_one():
    """The routed experts' weights are not multiplied by
    ``moe_routed_scaling_factor``."""
    from tensorflowonspark_tpu.models import moe

    _with(moe.SigmoidMoE, scaling=lambda m: 1.0)


def shared_expert_left_out():
    """The shared expert is not added."""
    from tensorflowonspark_tpu.models import moe

    _with(moe.SigmoidMoE, shared_experts=lambda m: 0)


def window_513():
    """Every sliding layer sees 513 keys, one more than its window of
    512 (the off-by-one of ``j >= i - W`` for ``j > i - W``); the ring
    is sized for what the layer sees, so nothing else gives it away."""
    from tensorflowonspark_tpu.models import transformer as tr

    window_of = tr.TransformerConfig.window_of

    def wider(self, layer):
        w = window_of(self, layer)
        return w + 1 if w else 0

    tr.TransformerConfig.window_of = wider


FAULTS = {
    "gate_skipped": gate_skipped,
    "full_rotation": full_rotation,
    "scaling_left_at_one": scaling_left_at_one,
    "shared_expert_left_out": shared_expert_left_out,
    "window_513": window_513,
}


def plant(name):
    FAULTS[name]()
